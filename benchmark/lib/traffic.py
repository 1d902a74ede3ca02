"""The one general traffic generator.

A traffic mix is a data file (``benchmark/traffic/<name>.json``); this
module turns its ``requests`` group and a seed into request specs.
Nothing here knows a cell by name, so a later PR adds a mix by adding a
file.

``requests`` (serving mixes)::

    {"prompt_len": {"dist": "log_uniform", "min": 32, "max": 768, "strata": 16},
     "max_new_tokens": {"dist": "log_uniform", "min": 16, "max": 256},
     "sampling": [{"temperature": 0.0}, {"temperature": 0.8, "top_p": 0.95}]}

``sampling`` is cycled request by request (entry ``i % len``), so
"every second request greedy" is a two-entry list.  Lengths are clipped
so that prompt + output fits ``max_len``.

A length is an independent draw from its distribution.  With
``"strata": n`` the draws are stratified: every ``n`` consecutive draws
take one quantile from each of the ``n`` equal slices of 0..1, uniform
within its slice, in an order the seed shuffles.  Each draw still has
the distribution's own law and can take any length in ``min..max``; what
the strata remove is most of the seed-to-seed swing in the total work
of a hundred-odd heavy-tailed requests.  They do not fix the lengths: a
mix must not be shaped to what a runner happens to have warmed.

``arrivals``: ``{"process": "closed", "clients": 24}``, a closed loop
whose clients each submit their next request when their last completes.
The open-loop processes (Poisson, bursts) and shared prefixes come with
the cells that need them (PERF.md section 7; the arithmetic to copy is
``serving/replay.py``'s).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterator

import numpy as np


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    index: int  # position in its stream
    prompt: tuple  # token ids
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0


def length_at(spec: dict, u: float) -> int:
    """The length at quantile ``u`` (0..1) of the distribution ``spec``."""
    dist = spec.get("dist", "fixed")
    if dist == "fixed":
        return int(spec["value"])
    lo, hi = int(spec["min"]), int(spec["max"])
    if not 1 <= lo <= hi:
        raise ValueError(f"bad length range {spec}")
    if dist != "log_uniform":
        raise ValueError(f"unknown length distribution {dist!r}")
    x = math.exp(math.log(lo) + u * (math.log(hi + 1) - math.log(lo)))
    return int(min(hi, max(lo, math.floor(x))))


class LengthSource:
    """Lengths of one field of a stream: independent draws, stratified
    where the field says ``strata`` (module docstring)."""

    def __init__(self, rng: np.random.Generator, spec: dict):
        self._rng, self._spec = rng, spec
        self._strata = int(spec.get("strata", 0))
        self._cycle: list = []

    def next(self) -> int:
        if self._strata <= 0:
            return length_at(self._spec, float(self._rng.uniform()))
        if not self._cycle:
            n = self._strata
            within = self._rng.uniform(size=n)
            self._cycle = [
                length_at(self._spec, (k + within[k]) / n)
                for k in self._rng.permutation(n)
            ]
        return self._cycle.pop()


def length_support(spec: dict) -> list:
    """Every length the field can take."""
    if spec.get("dist", "fixed") == "fixed":
        return [int(spec["value"])]
    return list(range(int(spec["min"]), int(spec["max"]) + 1))


def request_stream(
    requests: dict, seed: int, stream: int, *, vocab: int, max_len: int
) -> Iterator[RequestSpec]:
    """An endless, seeded stream of requests: the same ``(seed,
    stream)`` always gives the same requests.  The serving runner draws
    every request of a run from stream 0, whichever client sends it."""
    rng = np.random.default_rng([seed, stream])
    sampling = requests.get("sampling") or [{}]
    prompt_lens = LengthSource(rng, requests["prompt_len"])
    new_lens = LengthSource(rng, requests["max_new_tokens"])
    index = 0
    while True:
        prompt_len = max(1, min(prompt_lens.next(), max_len - 1))
        new = max(1, min(new_lens.next(), max_len - prompt_len))
        prompt = tuple(int(t) for t in rng.integers(0, vocab, prompt_len))
        mode = sampling[index % len(sampling)]
        yield RequestSpec(
            index=index,
            prompt=prompt,
            max_new_tokens=new,
            temperature=float(mode.get("temperature", 0.0)),
            top_k=int(mode.get("top_k", 0)),
            top_p=float(mode.get("top_p", 1.0)),
        )
        index += 1
