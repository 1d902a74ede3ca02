"""Runner ``serve_loop``: traffic against ``serving/server.py::LMServer``.

One process: the server's worker thread owns the engine, this thread
is every client.  A closed loop keeps ``clients`` requests outstanding
(each client submits its next request when its last completes).
Requests, lengths and sampling modes come from the traffic file
through ``lib/traffic.py``.

Clocks.  A request is *due* when its client became free.
``Completion.ttft_s`` starts
at the scheduler's pick-up, so the benchmark adds what it can see from
outside: the time from the due instant to the return of
``LMServer.submit``.  What then still lies between the two clocks (the
wait in the server's intake queue) shows in ``intake_wait_p95_ms``.
Completions are noticed by polling the handles, so client-side stamps
are up to ``POLL_S`` late.

Phases: build weights on the device from ``--seed`` in one jitted
call, start the server, one short request (compiles the engine's two
programs), the key schedule of every output length the mix can draw,
``warmup_s`` of the loop, then the window; a traced run goes on for
``trace_s`` under ``jax.profiler`` after the window has closed.  Then
the outstanding requests finish, a seeded sample of the window's
requests is served again one at a time (the streams have to be
byte-identical), the server drains, and a seeded sample of the
window's greedy streams is held against the configuration's plain
reference (:func:`reference_margins`).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time

import numpy as np

from benchmark.lib import cells, device, stats, trace_reduce, traffic
from benchmark.lib.result import RunOptions, RunResult
from benchmark.lib.window_hook import MARKER_IDLE_S, SYNC_MARKER, start_profiler

POLL_S = 0.0005
RESULT_TIMEOUT_S = 300.0
FIRST_REQUEST_TIMEOUT_S = 1100.0  # the first run of a cell compiles
WORKER_THREAD = "serve-worker"  # serving/server.py names its thread


class WorkerDied(RuntimeError):
    """The server's worker thread ended while requests were outstanding
    (a program that does not compile or fit ends it); its handles would
    never resolve, so the run stops instead of waiting."""


def _worker_alive() -> bool:
    return any(
        t.name == WORKER_THREAD and t.is_alive() for t in threading.enumerate()
    )


def _wait(handle, timeout_s: float):
    """``handle.result`` that notices a dead worker within a second."""
    deadline = time.perf_counter() + timeout_s
    while not handle.done():
        if not _worker_alive():
            raise WorkerDied("the serve worker thread is gone")
        if time.perf_counter() > deadline:
            raise TimeoutError(f"request not finished in {timeout_s}s")
        time.sleep(0.05)
    return handle.result(0)


@dataclasses.dataclass
class _Sent:
    """One request: from its due instant to the instant this thread
    noticed that it completed."""

    client: int
    handle: object
    spec: traffic.RequestSpec
    rid: int
    due: float
    submitted: float  # LMServer.submit returned
    noticed: float = 0.0
    outcome: object = None  # Completion, or the exception its handle raised

    @property
    def served(self) -> bool:
        return (
            not isinstance(self.outcome, Exception)
            and self.outcome.finish_reason in ("length", "eos")
            and len(self.outcome.tokens) > 0
        )


def reference_margins(reference, params, num_heads: int, max_len: int, streams) -> list:
    """How far each served greedy token is from the plain reference's
    choice.  ``streams`` are ``(prompt, tokens)`` of greedy requests.
    The reference (float32, products at precision ``highest``, on the
    weights the benchmark made, not on whatever the engine holds) reads
    the prompt and the served tokens in one pass, padded to ``max_len``
    so that one program serves every length (attention is causal, the
    padding changes nothing before it).  Per served token: the
    reference's highest logit at that position minus its logit of the
    served token, over the standard deviation of its logits there.  0
    where the engine chose the reference's argmax; small where the two
    were near-tied and bf16 arithmetic decided (PR 21: streams leave
    solo ``generate()`` for that reason); of the order of 1 and more
    where the served token has little to do with the model."""
    import jax
    import jax.numpy as jnp

    forward = jax.jit(lambda p, t: reference.forward(p, t, num_heads=num_heads))
    margins = []
    for prompt, tokens in streams:
        text = list(prompt) + list(tokens)
        padded = np.zeros((1, max_len), np.int32)
        padded[0, : len(text) - 1] = text[:-1]
        logits = np.asarray(forward(params, jnp.asarray(padded))[0], np.float32)
        for i, token in enumerate(tokens):
            row = logits[len(prompt) - 1 + i]
            margins.append(float((row.max() - row[token]) / row.std()))
    return margins


def _build_model(cell, serve: dict, opts: RunOptions):
    """The configuration's model in the type it is served in, and its
    weights made on the device in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_models_tpu.harness.config import get_config
    from distributed_tensorflow_models_tpu.models import get_model

    kwargs = {**cell.config["overrides"]["model_kwargs"], "dropout_rate": 0.0}
    model = get_model(
        get_config(cell.config["program_config"]).model,
        **kwargs,
        dtype=jnp.dtype(serve["dtype"]),
    )
    sample = jnp.zeros((1, 4), jnp.int32)
    init = jax.jit(lambda key: model.init(key, sample)["params"])
    params = jax.block_until_ready(init(jax.random.key(opts.seed)))
    return model, params, kwargs


def run(cell, opts: RunOptions) -> RunResult:
    import jax

    from distributed_tensorflow_models_tpu.serving.engine import InferenceEngine
    from distributed_tensorflow_models_tpu.serving.server import LMServer
    from distributed_tensorflow_models_tpu.telemetry import registry as reglib

    serve = cell.traffic["serve"]
    requests = cell.traffic["requests"]
    arrivals = cell.traffic["arrivals"]
    if arrivals["process"] != "closed":
        raise ValueError(f"serve_loop runs closed loops, not {arrivals['process']!r}")
    model, params, model_kwargs = _build_model(cell, serve, opts)
    vocab, max_len = int(model_kwargs["vocab_size"]), int(model_kwargs["max_len"])
    engine_kwargs = dict(serve["engine"])
    built: dict = {}

    def factory():
        built["engine"] = InferenceEngine(model, params, **engine_kwargs)
        return built["engine"]

    registry = reglib.MetricsRegistry()
    server = LMServer(factory, registry=registry, process_index=0)
    server.start()

    n_clients = int(arrivals["clients"])
    warmup_s = float(serve["warmup_s"])
    trace_s = float(serve["trace_s"]) if opts.trace else 0.0
    # Starting the profiler stalls this thread (every client) for
    # seconds: the loop refills under the running profiler before the
    # marker opens the traced sub-window.
    settle_s = float(serve["trace_settle_s"]) if opts.trace else 0.0
    # One stream for the whole run: the clients take the next request
    # of it as they become free.
    stream = traffic.request_stream(requests, opts.seed, 0, vocab=vocab, max_len=max_len)
    next_rid = iter(range(1, 1 << 30))
    outstanding: list = []
    done: list = []

    def submit(client, due):
        spec = next(stream)
        rid = next(next_rid)
        handle = server.submit(
            spec.prompt, spec.max_new_tokens, temperature=spec.temperature,
            top_k=spec.top_k, top_p=spec.top_p, seed=opts.seed, request_id=rid,
        )
        outstanding.append(
            _Sent(client, handle, spec, rid, due, time.perf_counter())
        )

    def harvest(now):
        freed = []
        for c in outstanding[:]:
            if c.handle.done():
                outstanding.remove(c)
                try:
                    c.outcome = c.handle.result(0)
                except Exception as e:  # noqa: BLE001 - counted as failed
                    c.outcome = e
                c.noticed = now
                done.append(c)
                freed.append(c.client)
        return freed

    tracing = False
    try:
        # One short sampled request: compiles the engine's two programs
        # and the per-request key derivation.
        first = server.submit(
            [1, 2, 3, 4, 5], 6, temperature=0.8, seed=opts.seed,
            request_id=next(next_rid),
        )
        _wait(first, FIRST_REQUEST_TIMEOUT_S)
        # The engine derives a sampled request's keys with a program of
        # its own for every distinct ``max_new_tokens`` (a split into that
        # many keys), compiled on the worker when the first such request
        # is admitted: with 16 possible lengths, 8 of 9 runs had such
        # compiles inside the window, the slowest of them 17% under the
        # others (my chip runs, PR 22).
        # Users send any length, so every length the mix can draw is
        # warmed through the engine's own entry point, and what that
        # costs is set-up: a program-side fix (one schedule of fixed
        # length) shows in ``setup_s``.
        new_lengths = traffic.length_support(requests["max_new_tokens"])
        t_keys = time.perf_counter()
        if any(m.get("temperature", 0.0) > 0 for m in requests.get("sampling") or [{}]):
            for n in new_lengths:
                built["engine"].request_keys(jax.random.key(opts.seed), n)
        key_warmup_s = time.perf_counter() - t_keys

        t_loop = time.perf_counter()
        t0 = t_loop + warmup_s
        t1 = t0 + opts.seconds
        t_end = t1 + settle_s + trace_s
        t_marker = None
        snap0 = snap1 = None
        seen: dict = {}
        last_alive_check = t_loop
        trace_dir = os.path.join(opts.workdir, "profile")
        for i in range(n_clients):
            submit(i, time.perf_counter())
        while True:
            now = time.perf_counter()
            if snap0 is None and now >= t0:
                snap0 = registry.snapshot()
                seen["compiles_t0"] = opts.compiles.total()
                seen["setup_s"] = opts.since_start()
                t0 = now = time.perf_counter()
                t1 = t0 + opts.seconds
                t_end = t1 + settle_s + trace_s
            if snap1 is None and now >= t1:
                snap1 = registry.snapshot()
                seen["compiles_t1"] = opts.compiles.total()
                seen["temp_bytes"] = device.program_temp_bytes(opts.devices[0].client)
                t1 = now
                if trace_s > 0:
                    start_profiler(trace_dir)
                    tracing = True
                    t_marker = time.perf_counter() + settle_s
                    t_end = t_marker + trace_s
            if tracing and "marker_mono" not in seen and now >= t_marker:
                # No sync here (the worker owns the device); the marker only
                # joins the clocks and opens the traced sub-window.
                seen["marker_mono"] = time.perf_counter()
                with jax.profiler.TraceAnnotation(SYNC_MARKER):
                    time.sleep(MARKER_IDLE_S)
                t_end = time.perf_counter() + trace_s
            if now >= t_end and snap1 is not None:
                break
            if now - last_alive_check > 1.0:
                last_alive_check = now
                if not _worker_alive():
                    raise WorkerDied("the serve worker thread is gone")
            for client in harvest(now):
                submit(client, now)
            time.sleep(POLL_S)
        if tracing:
            jax.profiler.stop_trace()
            tracing = False
        # Let what is outstanding finish (outside the window).
        deadline = time.perf_counter() + RESULT_TIMEOUT_S
        while outstanding and time.perf_counter() < deadline and _worker_alive():
            harvest(time.perf_counter())
            time.sleep(POLL_S)

    except BaseException:
        # Leave nothing running: the profiler, then the server (a dead
        # worker joins at once; a live one serves out its backlog).
        if tracing:
            jax.profiler.stop_trace()
        try:
            server.drain(timeout=30.0)
        except Exception:  # noqa: BLE001 - the first error is the one to report
            pass
        raise

    in_window = [r for r in done if stats.in_window(r.noticed, t0, t1)]
    ok = [r for r in in_window if r.served]
    failed = len(in_window) - len(ok) + len(outstanding)

    # Streams do not depend on what they were batched with: a seeded
    # sample of the window's requests, served again one at a time.
    rng = np.random.default_rng([opts.seed, 0x5A])
    replay_n = min(int(serve["replay_requests"]), len(ok))
    replay_same = 0
    for i in rng.choice(len(ok), size=replay_n, replace=False) if replay_n else []:
        spec, comp = ok[int(i)].spec, ok[int(i)].outcome
        key = (
            jax.random.fold_in(jax.random.key(opts.seed), ok[int(i)].rid)
            if spec.temperature > 0 else None
        )
        again = _wait(
            server.submit(
                spec.prompt, spec.max_new_tokens, temperature=spec.temperature,
                top_k=spec.top_k, top_p=spec.top_p, rng=key,
                request_id=next(next_rid),
            ),
            RESULT_TIMEOUT_S,
        )
        replay_same += list(again.tokens) == list(comp.tokens)
    server.drain()
    report = server.stats()
    compile_counts = built["engine"].compile_counts()

    # The served greedy streams against the configuration's plain
    # reference, on the weights the benchmark made.
    oracle = serve["reference"]
    greedy = [r for r in ok if r.spec.temperature == 0]
    picked = rng.choice(
        len(greedy), size=min(int(oracle["requests"]), len(greedy)), replace=False
    )
    margins = reference_margins(
        cells.load_module("references", cell.config["reference"]),
        params, int(model_kwargs["num_heads"]), max_len,
        [(greedy[int(i)].spec.prompt, list(greedy[int(i)].outcome.tokens)) for i in picked],
    )
    far = sum(m > float(oracle["margin_tol"]) for m in margins)
    mix_has_greedy = any(
        m.get("temperature", 0.0) == 0 for m in requests.get("sampling") or [{}]
    )

    window_s = t1 - t0
    tokens = sum(len(r.outcome.tokens) for r in ok)
    ttft_ms = [1e3 * (r.outcome.ttft_s + (r.submitted - r.due)) for r in ok]
    tpot_ms = [1e3 * r.outcome.tpot_s for r in ok if len(r.outcome.tokens) > 1]
    intake_ms = [
        1e3 * (
            (r.noticed - r.due) - r.outcome.ttft_s
            - r.outcome.tpot_s * (len(r.outcome.tokens) - 1)
        )
        for r in ok
    ]
    lateness_ms = [1e3 * (r.submitted - r.due) for r in ok]
    checks = {
        "no_failed_or_shed": failed == 0 and len(ok) > 0,
        "two_programs": tuple(compile_counts) == (1, 1),
        "arena_fsck_clean": not report.get("fsck_errors"),
        "replayed_streams_identical": replay_n > 0 and replay_same == replay_n,
        "greedy_tokens_near_reference": far == 0 and (bool(margins) or not mix_has_greedy),
        "no_compile_in_window": seen["compiles_t1"] == seen["compiles_t0"],
    }

    trace = None
    if opts.trace and trace_s > 0:
        events = registry.trace.events()
        spans = [
            (e["name"], e["ts_mono"], e["ts_mono"] + e["dur_s"])
            for e in events if e["ph"] == "X"
        ]
        trace = trace_reduce.reduce_trace(
            trace_dir, marker=SYNC_MARKER, marker_stamp_s=seen.get("marker_mono"),
            program_spans=spans, clip_after_marker_s=MARKER_IDLE_S / 2,
        )
        checks["device_ran_in_trace"] = trace is not None and trace["busy_s"] > 0

    end_to_end = {
        # Counted by the benchmark itself from the streams handed back:
        # output tokens of the requests completed inside the window.
        "serve_tokens_per_s": stats.rate(tokens, t0, t1),
        "serve_ttft_p95_ms": stats.percentile(ttft_ms, 95),
        "serve_tpot_p95_ms": stats.percentile(tpot_ms, 95),
        "serve_ttft_p50_ms": stats.percentile(ttft_ms, 50),
        "serve_tpot_p50_ms": stats.percentile(tpot_ms, 50),
        "setup_s": seen["setup_s"],
    }
    ctx = {
        "chips": len(opts.devices),
        "device_kind": opts.devices[0].device_kind,
        "window_s": window_s,
        "snap0": snap0,
        "snap1": snap1,
        "decode_burst": int(engine_kwargs.get("decode_burst", 1)),
        "intake_ms": intake_ms,
        "ttft_ms": ttft_ms,
        "tpot_ms": tpot_ms,
        "compiles_in_window": seen["compiles_t1"] - seen["compiles_t0"],
        "config": cell.config,
        "trace": trace,
        "program_temp_bytes": seen.get("temp_bytes", 0),
    }
    notes = {
        "key_schedules_warmed": len(new_lengths),
        "key_warmup_s": key_warmup_s,
        "requests_completed_in_window": len(ok),
        "ttft_samples": len(ttft_ms),
        "tpot_samples": len(tpot_ms),
        "output_tokens_of_requests_completed_in_window": tokens,
        "tokens_emitted_in_window": stats.delta(snap1, snap0, "serve/tokens"),
        "reference_margin_max": max(margins) if margins else None,
        "reference_margins_over_tol": [far, len(margins)],
        "window_s": window_s,
        "generator_lateness_p95_ms": stats.percentile(lateness_ms, 95),
        "replayed": [replay_same, replay_n],
        "compile_counts": list(compile_counts),
        "prompt_len_mean": float(np.mean([len(r.spec.prompt) for r in ok])) if ok else None,
        "output_len_mean": tokens / len(ok) if ok else None,
        "serve_ttft_p50_ms": end_to_end["serve_ttft_p50_ms"],
        "serve_tpot_p50_ms": end_to_end["serve_tpot_p50_ms"],
    }
    return RunResult(
        checks=checks,
        attempted=len(in_window) + len(outstanding),
        failed=failed,
        end_to_end={k: v for k, v in end_to_end.items() if v is not None},
        ctx=ctx,
        notes=notes,
    )
