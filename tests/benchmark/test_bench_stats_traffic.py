"""Percentile and window arithmetic, and the traffic generator."""

import itertools

import numpy as np
import pytest

import bench_testlib  # noqa: F401
from benchmark.lib import stats, traffic

MIX = {
    "prompt_len": {"dist": "log_uniform", "min": 32, "max": 768},
    "max_new_tokens": {"dist": "log_uniform", "min": 16, "max": 256},
    "sampling": [{"temperature": 0.0}, {"temperature": 0.8, "top_p": 0.95}],
}


@pytest.mark.parametrize("q", [0, 25, 50, 95, 99, 100])
def test_percentile_matches_numpy(q):
    xs = np.random.default_rng(3).exponential(size=201)
    assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))


def test_percentile_edges():
    assert stats.percentile([], 95) is None
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_window_arithmetic():
    assert stats.rate(300, 10.0, 20.0) == 30.0
    with pytest.raises(ValueError):
        stats.rate(1, 5.0, 5.0)
    assert stats.in_window(20.0, 10.0, 20.0) and not stats.in_window(20.1, 10.0, 20.0)
    assert stats.delta({"a": 5.0}, {"a": 2.0}, "a") == 3.0
    assert stats.delta({"a": 5.0}, {}, "a") == 5.0
    assert stats.spread([10.0, 10.0, 10.0]) == 0.0
    assert stats.spread([9.0, 10.0, 11.0]) == pytest.approx(0.1)


def _take(seed, stream, n=50, **kw):
    gen = traffic.request_stream(MIX, seed, stream, vocab=50257, max_len=1024, **kw)
    return list(itertools.islice(gen, n))


def test_requests_are_deterministic_in_seed_and_stream():
    assert _take(1, 0) == _take(1, 0)
    assert _take(1, 0) != _take(2, 0)
    assert _take(1, 0) != _take(1, 1)


def test_requests_respect_the_mix():
    reqs = _take(5, 3, n=400)
    for i, r in enumerate(reqs):
        assert 32 <= len(r.prompt) <= 768
        assert 1 <= r.max_new_tokens <= 256
        assert len(r.prompt) + r.max_new_tokens <= 1024
        assert all(0 <= t < 50257 for t in r.prompt[:8])
        assert r.temperature == (0.0 if i % 2 == 0 else 0.8)
        assert r.top_p == (1.0 if i % 2 == 0 else 0.95)
    # Log-uniform: the median sits near the geometric mean of the range.
    med = float(np.median([len(r.prompt) for r in reqs]))
    assert 100 < med < 250, med


def test_strata_steady_the_work_and_fix_no_length():
    spec = {"dist": "log_uniform", "min": 16, "max": 256, "strata": 16}
    mix = {"prompt_len": {"dist": "log_uniform", "min": 32, "max": 768, "strata": 16},
           "max_new_tokens": spec}

    def take(seed, n=48):
        gen = traffic.request_stream(mix, seed, 0, vocab=50257, max_len=1024)
        return list(itertools.islice(gen, n))

    edges = [traffic.length_at(spec, k / 16) for k in range(17)]
    seen = set()
    for seed in range(40):
        reqs = take(seed)
        for block in range(3):
            news = sorted(r.max_new_tokens for r in reqs[16 * block:16 * block + 16])
            # One draw from each sixteenth of the distribution...
            assert all(edges[k] <= news[k] <= edges[k + 1] for k in range(16)), news
        seen.update(r.max_new_tokens for r in reqs)
    # ...and any length of the range can come: the mix is not 16 lengths.
    assert len(seen) > 150 and min(seen) == 16 and max(seen) >= 250
    assert [len(r.prompt) for r in take(1)] != [len(r.prompt) for r in take(2)]
    # The total work of a block swings far less than that of independent draws.
    free = {**mix, "max_new_tokens": {k: v for k, v in spec.items() if k != "strata"}}

    def block_sums(m):
        out = []
        for seed in range(40):
            gen = traffic.request_stream(m, seed, 0, vocab=50257, max_len=1024)
            out.append(sum(r.max_new_tokens for r in itertools.islice(gen, 16)))
        return np.std(out) / np.mean(out)

    assert block_sums(mix) < 0.3 * block_sums(free)


def test_length_quantiles_and_support():
    spec = {"dist": "log_uniform", "min": 32, "max": 768}
    assert traffic.length_at(spec, 0.0) == 32
    assert traffic.length_at(spec, 0.999999) == 768
    assert traffic.length_at({"dist": "fixed", "value": 7}, 0.3) == 7
    with pytest.raises(ValueError):
        traffic.length_at({"dist": "uniform", "min": 1, "max": 9}, 0.5)
    # Every length the field can take: what a runner has to have warmed.
    assert traffic.length_support({"dist": "log_uniform", "min": 16, "max": 256, "strata": 16}) == list(range(16, 257))
    assert traffic.length_support({"dist": "fixed", "value": 9}) == [9]


def test_log_uniform_is_log_uniform():
    spec = {"dist": "log_uniform", "min": 16, "max": 256, "strata": 16}
    gen = traffic.request_stream(
        {"prompt_len": {"dist": "fixed", "value": 8}, "max_new_tokens": spec},
        3, 0, vocab=100, max_len=1024,
    )
    news = np.array([r.max_new_tokens for r in itertools.islice(gen, 3200)])
    # Equal mass per octave: 16-31, 32-63, 64-127, 128-256.
    shares = [np.mean((news >= lo) & (news < hi)) for lo, hi in ((16, 32), (32, 64), (64, 128), (128, 257))]
    assert all(0.22 < s < 0.28 for s in shares), shares


def test_served_and_failed_requests_are_told_apart():
    import types

    from benchmark.lib import cells

    runner = cells.load_module("runners", "serve_loop")

    def sent(n, reason="length"):
        outcome = types.SimpleNamespace(tokens=list(range(n)), tpot_s=0.1, finish_reason=reason)
        return runner._Sent(0, None, None, 1, 0.0, 0.0, noticed=1.0, outcome=outcome)

    assert sent(3).served and not sent(0).served and not sent(3, "shed").served
    failed = runner._Sent(0, None, None, 1, 0.0, 0.0, noticed=1.0, outcome=RuntimeError("x"))
    assert not failed.served


def test_serve_loop_runs_closed_loops_only():
    import types

    from benchmark.lib import cells

    runner = cells.load_module("runners", "serve_loop")
    cell = types.SimpleNamespace(
        traffic={"serve": {}, "requests": {}, "arrivals": {"process": "poisson", "rate_per_s": 5}}
    )
    with pytest.raises(ValueError, match="closed"):
        runner.run(cell, None)
