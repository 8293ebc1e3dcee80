"""Percentiles and the load generator: the same seed, the same load."""

import math

import numpy as np
import pytest

from benchmarks.harness import loadgen, stats

TRAFFIC = {
    "arrival": {"process": "closed", "clients": 4},
    "prompt_len": {"dist": "loguniform", "lo": 32, "hi": 512},
    "output_len": {"dist": "loguniform", "lo": 32, "hi": 256},
    "shared_prefix": {"share": 0.5, "len": 16},
}


@pytest.mark.parametrize("q", [0, 25, 50, 90, 99, 100])
def test_percentile_is_numpys(q):
    xs = np.random.RandomState(3).lognormal(size=257).tolist()
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_spread_and_summary():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0]
    assert stats.spread(xs) == pytest.approx((13 - 11) / 12)
    s = stats.summary(list(range(1000)), (50, 90, 99))
    assert s["n"] == 1000 and s["beyond_p90"] == 100 and s["beyond_p99"] == 10
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_request_stream_repeats_from_its_seed():
    a = loadgen.RequestStream(TRAFFIC, 7, 50257)
    b = loadgen.RequestStream(TRAFFIC, 7, 50257)
    c = loadgen.RequestStream(TRAFFIC, 8, 50257)
    ra = [a.next() for _ in range(50)]
    assert ra == [b.next() for _ in range(50)]
    assert ra != [c.next() for _ in range(50)]
    for prompt, n_out in ra:
        assert 32 <= len(prompt) <= 512 and 32 <= n_out <= 256
        assert all(0 <= t < 50257 for t in prompt)
    shared = sum(p[:16] == a.prefix for p, _ in ra)
    assert 10 <= shared <= 40                      # about half of 50


def test_length_means():
    rng = np.random.RandomState(0)
    spec = TRAFFIC["output_len"]
    xs = [loadgen.draw_length(rng, spec) for _ in range(20000)]
    # a log-uniform draw on [lo, hi] has mean (hi - lo) / ln(hi / lo)
    assert np.mean(xs) == pytest.approx((256 - 32) / math.log(256 / 32),
                                        rel=0.03)
    assert loadgen.longest(spec) == 256
    assert loadgen.draw_length(rng, {"dist": "fixed", "value": 9}) == 9


def test_a_block_holds_the_same_lengths_whatever_the_seed():
    mix = {**TRAFFIC, "arrival": {"process": "closed", "clients": 64}}
    blocks = []
    for seed in (1, 2, 3):
        s = loadgen.RequestStream(mix, seed, 1000)
        wave = [s.next(residual=True) for _ in range(64)]
        later = [s.next() for _ in range(64)]
        blocks.append((sorted(len(p) for p, _ in wave),
                       sorted(n for _, n in wave),
                       sorted(n for _, n in later),
                       [n for _, n in wave]))
    assert blocks[0][:3] == blocks[1][:3] == blocks[2][:3]
    assert blocks[0][3] != blocks[1][3]            # in another order
    # one length from every slice: the block's mean is the distribution's
    assert np.mean(blocks[0][2]) == pytest.approx(
        (256 - 32) / math.log(256 / 32), rel=0.02)


def test_first_wave_is_the_steady_state_and_ends_in_no_set_up():
    spec = TRAFFIC["output_len"]
    left, levels = loadgen.residual_levels(spec)
    assert left.min() == 2 and left.max() <= 256
    assert levels[-1] == pytest.approx(1.0) and np.all(np.diff(levels) > 0)
    # against plain sampling: a request met in proportion to its length, at
    # a uniform point of it
    rng = np.random.RandomState(0)
    n = np.array([loadgen.draw_length(rng, spec) for _ in range(200000)])
    n = rng.choice(n, size=200000, p=n / n.sum())
    want = 1 + np.maximum(1, np.ceil((n - 1) * rng.uniform(size=n.size)))
    for q in (0.1, 0.5, 0.9):
        got = left[np.searchsorted(levels, q, side="right")]
        assert got == pytest.approx(np.quantile(want, q), rel=0.03, abs=1)
    one = {"dist": "fixed", "value": 1}
    assert set(loadgen.residual_levels(one)[0]) == {1}


@pytest.mark.parametrize("spec,cv", [
    ({"process": "poisson", "rate_per_s": 50.0}, 1.0),
    ({"process": "gamma", "rate_per_s": 50.0, "cv": 3.0}, 3.0)])
def test_open_loop_rate_and_burstiness(spec, cv):
    gaps = loadgen.arrival_gaps(np.random.RandomState(5), spec)
    xs = np.array([next(gaps) for _ in range(200000)])
    assert xs.mean() == pytest.approx(1 / 50.0, rel=0.03)
    assert xs.std() / xs.mean() == pytest.approx(cv, rel=0.05)


def test_arrivals_schedule_repeats_and_closed_loop():
    spec = {"process": "gamma", "rate_per_s": 100.0, "cv": 3.0}
    a, b = loadgen.Arrivals(spec, 3), loadgen.Arrivals(spec, 3)
    due_a = a.due_by(0.5) + a.due_by(1.0)
    assert due_a == b.due_by(1.0) and due_a == sorted(due_a)
    assert a.first_wave() == 0 and a.on_complete(1.0) is None
    assert a.next_due() > 1.0
    c = loadgen.Arrivals({"process": "closed", "clients": 8}, 3)
    assert c.first_wave() == 8 and c.due_by(10.0) == []
    assert c.on_complete(2.5) == 2.5 and c.next_due() is None
    assert math.isfinite(a.next_due())
