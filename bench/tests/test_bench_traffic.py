"""The benchmark's traffic generator: seeded, fixed work, lengths in the set."""

import numpy as np
import pytest

from bench import cells, traffic

MIXES = ["decode_heavy", "chat_bursty"]


def _mix(name):
    return cells._json("traffic", name)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_stream(name):
    a = traffic.stream(_mix(name), 2**33 + 5, 1000, 30.0)
    b = traffic.stream(_mix(name), 2**33 + 5, 1000, 30.0)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    """Each block of ``set_size`` requests is the same set of sizes in
    another order, and the arrivals do not move with the seed."""
    mix = _mix(name)
    n = mix["set_size"]
    a = traffic.stream(mix, 1, 1000, 30.0)
    b = traffic.stream(mix, 2, 1000, 30.0)
    sizes = lambda s: sorted((len(r.prompt), r.max_new) for r in s[:n])
    assert sizes(a) == sizes(b)
    assert [r.due for r in a] == [r.due for r in b]
    assert [len(r.prompt) for r in a[:n]] != [len(r.prompt) for r in b[:n]]
    assert any((x.prompt[:4] != y.prompt[:4]).any() for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_prompts_lie_in_the_set_and_outputs_in_range(name):
    mix = _mix(name)
    s = traffic.stream(mix, 7, 1000, 30.0)
    assert {len(r.prompt) for r in s} <= set(mix["prompt_set"])
    outs = [r.max_new for r in s]
    assert min(outs) >= mix["output"]["lo"]
    assert max(outs) <= mix["output"]["hi"]


def test_round_up_into_the_set():
    got = traffic.round_up(np.array([1, 256, 257, 700, 2048, 5000]),
                           [1024, 256, 2048, 512])
    assert got.tolist() == [256, 256, 512, 1024, 2048, 2048]


@pytest.mark.parametrize("kind", ["poisson", "bursty"])
def test_arrival_rate_is_the_mix_rate(kind):
    mix = {"arrivals": kind, "rate": 50.0, "burst_factor": 4.0,
           "burst_fraction": 0.2, "burst_s": 0.5}
    t = traffic.arrivals(mix, np.random.default_rng(0), 400.0)
    assert (np.diff(t) >= 0).all() and t[-1] < 400.0
    assert abs(len(t) / 400.0 - 50.0) < 50.0 * 0.08


def test_bursts_are_burstier_than_poisson():
    """Counts per 0.25 s vary more under the two-state process."""
    rng = np.random.default_rng(3)
    base = {"rate": 50.0, "burst_factor": 4.0, "burst_fraction": 0.2,
            "burst_s": 0.5}
    disp = {}
    for kind in ("poisson", "bursty"):
        t = traffic.arrivals(dict(base, arrivals=kind), rng, 400.0)
        c = np.bincount((t / 0.25).astype(int))
        disp[kind] = c.var() / c.mean()
    assert disp["bursty"] > 1.5 * disp["poisson"]
