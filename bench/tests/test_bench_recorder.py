"""The recorder: exact percentiles, first tokens stamped after the host's
wait, inter-token gaps inside the window, and gen_kernel_ms."""

import math
import time

import numpy as np
import pytest

from bench import recorder as R
from bench.loops import kernel_suite


@pytest.mark.parametrize("q", [50, 95, 99])
def test_percentile_is_exact(q):
    x = np.random.default_rng(0).lognormal(0, 1, 1001)
    assert R.percentile(x, q) == pytest.approx(np.sort(x)[int(q * 10)])


def test_first_token_is_stamped_at_the_next_hook(monkeypatch):
    """The engine reports the first token before the host waits for it;
    the stamp is the time of the next hook, which follows the wait."""
    clock = iter([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0])
    monkeypatch.setattr(R.time, "perf_counter", lambda: next(clock))
    rec = R.Recorder({0: 10})
    rec.on_submit(0)                   # t=1
    rec.on_admit(0, 10)                # t=2
    rec.on_token(0)                    # t=3: held, not stamped
    assert rec.tokens[0] == []
    rec.on_step(0, 1)                  # t=4: the held token is stamped
    assert rec.tokens[0] == [4.0]
    assert rec.steps == [(4.0, 1, 11)]  # 10 prompt rows + 1 emitted
    rec.on_token(0)                    # t=5: a decoded token
    assert rec.tokens[0] == [4.0, 5.0]


def test_ttft_counts_from_the_due_time():
    """An open loop's TTFT is first token minus due time, so a late
    submission adds to it."""
    rec = R.Recorder({0: 4})
    due = time.perf_counter()
    time.sleep(0.02)                   # the generator ran late
    rec.on_submit(0)
    rec.on_admit(0, 4)
    rec.on_token(0)
    rec.on_finish(0)
    assert rec.tokens[0][0] - due >= 0.02
    assert rec.tokens[0][0] >= rec.admit[0]


def test_gaps_are_inside_the_window():
    rec = R.Recorder({})
    rec.tokens = {1: [0.5, 1.0, 1.5, 2.5], 2: [1.2, 1.4], 3: [3.0]}
    gaps = np.sort(rec.inter_token_gaps(1.0, 3.0))
    assert gaps.tolist() == pytest.approx([0.2, 0.5, 1.0])
    assert rec.window_tokens(1.0, 3.0) == 5


def test_gen_kernel_ms_is_the_geometric_mean():
    per_call = {"a": 0.004, "b": 0.0001}
    assert kernel_suite.geomean_ms(per_call) == pytest.approx(
        math.sqrt(4.0 * 0.1))
