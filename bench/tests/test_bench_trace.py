"""``bench/trace.py`` on a small trace recorded on a TPU v5e.

The file is a ``--trace 1 --keep-trace`` run of ``qwen2_7b.gen_kernels``
with a 0.2-s window: two traced slices, one per generated kernel (the
GEMM, one Mosaic kernel per call; flash attention, five per call).
"""

import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"
FILES = sorted(DATA.glob("*.xplane.pb"))


def test_the_recorded_trace_is_here():
    assert len(FILES) == 2


@pytest.fixture(scope="module")
def reduced():
    return [trace.reduce_file(str(f)) for f in FILES]


def test_each_slice_is_busy_within_its_window(reduced):
    for r in reduced:
        assert 0 < r.busy_s <= r.window_s
        assert 0.05 < r.window_s < 5


def test_programs_and_their_kernels(reduced):
    gemm = next(r for r in reduced if any("gemm" in k for k in r.modules))
    flash = next(r for r in reduced if any("flash" in k for k in r.modules))
    (g,) = [k for k in gemm.modules if "gemm" in k]
    (f,) = [k for k in flash.modules if "flash" in k]
    assert g.startswith("stagecc_pallas_gemm_")
    runs, seconds = gemm.program(g)
    calls, kseconds = gemm.kernel(g)
    assert runs > 10 and calls == runs            # one kernel per call
    assert 0 < kseconds <= seconds
    runs, _ = flash.program(f)
    calls, _ = flash.kernel(f)
    assert calls == 5 * runs                       # one kernel per nest


def test_merge_adds_slices(reduced):
    total = trace.Reduced()
    for r in reduced:
        total.merge(r)
    assert total.window_s == pytest.approx(sum(r.window_s for r in reduced))
    assert total.busy_s == pytest.approx(sum(r.busy_s for r in reduced))
    b = total.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in b["device_ops"])


def test_names():
    assert trace.program_name("jit__batched_step(123456)") == "_batched_step"
    assert trace.op_name("%fusion.3 = f32[2]{0} fusion(...)") == "fusion.3"
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
