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


# What the reduction read from the recorded files before it counted the
# engine's spans: every reading of the existing cells comes from these
# fields, so they must not move.
RECORDED = {
    "gemm.xplane.pb": dict(
        window_s=0.079416098, busy_s=0.07847245600000001,
        modules={"stagecc_pallas_gemm_2048x18944x3584_none":
                 [24, 0.07847255300000001]},
        kernels={"stagecc_pallas_gemm_2048x18944x3584_none":
                 [24, 0.07847245599999998]},
        ops=(1, 0.07847245599999998), idle=(2, 0.000943642)),
    "flash.xplane.pb": dict(
        window_s=0.078039808, busy_s=0.07126313000000001,
        modules={"stagecc_pallas_flash_2048x2048x128": [345, 0.071269509]},
        kernels={"stagecc_pallas_flash_2048x2048x128":
                 [1725, 0.06340361799999893]},
        ops=(11, 0.07126313000000002), idle=(6, 0.006776678000000004)),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_the_recorded_reductions_are_unchanged(name):
    r = trace.reduce_file(str(DATA / name))
    want = RECORDED[name]
    assert r.window_s == want["window_s"] and r.busy_s == want["busy_s"]
    assert {k: list(v) for k, v in r.modules.items()} == want["modules"]
    assert {k: list(v) for k, v in r.kernels.items()} == want["kernels"]
    for field in ("ops", "idle"):
        d = getattr(r, field)
        assert len(d) == want[field][0]
        assert sum(d.values()) == pytest.approx(want[field][1], rel=1e-12)
    assert dict(r.spans) == {}              # recorded before the spans


def test_merge_adds_spans():
    a, b = trace.Reduced(), trace.Reduced()
    a.spans["serve.admit"] = [2, 0.05]
    b.spans["serve.admit"] = [1, 0.02]
    b.spans["serve.step"] = [4, 0.08]
    a.merge(b)
    assert a.spans["serve.admit"] == [3, pytest.approx(0.07)]
    assert a.spans["serve.step"] == [4, 0.08]
