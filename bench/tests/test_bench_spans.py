"""``bench/spans.py`` on the trace of a tiny traced serving run (CPU).

The run fails at its end, since the CPU's trace holds no device for the
cell's per-layer metrics, but it keeps its trace files first.  The
engine's spans are host events, so the CPU's trace holds them.  No
number here is a device metric.
"""

import json
import pathlib
import time

import pytest

from bench import run, spans
from bench.tests import tiny


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    tiny.patch_registry(mp)
    keep = tmp_path_factory.mktemp("trace")
    try:
        with pytest.raises(RuntimeError, match="holds nothing"):
            run.run_cell(tiny.tiny_cell("qwen2_7b.decode_heavy"), 7, 1.0,
                         True, tiny.FAKE_DEVICE, tiny.FAKE_PEAK,
                         t_process=time.perf_counter(), keep_trace=str(keep))
    finally:
        mp.undo()
    return str(keep)


def test_each_step_and_admission_has_its_spans(traced):
    got = spans.dir_spans(traced)
    decodes = got["serve.decode"][0]
    assert decodes > 0
    # a step decodes where a slot is busy (the toy backlog runs dry)
    assert got["serve.step"][0] >= decodes
    for name in ("serve.sync", "serve.emit"):
        assert got[name][0] == decodes, name
    admits = got["serve.admit"][0]
    assert admits > 0                       # requests finish and refill
    assert got["serve.first_token"][0] == admits
    assert got["serve.prefill"][0] == admits
    assert got["serve.write"][0] <= admits
    # the warm-up prefilled every prompt length: nothing compiles here
    assert "serve.prefill.compile" not in got
    for name, (n, s) in got.items():
        assert name.startswith("serve.") and s > 0, name


def test_readings(traced):
    got = spans.dir_spans(traced)
    n, step_s = got["serve.step"]
    host = spans.host_step_ms(got)
    assert 0 < host < 1e3 * step_s / n
    assert spans.admit_ms(got) == pytest.approx(
        1e3 * got["serve.admit"][1] / got["serve.admit"][0])
    assert spans.admit_ms({}) is None and spans.host_step_ms({}) is None


def test_the_script_prints_one_object(traced, capsys):
    assert spans.main([traced]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["admit_ms"] > 0 and out["host_step_ms"] > 0
    assert out["spans"]["serve.step"][0] > 0


def test_a_trace_with_no_serving_spans_reads_none():
    """The committed v5e trace of the kernel cell predates the spans."""
    got = spans.file_spans(str(pathlib.Path(__file__).parent / "data" /
                               "gemm.xplane.pb"))
    assert got == {}
    assert spans.admit_ms(got) is None and spans.host_step_ms(got) is None
