"""``bench/run.py`` end to end on the CPU at toy sizes.

Each test skips the harness's look for a chip (``run_cell`` is handed a
device) and drives the rest of a run: set-up, the window, the check
against the plain reference, the result's line.  The faults break the
timed path underneath and must turn ``correct`` false; the control (the
reference at float8 operands, put in the program's place) must fail the
cell's own limit.  No number here is a device metric.
"""

import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, run
from bench.tests import tiny

SERVING = ["qwen2_7b.decode_heavy", "mamba2_130m.chat_bursty"]
SEED = 2**33 + 11                       # more than 32 bits


def _run(name, monkeypatch, seconds=1.0, hooks=None, trace=False,
         **traffic):
    tiny.patch_registry(monkeypatch)
    cell = tiny.tiny_cell(name, **traffic)
    return run.run_cell(cell, SEED, seconds, trace, tiny.FAKE_DEVICE,
                        tiny.FAKE_PEAK, t_process=time.perf_counter(),
                        hooks=hooks)


def test_refuses_a_platform_that_is_not_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2_7b.gen_kernels", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cells.ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)}, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.NoDevice):
        run.device_peak("TPU v99")
    assert run.device_peak("TPU v5 lite")["flops_per_s"] == 197e12


@pytest.mark.parametrize("name", SERVING + ["qwen2_7b.gen_kernels"])
def test_a_run_is_correct_and_reports_its_metrics(name, monkeypatch):
    result, lines = _run(name, monkeypatch)
    assert result["correct"], lines
    want = {m["name"] for m in tiny.tiny_cell(name).end_to_end}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"
    assert "compiles inside the window: 0" in lines
    json.dumps(result)


def _slow_steps(engine):
    """Every step after the warm-up takes 0.15 s more, so requests fall
    due while the host is inside a step, up to the window's close."""
    step = engine.step

    def slow():
        if type(engine.metrics).__name__ == "Recorder":
            time.sleep(0.15)
        return step()
    engine.step = slow


def test_an_open_loop_serves_every_request_due_in_the_window(monkeypatch):
    result, lines = _run("mamba2_130m.chat_bursty", monkeypatch,
                         hooks={"engine_hook": _slow_steps}, rate=8.0)
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0, lines


def test_a_traced_run_whose_metrics_find_nothing_fails(monkeypatch):
    """The CPU's trace has no TPU plane, so every reader finds nothing:
    the run fails instead of leaving the cell's metrics out."""
    with pytest.raises(RuntimeError, match="holds nothing"):
        _run("qwen2_7b.decode_heavy", monkeypatch, trace=True)


def test_each_metric_finds_its_reader_by_name():
    bench = cells.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(cells.load_file_module("metrics", m["name"]).read)
    assert cells.quantity("itl_p95_ms.chat") == "itl_p95_ms"
    assert cells.load_file_module("metrics", "idle_share.chat") \
        .__file__.endswith("idle_share.py")


def _alter_tokens(engine):
    """A token altered where it is produced: every decoded token + 1."""
    step = engine._decode_all
    vocab = engine.model.cfg.vocab_size

    def broken(*args):
        nxt, stacked, keys = step(*args)
        return (nxt + 1) % vocab, stacked, keys
    engine._decode_all = broken


def _freeze_state(engine):
    """A step that returns its state unchanged."""
    step = engine._decode_all

    def broken(params, stacked, tok, active, keys):
        old = jax.tree.map(jnp.copy, stacked)
        nxt, _, keys = step(params, stacked, tok, active, keys)
        return nxt, old, keys
    engine._decode_all = broken


@pytest.mark.parametrize("fault", [_alter_tokens, _freeze_state])
@pytest.mark.parametrize("name", SERVING)
def test_a_broken_serving_path_is_not_correct(name, fault, monkeypatch):
    result, lines = _run(name, monkeypatch,
                         hooks={"engine_hook": fault})
    assert not result["correct"], lines


def test_an_altered_kernel_answer_is_not_correct(monkeypatch):
    def alter(kernels):
        for k in kernels:
            fn = k.fn
            k.fn = lambda *a, fn=fn: fn(*a).at[0, 0].add(1.0)
        return kernels
    result, lines = _run("qwen2_7b.gen_kernels", monkeypatch,
                         hooks={"kernel_hook": alter})
    assert not result["correct"], lines
    assert all(v["value"] > v["limit"] for v in result["checks"].values())


@pytest.mark.parametrize("name", ["qwen2_7b.decode_heavy",
                                  "mamba2_130m.chat_bursty"])
def test_the_control_fails_the_cell_limit(name, monkeypatch):
    """The reference at float8 operands, read at every position of the
    served requests, lies past the cell's limit where the program does
    not."""
    ref = cells.family_module("refs", tiny.tiny_cell(name).config)
    score = ref.score
    seen = []

    def spy(params, config, tokens, n_served, control=False):
        gap, ctrl = score(params, config, tokens, n_served, control=True)
        seen.append((gap.max(), ctrl.max()))
        return gap, ctrl
    monkeypatch.setattr(ref, "score", spy)
    result, _ = _run(name, monkeypatch, check_tokens=400, check_requests=12)
    limit = result["checks"]["logit_gap"]["limit"]
    assert result["correct"]
    assert max(c for _, c in seen) > limit >= max(g for g, _ in seen)


def test_the_kernel_control_fails_the_cell_limit(monkeypatch):
    from bench import control
    tiny.patch_registry(monkeypatch)
    cell = tiny.tiny_cell("qwen2_7b.gen_kernels")
    got = control.control_kernels(cell, SEED)
    assert all(v > cell.limits[k] for k, v in got.items()), got


def test_weights_are_made_from_the_seed(monkeypatch):
    tiny.patch_registry(monkeypatch)
    cfg = tiny.tiny_cell("qwen2_7b.decode_heavy").config
    fam = cells.family_module("models", cfg)
    from bench.models.common import run_key
    model, _, _ = fam.build(cfg)
    a = fam.init_params(model, cfg, run_key(SEED))
    b = fam.init_params(model, cfg, run_key(SEED))
    c = fam.init_params(model, cfg, run_key(SEED + 2**32))
    la, lb, lc = (jax.tree.leaves(x) for x in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[0], lc[0])
    assert {x.dtype for x in la} == {jnp.dtype(jnp.bfloat16)}


def _device_from_host_spans(reduce):
    """The CPU's trace has no device plane.  Stand in for the programs'
    device time with the host spans around their dispatch and wait, so
    that every reader has something to read."""
    def stand_in(self):
        red = reduce(self)
        sp = red.spans
        step = [sp["serve.decode"][0],
                sp["serve.decode"][1] + sp["serve.sync"][1]]
        red.modules["_batched_step"] = list(step)
        red.kernels["_batched_step"] = list(step)
        red.modules["_prefill"] = [sp["serve.prefill"][0],
                                   sp["serve.prefill"][1] +
                                   sp["serve.first_token"][1]]
        red.modules["_write"] = list(sp["serve.write"])
        return red
    return stand_in


@pytest.mark.parametrize("name", SERVING)
def test_a_traced_run_reads_every_per_layer_metric(name, monkeypatch):
    """A tiny traced run: the scheduler's metrics read the engine's spans,
    and each share of a peak or roofline lies in (0, 100]."""
    from bench.tracer import Tracer
    monkeypatch.setattr(Tracer, "reduce",
                        _device_from_host_spans(Tracer.reduce))
    result, lines = _run(name, monkeypatch, trace=True)
    assert result["correct"], lines
    cell = tiny.tiny_cell(name)
    assert set(result["metrics"]) == {m["name"] for m in cell.per_layer}
    for m in cell.per_layer:
        v = result["metrics"][m["name"]]["value"]
        assert v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, m["name"]
    for q in ("admit_ms", "host_step_ms"):
        got = [v["value"] for k, v in result["metrics"].items()
               if cells.quantity(k) == q]
        assert len(got) == 1 and got[0] > 0, q
    assert result["device"]["window_s"] > 0
