"""The four-chip cell, ``qwen2_7b_28l.decode_4chip``, on the CPU.

Its configuration loads with ``chips == 4``; its readers divide what
``bench/trace.py`` sums over the device planes by the chips, so shares
of a roofline or a peak stay under 100% (checked on a synthetic
four-plane reduction); and the whole run, at a toy qwen2 shape on four
host devices, serves through the model's own mesh and is correct.  No
number here is a device metric.
"""

import json
import os
import subprocess
import sys

import pytest

from bench import cells, trace
from bench.cells import load_file_module
from bench.work import decode_attention, decode_step

CELL = "qwen2_7b_28l.decode_4chip"
CHIPS = 4
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def test_the_cell_loads_with_four_chips():
    cell = cells.load_cell(CELL, cells.load_benchmark())
    assert cell.chips == CHIPS
    cfg = cell.config
    assert cfg["run"]["model_parallel"] == CHIPS
    assert cfg["num_hidden_layers"] == cfg["published"]["num_hidden_layers"]
    assert cfg["reduced"] == []
    assert (cfg["slots"], cfg["max_len"]) == (64, 4096)
    assert cfg["run"]["prefill_chunk"] == 512
    assert cell.traffic.name == "decode_heavy"
    assert [m["name"] for m in cell.end_to_end] == [
        "tokens_per_s", "itl_p95_ms", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "collective_ms.4chip", "decode_attention_roofline.4chip",
        "decode_mfu.4chip", "decode_step_ms.4chip", "idle_share.4chip",
        "admit_ms.4chip"}
    assert set(cell.limits) >= {"logit_gap"}


def _config():
    return cells.load_cell(CELL, cells.load_benchmark()).config


def _four_planes(step_s, kernel_s, ops, steps):
    """A reduction of ``steps`` decode steps on each of four chips: every
    plane adds its runs and device time, as ``bench/trace.py`` does."""
    t = trace.Reduced(window_s=10.0, busy_s=9.0)
    t.modules["_batched_step"] = [CHIPS * steps, CHIPS * steps * step_s]
    t.kernels["_batched_step"] = [CHIPS * steps, CHIPS * steps * kernel_s]
    for name, s in ops.items():
        t.ops[name] = CHIPS * steps * s
    return t


def _rec(cfg, steps, active, rows, span=10.0):
    return {"config": cfg, "slices": [(0.0, span)],
            "steps": [(span * i / steps, active, rows)
                      for i in range(steps)]}


def test_collective_ms_counts_each_op_once_per_chip_and_step():
    ops = {"_batched_step:all-reduce.5": 1e-3,
           "_batched_step:all-reduce-start.1": 0.25e-3,
           "_batched_step:all-reduce-done.1": 0.5e-3,
           "_batched_step:all-gather.4": 0.125e-3,
           "_batched_step:fusion.3": 9e-3,           # not a collective
           "_batched_step:all-reduce-scatter-fusion": 7e-3,  # nor this
           "_prefill:all-reduce.2": 5e-3,            # outside the step
           "_write:all-gather.1": 5e-3}
    t = _four_planes(0.03, 0.01, ops, steps=300)
    got = load_file_module("metrics", "collective_ms.4chip").read(
        t, _rec(_config(), 300, 64, 64 * 1500), PEAK)
    assert got == pytest.approx(1.875)               # ms a step, a chip


def test_collective_ms_reads_nothing_without_collectives():
    t = _four_planes(0.03, 0.01, {"_batched_step:fusion.3": 1e-3}, 300)
    reader = load_file_module("metrics", "collective_ms.4chip")
    assert reader.read(t, _rec(_config(), 300, 64, 1), PEAK) is None
    assert reader.kind("all-reduce-start.12") == "all-reduce-start"
    assert reader.kind("all-gather") == "all-gather"


def test_the_kernel_roofline_is_per_chip():
    """A kernel that moves its chip's share of the bytes in 1.25 times
    the least time reads 80%, though four planes are summed; the
    one-chip reader, which counts every head on one chip, would read
    four times the truth."""
    cfg = _config()
    steps, active, rows = 300, 64, 64 * 1500
    _, nbytes = decode_attention.step(cfg, active, rows)
    least = nbytes / CHIPS / PEAK["hbm_bytes_per_s"]
    t = _four_planes(0.03, 1.25 * least, {}, steps)
    rec = _rec(cfg, steps, active, rows)
    got = load_file_module("metrics", "decode_attention_roofline.4chip") \
        .read(t, rec, PEAK)
    assert got == pytest.approx(80.0, rel=1e-3)
    one = load_file_module("metrics", "decode_attention_roofline").read(
        t, rec, PEAK)
    assert one == pytest.approx(CHIPS * got)


def test_the_step_mfu_is_over_four_peaks():
    cfg = _config()
    steps, active, rows = 300, 64, 64 * 1500
    span = steps * decode_step.step_flops(cfg, active, rows) / \
        (CHIPS * PEAK["flops_per_s"]) / 0.5          # half the peak
    t = _four_planes(span / steps, span / steps / 3, {}, steps)
    rec = _rec(cfg, steps, active, rows, span=span)
    got = load_file_module("metrics", "decode_mfu.4chip").read(t, rec, PEAK)
    assert got == pytest.approx(50.0)
    assert load_file_module("metrics", "decode_mfu").read(t, rec, PEAK) == \
        pytest.approx(CHIPS * got)


def test_the_existing_readers_are_per_chip():
    t = _four_planes(0.03, 0.01, {}, steps=300)
    rec = _rec(_config(), 300, 64, 1)
    step = load_file_module("metrics", "decode_step_ms.4chip")
    assert step.read(t, rec, PEAK) == pytest.approx(30.0)
    idle = load_file_module("metrics", "idle_share.4chip")
    assert idle.read(t, rec, PEAK) == pytest.approx(10.0)


_RUN = r"""
import dataclasses, json, sys, time
sys.path[0:0] = [%(repo)r, %(src)r]
from bench import cells, run
import bench.models.common as common
from bench.tests import tiny
from bench.tracer import Tracer
from repro.configs.base import get_config

QWEN = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=8, num_key_value_heads=4, head_dim=16,
            vocab_size=256, slots=4, max_len=128)
common.get_config = lambda name: dataclasses.replace(
    get_config(name), d_model=64, d_ff=128, num_heads=8, num_kv_heads=4,
    head_dim=16, vocab_size=256)
cell = cells.load_cell(%(cell)r, cells.load_benchmark())
params = dict(cell.traffic.params, **tiny.TRAFFIC)
# prompts of 16 tokens prefill in two pieces
QWEN["run"] = dict(cell.config["run"], prefill_chunk=8)
cell = dataclasses.replace(
    cell, config=dict(cell.config, **QWEN),
    traffic=cells.Traffic(cell.traffic.name, params["loop"], params))

reduce = Tracer.reduce


def stand_in(self):
    # the CPU's trace has no device plane: stand in for the four chips'
    # programs with the host spans around their dispatch and wait
    red = reduce(self)
    sp = red.spans
    n, s = sp["serve.decode"][0], sp["serve.decode"][1] + sp["serve.sync"][1]
    red.modules["_batched_step"] = [4 * n, 4 * s]
    red.kernels["_batched_step"] = [4 * n, 4 * s / 2]
    red.ops["_batched_step:all-reduce.1"] = 4 * s / 10
    red.busy_s = red.window_s / 2
    return red


Tracer.reduce = stand_in
device = dict(tiny.FAKE_DEVICE, count=4)
out = {}
for trace in (False, True):
    result, lines = run.run_cell(cell, 2**33 + 5, 1.0, trace, device,
                                 tiny.FAKE_PEAK,
                                 t_process=time.perf_counter())
    out[str(trace)] = result
print("RESULT " + json.dumps(out))
"""


def test_a_tiny_run_on_four_host_devices():
    repo = str(cells.ROOT)
    src = os.path.join(repo, "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "-c",
         _RUN % {"repo": repo, "src": src, "cell": CELL}],
        capture_output=True, text=True, env=env, timeout=600, cwd=repo)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    line = next(l for l in r.stdout.splitlines() if l.startswith("RESULT "))
    out = json.loads(line[len("RESULT "):])
    plain, traced = out["False"], out["True"]
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"tokens_per_s", "itl_p95_ms",
                                     "setup_s"}
    cell = cells.load_cell(CELL, cells.load_benchmark())
    assert set(traced["metrics"]) == {m["name"] for m in cell.per_layer}
    for m in cell.per_layer:
        v = traced["metrics"][m["name"]]["value"]
        assert v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, m["name"]
