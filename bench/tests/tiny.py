"""Tiny cells for CPU tests: the real cells' files at toy widths.

``tiny_cell(name)`` loads the named cell of ``BENCHMARK.json`` and
shrinks its configuration, traffic and kernel shapes so a run takes
seconds on the CPU; ``patch_registry`` makes the program's registry
return the matching toy architecture.  Nothing here is a measurement.
"""

from __future__ import annotations

import dataclasses

from bench import cells

FAKE_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
FAKE_PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}

QWEN = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256, slots=4, max_len=128)
MAMBA = dict(d_model=64, n_layer=2, vocab_size=256, d_state=16, expand=2,
             headdim=16, chunk_size=8, slots=4, max_len=128)
TRAFFIC = dict(prompt_set=[8, 16], set_size=16,
               prompt={"median": 10, "sigma": 0.5, "lo": 4, "hi": 16},
               output={"median": 6, "sigma": 0.5, "lo": 3, "hi": 12},
               check_tokens=40, check_requests=3, prelude_s=0.2,
               requests=64)
KERNELS = [{"name": "gen_gemm", "kind": "gemm", "m": 64, "n": 256, "k": 128,
            "schedule": "tpu_mxu_kgrid", "tile": [32, 128, 64]},
           {"name": "gen_flash", "kind": "flash", "s": 64, "d": 16}]


def _toy_arch(name: str):
    from repro.configs.base import SSMConfig, get_config
    cfg = get_config(name)
    if cfg.ssm is None:
        q = QWEN
        return dataclasses.replace(
            cfg, d_model=q["hidden_size"], d_ff=q["intermediate_size"],
            num_heads=q["num_attention_heads"],
            num_kv_heads=q["num_key_value_heads"], head_dim=q["head_dim"],
            vocab_size=q["vocab_size"])
    m = MAMBA
    return dataclasses.replace(
        cfg, d_model=m["d_model"],
        ssm=SSMConfig(d_inner=m["expand"] * m["d_model"],
                      head_dim=m["headdim"], state_dim=m["d_state"]))


def patch_registry(monkeypatch) -> None:
    import bench.models.common as common
    monkeypatch.setattr(common, "get_config", _toy_arch)


def tiny_cell(name: str, **traffic) -> cells.Cell:
    """A cell of ``BENCHMARK.json``, or one that only its files describe
    (``<config>.<mix>``, with ``bench/limits/<cell>.json``)."""
    bench = cells.load_benchmark()
    if all(w["name"] != name for w in bench["workloads"]):
        config, mix = name.split(".", 1)
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": 1})
    cell = cells.load_cell(name, bench)
    config = dict(cell.config)
    config.update(QWEN if config["family"] == "qwen2" else MAMBA)
    params = dict(cell.traffic.params)
    if cell.traffic.loop == "kernel_suite":
        params["kernels"] = KERNELS
    else:
        params.update(TRAFFIC)
        if params["arrivals"] != "backlog":
            params["rate"] = 20.0
    params.update(traffic)
    return dataclasses.replace(
        cell, config=config,
        traffic=cells.Traffic(cell.traffic.name, params["loop"], params))
