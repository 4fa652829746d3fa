"""Each work count of ``bench/work`` on a small shape, counted by hand."""

import pytest

from bench.cells import load_file_module
from bench.work import bound, roofline_pct

PEAK = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
CFG = {"num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 8, "hidden_size": 32,
       "intermediate_size": 64, "vocab_size": 100,
       "run": {"cache_dtype": "bfloat16", "activation_dtype": "bfloat16"}}


def test_gen_gemm():
    flops, nbytes = load_file_module("work", "gen_gemm").call(
        {"m": 2, "n": 3, "k": 4})
    assert flops == 2 * 2 * 3 * 4
    assert nbytes == 4 * (2 * 4 + 4 * 3 + 2 * 3)


def test_gen_flash():
    flops, nbytes = load_file_module("work", "gen_flash").call(
        {"s": 8, "d": 4})
    assert flops == 2 * 8 * 8 * 4 * 2          # q kt and p v
    assert nbytes == 4 * (8 * 4 * 4 + 8 * 8)   # q, kt, v, out; mask


def test_decode_attention():
    """Two live slots with 10 and 6 valid rows: K and V rows of 2 KV heads
    of 8 in bf16, plus q and out of 4 heads, on each of 2 layers."""
    flops, nbytes = load_file_module("work", "decode_attention").step(
        CFG, active=2, valid_rows=16)
    assert nbytes == 2 * (2 * 16 * 2 * 8 * 2 + 2 * 2 * 4 * 8 * 2)
    assert flops == 2 * (4 * 16 * 4 * 8)


def test_decode_step():
    mod = load_file_module("work", "decode_step")
    per_layer = 32 * 32 + 2 * 32 * 16 + 32 * 32 + 3 * 32 * 64
    assert mod.matmul_weights(CFG) == 2 * per_layer + 32 * 100
    assert mod.step_flops(CFG, 3, 20) == pytest.approx(
        2 * mod.matmul_weights(CFG) * 3 + 4 * 20 * 4 * 8 * 2)


def test_roofline_takes_the_binding_bound():
    assert roofline_pct(100.0, 5.0, 2.0, PEAK) == pytest.approx(50.0)
    assert roofline_pct(10.0, 20.0, 4.0, PEAK) == pytest.approx(50.0)
    assert bound(100.0, 5.0, PEAK) == "compute"
    assert bound(10.0, 20.0, PEAK) == "bandwidth"


def test_mamba2_step():
    """d 4, d_inner 8, d_state 2, two heads of 4, one group (xBC 12 wide),
    d_conv 3, 2 layers, vocab 10, bf16 weights and conv window, float32
    state; three live slots."""
    cfg = {"d_model": 4, "expand": 2, "d_state": 2, "headdim": 4,
           "ngroups": 1, "d_conv": 3, "n_layer": 2, "vocab_size": 10,
           "run": {"param_dtype": "bfloat16", "cache_dtype": "bfloat16"}}
    mod = load_file_module("work", "mamba2_step")
    in_proj, out_proj = 4 * (8 + 12 + 2), 8 * 4
    assert mod.matmul_weights(cfg) == 2 * (in_proj + out_proj) + 4 * 10
    ssd = 3 * 8 * 2 + 2 * 8 * 2             # update, readout
    conv = 2 * 3 * 12
    assert mod.step_flops(cfg, 3) == 3 * (2 * 280 + 2 * (ssd + conv))
    small = 3 * 12 + 3 * 2 + 4 + 8          # filter, A_log/D/dt_bias, norms
    weights = 2 * (280 + 2 * small + 4)
    state, window = 8 * 2 * 4, 2 * 12 * 2
    assert mod.step_bytes(cfg, 3) == weights + 3 * 2 * 2 * (state + window)
    assert mod.step_bytes(cfg, 0) == weights


def test_mamba2_step_at_the_cell_size():
    """The published sizes: 786,432 bytes of state per slot and layer, as
    the program holds it (24 heads x 64 x 128, float32)."""
    from bench.cells import _json
    cfg = _json("configs", "mamba2_130m")
    mod = load_file_module("work", "mamba2_step")
    per_slot = mod.step_bytes(cfg, 1) - mod.step_bytes(cfg, 0)
    assert per_slot == 24 * 2 * (786_432 + 3 * 1792 * 2)
    assert 2.5e8 < mod.step_bytes(cfg, 0) < 2.7e8      # ~0.26 GB of weights
