"""``BENCHMARK.json`` as the harness loads it: every cell finds its files,
and the bursty chat cell reports what it was added for."""

import pytest

from bench import cells

CHAT = "mamba2_130m.chat_bursty"
BENCH = cells.load_benchmark()


def test_the_chat_cell_loads_with_its_own_metrics():
    cell = cells.load_cell(CHAT, BENCH)
    assert cell.chips == 1 and cell.config["name"] == "mamba2_130m"
    assert cell.traffic.name == "chat_bursty"
    assert [m["name"] for m in cell.end_to_end] == [
        "ttft_p95_ms", "itl_p95_ms.chat", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "idle_share.chat", "prefill_ms.chat", "admit_ms.chat",
        "host_step_ms.chat", "decode_step_ms.chat", "decode_mfu.chat",
        "decode_hbm_roofline.chat"}
    assert cell.limits == {"logit_gap": 0.05}


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_each_per_layer_metric_moves_a_metric_of_its_cell(name):
    cell = cells.load_cell(name, BENCH)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in reported, (name, m["name"])
        assert callable(cells.load_file_module("metrics", m["name"]).read)


def test_the_scheduler_metrics_of_decode_heavy():
    cell = cells.load_cell("qwen2_7b.decode_heavy", BENCH)
    moves = {m["name"]: m["moves"] for m in cell.per_layer}
    assert moves["admit_ms"] == "itl_p95_ms"
    assert moves["host_step_ms"] == "tokens_per_s"
