"""CLI entrypoint smokes: the train and serve launchers run end to end."""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=600, env=None):
    full = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    full.pop("XLA_FLAGS", None)
    full.update(env or {})
    return subprocess.run([sys.executable, "-m"] + args,
                          capture_output=True, text=True, timeout=timeout,
                          env=full, cwd=REPO)


@pytest.mark.slow
def test_train_cli(tmp_path):
    r = _run(["repro.launch.train", "--arch", "qwen2-7b", "--reduced",
              "--steps", "6", "--seq-len", "32", "--global-batch", "4",
              "--checkpoint-dir", str(tmp_path), "--checkpoint-every", "3"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss first->last" in r.stdout
    assert any(n.startswith("step_") for n in os.listdir(tmp_path))


@pytest.mark.slow
def test_serve_cli():
    r = _run(["repro.launch.serve", "--arch", "mamba2-130m", "--reduced",
              "--batch", "2", "--prompt-len", "8", "--gen", "6"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "tok/s" in r.stdout


@pytest.mark.slow
def test_dryrun_list_cli():
    r = _run(["repro.launch.dryrun", "--list"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.count("SKIP") == 7       # the 7 long_500k skips
    assert r.stdout.count("run") >= 33


def test_serve_continuous_cli_reports_prefill_compiles():
    """The operator's summary line counts the prefills that compiled:
    one per distinct prompt length of the stream."""
    r = _run(["repro.launch.serve", "--arch", "qwen2-7b", "--reduced",
              "--continuous", "--slots", "2", "--requests", "6",
              "--prompt-len", "6", "--gen", "4", "--rate", "50"])
    assert r.returncode == 0, r.stderr[-2000:]
    line = next(l for l in r.stdout.splitlines()
                if l.startswith("[serve] continuous:"))
    n = int(re.search(r"prefill_compiles=(\d+)", line)[1])
    assert 1 <= n <= 3                  # prompt lengths are drawn in [4, 6]
    assert "inplace_cache_leaves=2/2" in line    # K and V of its one kind


def test_serve_continuous_cli_reports_inplace_cache_leaves():
    """A state-space model's cache leaves, its SSD state and convolution
    window, all ride the layer scan in place."""
    r = _run(["repro.launch.serve", "--arch", "mamba2-130m", "--reduced",
              "--continuous", "--slots", "2", "--requests", "3",
              "--prompt-len", "6", "--gen", "3", "--rate", "50"])
    assert r.returncode == 0, r.stderr[-2000:]
    line = next(l for l in r.stdout.splitlines()
                if l.startswith("[serve] continuous:"))
    inplace, total = re.search(r"inplace_cache_leaves=(\d+)/(\d+)",
                               line).groups()
    assert int(inplace) == int(total) > 0


@pytest.mark.parametrize("n", [1, 4])
def test_serve_continuous_cli_model_parallel(n):
    """``--model-parallel N`` serves one replica over N devices (four host
    devices here) and reports the layout and what one chip holds."""
    extra = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"} \
        if n > 1 else {}
    r = _run(["repro.launch.serve", "--arch", "qwen2-7b", "--reduced",
              "--continuous", "--slots", "2", "--requests", "3",
              "--prompt-len", "6", "--gen", "3", "--rate", "50",
              "--model-parallel", str(n)], env=extra)
    assert r.returncode == 0, r.stderr[-2000:]
    line = next(l for l in r.stdout.splitlines()
                if l.startswith("[serve] continuous:"))
    assert f"layout=data1xmodel{n} " in line
    params = int(re.search(r"param_bytes_per_chip=(\d+)", line)[1])
    cache = int(re.search(r"cache_bytes_per_chip=(\d+)", line)[1])
    total = int(re.search(r"params=([\d,]+)", r.stdout)[1].replace(",", ""))
    assert 0 < cache
    # f32 weights; the replicated norms add a little to a chip's share
    assert total * 4 / n <= params < 1.1 * total * 4 / n
