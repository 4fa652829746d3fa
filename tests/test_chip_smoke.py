"""chip_smoke.py rehearsed on the CPU at a tiny size.

The script itself refuses any platform but the TPU; these tests call its
phases directly, with reduced shapes, so that wrong paths, arguments and
control flow show up before a chip run.  Pallas kernels run in the
interpreter here (``repro.device.pallas_interpret``)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.configs.base import get_config, reduced  # noqa: E402


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.update(extra)
    return env


def test_smoke_refuses_a_cpu():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=_cpu_env(),
                       timeout=300)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stdout
    assert '"ok"' not in r.stdout


def test_compiler_phase_tiny():
    chip_smoke.phase_compiler(gemm=(16, 256, 24), flash=(16, 8),
                              tile={"m": 8, "n": 128, "k": 8})


def test_serve_phase_tiny(capsys):
    chip_smoke.phase_serve(reduced(get_config("qwen2_7b")), n_requests=3,
                           slots=2, prompt=(4, 8), out=(2, 4))
    out = capsys.readouterr().out
    assert "requests=3" in out
    assert "first decode logits pallas vs xla" in out


def test_a_failed_check_ends_the_run():
    with pytest.raises(SystemExit):
        chip_smoke.check("x", 1.0, 0.5)
    with pytest.raises(SystemExit):
        chip_smoke.check("nan", float("nan"), 0.5)


def test_four_chip_phase_on_host_devices():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke as c;"
            "from repro.configs.base import get_config, reduced;"
            "c.phase_four_chips(reduced(get_config('qwen2_7b')), cut=1,"
            " n_requests=2, slots=2, prompt=(4, 8), out=(2, 4))" % REPO)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "sharded engine: requests=2" in r.stdout
    assert "1-layer first decode logits sharded vs device 0" in r.stdout


def test_four_chip_phase_at_one_device(capsys):
    """The same phase at ``model_parallel=1``: the model builds no mesh
    and the engine serves on one device."""
    chip_smoke.phase_four_chips(reduced(get_config("qwen2_7b")), cut=1,
                                n_requests=2, slots=2, prompt=(4, 8),
                                out=(2, 4), model_parallel=1)
    out = capsys.readouterr().out
    assert "model_parallel=1" in out
    assert "sharded engine: requests=2" in out


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_directory(tmp_path, env_dir):
    code = ("import jax, jax.numpy as jnp;"
            "from repro.device import enable_compile_cache, CACHE_DIR;"
            "d = enable_compile_cache();"
            "jax.jit(lambda x: x * 2)(jnp.ones(3)).block_until_ready();"
            "print(d, jax.config.jax_compilation_cache_dir, CACHE_DIR)")
    env = _cpu_env(JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    returned, configured, fixed = r.stdout.split()
    want = str(tmp_path) if env_dir else fixed
    assert returned == configured == want
    assert fixed == os.path.join(REPO, ".jax_cache")
    if env_dir:
        assert any(tmp_path.iterdir())       # the compile landed there
