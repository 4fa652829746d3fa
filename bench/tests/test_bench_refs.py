"""The plain references against the program at toy sizes, in float32.

The program decodes greedily at float32 (recomputing its full forward
pass each step); the reference, independent of its code, must then put
every served token first, to rounding.  A served token the program did
not choose must show a gap.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells
from bench.models.common import run_key
from bench.tests import tiny


def _f32(config):
    return dict(config, run={"param_dtype": "float32",
                             "activation_dtype": "float32",
                             "cache_dtype": "float32", "backend": "xla"})


@pytest.mark.parametrize("name", ["qwen2_7b.decode_heavy",
                                  "mamba2_130m.chat_bursty"])
def test_reference_agrees_with_the_program(name, monkeypatch):
    tiny.patch_registry(monkeypatch)
    config = _f32(tiny.tiny_cell(name).config)
    fam = cells.family_module("models", config)
    ref = cells.family_module("refs", config)
    model, _, _ = fam.build(config)
    params = fam.init_params(model, config, run_key(5))
    vocab = model.cfg.vocab_size
    seq = list(np.random.default_rng(0).integers(0, vocab, 9))
    fwd = jax.jit(lambda p, t: model.apply(p, t)[0])
    for _ in range(12):
        logits = fwd(params, jnp.asarray([seq], jnp.int32))[0, -1, :vocab]
        seq.append(int(jnp.argmax(logits)))
    seq = np.asarray(seq, np.int32)
    gap, _ = ref.score(params, config, seq, 12)
    assert gap.shape == (12,)
    assert gap.max() < 1e-4
    wrong = seq.copy()
    wrong[-12:] = (wrong[-12:] + 1) % vocab
    bad, _ = ref.score(params, config, wrong, 12)
    assert bad.min() > 1e-3


def test_control_departs_from_the_reference(monkeypatch):
    """At float8 operands the reference's own first choice moves."""
    tiny.patch_registry(monkeypatch)
    config = tiny.tiny_cell("qwen2_7b.decode_heavy").config
    fam = cells.family_module("models", config)
    ref = cells.family_module("refs", config)
    model, _, _ = fam.build(config)
    params = fam.init_params(model, config, run_key(6))
    seq = np.random.default_rng(1).integers(0, 256, 200).astype(np.int32)
    _, ctrl = ref.score(params, config, seq, 150, control=True)
    assert ctrl.max() > 0.05 and (ctrl > 0).mean() > 0.05


def test_tiny_architecture_matches_the_file(monkeypatch):
    tiny.patch_registry(monkeypatch)
    cell = tiny.tiny_cell("mamba2_130m.chat_bursty")
    fam = cells.family_module("models", cell.config)
    model, slots, max_len = fam.build(cell.config)
    assert model.cfg.ssm.d_inner == 128 and model.cfg.num_layers == 2
    assert model.cfg.vocab_size == 256
    assert dataclasses.replace(model.cfg).ssm.chunk == 8
