#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU.

    python chip_smoke.py               # one chip: phases compiler + serve
    python chip_smoke.py --four-chips  # four chips: whole qwen2_7b sharded

One chip runs two phases, each through the entry points a user calls:

* ``compiler``: the generated GEMM at qwen2_7b's MLP width and one
  generated multi-nest kernel (flash attention, head 128), both compiled
  through Mosaic and checked against the compiler's numpy oracle;
* ``serve``: qwen2_7b at published widths (bf16, cut in depth to 7 of
  its 28 layers) served through ``ContinuousEngine`` with the pallas
  decode-attention kernel, its logits checked against the XLA backend.

``--four-chips`` runs only the whole 28-layer model at
``RunConfig(model_parallel=4)``: the model's own ``data=1, model=4``
mesh, the path ``launch/serve.py --continuous --model-parallel 4`` and
the benchmark take; then the 7-layer cut on that layout against the same
cut alone on device 0.

The script exits non-zero when JAX finds no TPU, and any failed check
ends the run with a non-zero exit code.  The last line of its output is
one JSON object naming the device.  Seconds, bytes and token counts on
the earlier lines describe this run only; none is a benchmark figure.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.device import enable_compile_cache, pallas_interpret  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.distributed.sharding import axis_rules  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.model import Model, RunConfig  # noqa: E402
from repro.serve import loadgen  # noqa: E402
from repro.serve.continuous import ContinuousEngine, Request  # noqa: E402

# qwen2_7b's MLP GEMM for a 256-token step, and one attention head
GEMM_MNK = (256, 18944, 3584)
FLASH_SEQ, FLASH_HEAD = 512, 128
# Generated kernels against the float oracle, as max|err| / max|ref|:
# the MXU may round f32 operands to bf16 (8 significant bits, so a
# relative step of 2^-8 per product); over K random-sign terms the error
# grows as sqrt(K), as does the result, so the ratio stays near 2^-8 and
# its largest element within a few times that: 2e-2 leaves a margin of
# about 4.
KERNEL_TOL = 2e-2
# Logits of two serving paths, as max|diff| / max|logits|: activations
# and the cache are bf16 (relative step 2^-8).  Two attention
# implementations sum in different orders, so their bf16 outputs can
# differ by a step at each of the 7 layers and the residual stream
# carries every such difference to the logits: 7 * 2^-8 ~= 0.03, so
# 0.05.
LOGITS_TOL = 5e-2
SERVE_LAYERS = 7             # one stage of a 4-stage pipeline


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def check(name: str, err: float, tol: float) -> None:
    log(f"[check] {name}: max|err|/max|ref|={err!r} tol={tol!r}")
    if not err <= tol:
        raise SystemExit(f"FAIL {name}: {err!r} > {tol!r}")


def device_bytes(stat: str = "bytes_in_use"):
    return [int((d.memory_stats() or {}).get(stat, 0))
            for d in jax.devices()]


# ---------------------------------------------------------------------------
# phase: compiler
# ---------------------------------------------------------------------------


def _run_kernel(name: str, ck, inputs) -> float:
    """Compile the generated kernel for this platform, run it, and return
    its error against the numpy oracle."""
    if ck.run_pallas is None:
        raise SystemExit(f"FAIL {name}: pallas emitter refused: "
                         f"{ck.pallas_error}")
    t0 = time.perf_counter()
    compiled = jax.jit(ck.run_pallas).lower(*inputs).compile()
    compile_s = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    got = np.asarray(compiled(*inputs))
    want = ck.run_ref(*inputs)[0]
    err = rel_err(got, want)
    log(f"[compiler] {name}: compile_s={compile_s!r} "
        f"tpu_custom_calls={kernels} max_err={err!r}")
    if not pallas_interpret() and kernels == 0:
        raise SystemExit(f"FAIL {name}: no Mosaic kernel in the program")
    return err


def phase_compiler(gemm=GEMM_MNK, flash=(FLASH_SEQ, FLASH_HEAD),
                   tile=None) -> None:
    from repro.core import frontend as fe
    from repro.core.pipeline import compile_gemm, compile_traced

    rng = np.random.default_rng(0)
    m, n, k = gemm
    ck = compile_gemm(m, n, k, schedule="tpu_mxu_kgrid", tile=tile)
    a = rng.standard_normal((m, k)).astype(np.float32)
    b = rng.standard_normal((k, n)).astype(np.float32)
    check(f"generated gemm {m}x{n}x{k}",
          _run_kernel(f"gemm_{m}x{n}x{k}", ck, (a, b)), KERNEL_TOL)

    s, d = flash
    ck = compile_traced(fe.flash_attention_graph(s, s, d), tile=tile)
    q = (rng.standard_normal((s, d)) / np.sqrt(d)).astype(np.float32)
    kt = rng.standard_normal((d, s)).astype(np.float32)
    v = rng.standard_normal((s, d)).astype(np.float32)
    mask = np.where(np.tril(np.ones((s, s), bool)), 0.0,
                    -1e30).astype(np.float32)
    check(f"generated flash {s}x{s}x{d}",
          _run_kernel(f"flash_{s}x{s}x{d}", ck, (q, kt, v, mask)),
          KERNEL_TOL)


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------


def serve_models(cfg, max_len: int, model_parallel: int = 1):
    """The pallas-backend model and the XLA-backend model it is checked
    against, both bf16, over ``model_parallel`` devices."""
    run = RunConfig(param_dtype="bfloat16", activation_dtype="bfloat16",
                    cache_dtype="bfloat16", backend="pallas",
                    max_seq=max_len, model_parallel=model_parallel)
    return Model(cfg, run), Model(cfg, dataclasses.replace(run,
                                                           backend="xla"))


def init_alone(model, key):
    """Parameters on device 0 alone, built by one jit'd init on a 1x1
    mesh: an eager init compiles each op on its own (72 s for the 7-layer
    cut on a v5e)."""
    alone = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    return model.init(key, mesh=alone)


def first_logits(model, params, prompt, max_len: int):
    """Prefill logits (P, V) and the first greedy decode step's logits
    (V,), from one compiled program under the model's layout; also
    returns that program's text and compile seconds."""
    def run(params, tokens):
        cache = model.cache_init(1, max_len)
        lp, cache, _ = model.apply(params, tokens, cache=cache)
        tok = jnp.argmax(lp[:, -1], -1).astype(jnp.int32)[:, None]
        ld, _, _ = model.apply(params, tok, cache=cache)
        v = model.cfg.vocab_size
        return (lp[0, :, :v].astype(jnp.float32),
                ld[0, -1, :v].astype(jnp.float32))

    tokens = jnp.asarray(prompt[None, :], jnp.int32)
    t0 = time.perf_counter()
    mesh = model.mesh
    with axis_rules(mesh) if mesh is not None else contextlib.nullcontext():
        compiled = jax.jit(run).lower(params, tokens).compile()
    compile_s = time.perf_counter() - t0
    lp, ld = compiled(params, tokens)
    return np.asarray(lp), np.asarray(ld), compiled.as_text(), compile_s


def requests(cfg, n: int, prompt, out, seed: int):
    load = loadgen.LoadConfig(
        num_requests=n, vocab_size=cfg.vocab_size, seed=seed,
        prompt=loadgen.LengthDist("uniform", *prompt),
        output=loadgen.LengthDist("uniform", *out))
    return loadgen.generate_stream(load)


def serve(model, params, stream, *, slots: int, max_len: int):
    """Serve ``stream`` through ContinuousEngine as ``launch.serve
    --continuous`` does, on the model's layout; returns (rid -> tokens,
    wall seconds, the engine's bytes per chip of parameters and cache)."""
    engine = ContinuousEngine(model, params, slots=slots, max_len=max_len)
    t0 = time.perf_counter()
    for r in stream:
        while not engine.submit(Request(r.rid, r.prompt, r.max_new)):
            engine.step()
    results = engine.drain()
    wall = time.perf_counter() - t0
    missing = [r.rid for r in stream
               if len(results.get(r.rid, ())) != r.max_new]
    if missing:
        raise SystemExit(f"FAIL serve: requests {missing} did not complete")
    return results, wall, (engine.param_bytes_per_chip,
                           engine.cache_bytes_per_chip)


def cache_len(prompt, out) -> int:
    """Cache depth for the longest request, rounded up to whole 128-row
    blocks of the decode kernel."""
    return -(-(prompt[1] + out[1] + 1) // 128) * 128


def phase_serve(cfg, *, n_requests: int = 8, slots: int = 4,
                prompt=(32, 256), out=(16, 32), seed: int = 0) -> None:
    max_len = cache_len(prompt, out)
    model, model_xla = serve_models(cfg, max_len)
    t0 = time.perf_counter()
    params = init_alone(model, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"[serve] {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"params={model.param_count()} init_s={time.perf_counter() - t0!r} "
        f"interpret={pallas_interpret()}")
    stream = requests(cfg, n_requests, prompt, out, seed)

    lp, ld, text, compile_s = first_logits(model, params, stream[0].prompt,
                                           max_len)
    log(f"[serve] logits program: compile_s={compile_s!r} "
        f"tpu_custom_calls={text.count('tpu_custom_call')}")
    if not pallas_interpret() and "tpu_custom_call" not in text:
        raise SystemExit("FAIL serve: decode attention is not a Mosaic "
                         "kernel")
    xp, xd, _, _ = first_logits(model_xla, params, stream[0].prompt,
                                max_len)
    check("prefill logits pallas vs xla", rel_err(lp, xp), LOGITS_TOL)
    check("first decode logits pallas vs xla", rel_err(ld, xd), LOGITS_TOL)

    got, wall, _ = serve(model, params, stream, slots=slots,
                         max_len=max_len)
    tokens = sum(len(t) for t in got.values())
    log(f"[serve] pallas engine: requests={len(got)} tokens={tokens} "
        f"wall_s_incl_compile={wall!r}")
    ref, wall, _ = serve(model_xla, params, stream, slots=slots,
                         max_len=max_len)
    log(f"[serve] xla engine: requests={len(ref)} "
        f"wall_s_incl_compile={wall!r}")
    same = sum(int(np.sum(got[r] == ref[r])) for r in got)
    log(f"[serve] greedy tokens matching the xla engine: {same}/{tokens} "
        f"= {same / tokens!r}")
    log(f"[serve] peak_bytes_in_use={device_bytes('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_four_chips(cfg, *, cut: int = SERVE_LAYERS, n_requests: int = 4,
                     slots: int = 4, prompt=(32, 128), out=(8, 16),
                     seed: int = 0, model_parallel: int = 4) -> None:
    max_len = cache_len(prompt, out)
    model, _ = serve_models(cfg, max_len, model_parallel)
    before = device_bytes()
    params = model.init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    held = [a - b for a, b in zip(device_bytes(), before)]
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    log(f"[four] {cfg.name}: layers={cfg.num_layers} "
        f"model_parallel={model_parallel} param_bytes={total} "
        f"per_device_bytes={held}")
    # spread: each device holds about 1/model_parallel (the replicated
    # norms and biases are a small part of the weights); the CPU reports
    # no bytes
    share = 1.0 / model_parallel
    if jax.devices()[0].memory_stats() is not None and \
            not all(0.8 * share * total < h < 1.4 * share * total
                    for h in held[:model_parallel]):
        raise SystemExit(f"FAIL four: weights not spread: {held}")
    stream = requests(cfg, n_requests, prompt, out, seed)
    got, wall, (pbytes, cbytes) = serve(model, params, stream, slots=slots,
                                        max_len=max_len)
    log(f"[four] sharded engine: requests={len(got)} "
        f"tokens={sum(len(t) for t in got.values())} "
        f"param_bytes_per_chip={pbytes} cache_bytes_per_chip={cbytes} "
        f"wall_s_incl_compile={wall!r}")
    del params

    cut_cfg = dataclasses.replace(cfg, num_layers=cut)
    model, _ = serve_models(cut_cfg, max_len, model_parallel)
    alone, _ = serve_models(cut_cfg, max_len)
    key = jax.random.PRNGKey(seed)
    sharded = first_logits(model, model.init(key), stream[0].prompt,
                           max_len)
    single = first_logits(alone, init_alone(alone, key), stream[0].prompt,
                          max_len)
    check(f"{cut}-layer prefill logits sharded vs device 0",
          rel_err(sharded[0], single[0]), LOGITS_TOL)
    check(f"{cut}-layer first decode logits sharded vs device 0",
          rel_err(sharded[1], single[1]), LOGITS_TOL)
    log(f"[four] peak_bytes_in_use={device_bytes('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded serving path")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"[device] platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    if dev.platform != "tpu":
        log(f"FAIL: needs a TPU, JAX found platform {dev.platform!r}")
        return 1
    if args.four_chips and device["count"] < 4:
        log(f"FAIL: --four-chips needs 4 devices, found {device['count']}")
        return 1
    log(f"[cache] {enable_compile_cache()}")

    qwen = get_config("qwen2_7b")
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(qwen)
    else:
        phase_compiler()
        phase_serve(dataclasses.replace(qwen, num_layers=SERVE_LAYERS))
    log(f"[done] wall_s={time.perf_counter() - t0!r}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
