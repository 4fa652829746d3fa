"""Continuous-batching scheduler: results must match single-request
generation exactly (greedy), regardless of slot scheduling order — and
the batched engine (one vmap'd jit'd decode step across all slots) must
be bit-identical to the serial per-slot reference engine."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.models.model import Model, RunConfig
from repro.serve.engine import (ContinuousEngine, Engine, EngineConfig,
                                Request, SerialSlotEngine)
from repro.serve.metrics import ServeMetrics, VirtualClock


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("qwen2_7b"))
    model = Model(cfg, RunConfig(max_seq=64))
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def setup_ssm():
    cfg = reduced(get_config("mamba2_130m"))
    model = Model(cfg, RunConfig(max_seq=64))
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _reduced_setup(arch, backend="xla"):
    cfg = reduced(get_config(arch))
    model = Model(cfg, RunConfig(max_seq=64, backend=backend))
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def setup_windows():
    """Local and global attention layers in one scan (gemma3)."""
    return _reduced_setup("gemma3_4b")


@pytest.fixture(scope="module")
def setup_recurrent():
    """RG-LRU states (rewritten whole) beside attention K/V (appended)
    in one scanned group."""
    return _reduced_setup("recurrentgemma_2b")


@pytest.fixture(scope="module")
def setup_pallas():
    """The decode kernel (interpreted here), batched over slots."""
    return _reduced_setup("qwen2_7b", backend="pallas")


def _mixed_requests(cfg, n=6, seed=1):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        (4 + i,)).astype(np.int32),
                    max_new=int(rng.integers(1, 8)))
            for i in range(n)]


def test_continuous_matches_sequential(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        (4 + i,)).astype(np.int32),
                    max_new=5)
            for i in range(6)]

    ce = ContinuousEngine(model, params, slots=2, max_len=64)
    got = ce.serve(list(reqs))

    eng = Engine(model, params, EngineConfig(max_len=64))
    for r in reqs:
        want = eng.generate(r.prompt[None, :], r.max_new)[0,
                                                          len(r.prompt):]
        np.testing.assert_array_equal(got[r.rid][:r.max_new], want,
                                      err_msg=f"request {r.rid}")


def test_more_requests_than_slots(setup):
    cfg, model, params = setup
    reqs = [Request(rid=i, prompt=np.arange(3, dtype=np.int32),
                    max_new=3) for i in range(7)]
    ce = ContinuousEngine(model, params, slots=3, max_len=32)
    got = ce.serve(reqs)
    assert sorted(got) == list(range(7))
    for v in got.values():
        assert len(v) == 3


@pytest.mark.parametrize("fixture", ["setup", "setup_ssm", "setup_windows",
                                     "setup_recurrent", "setup_pallas"])
def test_batched_bit_identical_to_serial(fixture, request):
    """Acceptance: the vmap-batched decode step emits bit-identical
    greedy token streams to the old per-slot B=1 engine on a mixed
    request set (different prompt lengths, different max_new incl. 1)."""
    cfg, model, params = request.getfixturevalue(fixture)
    reqs = _mixed_requests(cfg)
    batched = ContinuousEngine(model, params, slots=2, max_len=64).serve(
        [Request(r.rid, r.prompt, r.max_new) for r in reqs])
    serial = SerialSlotEngine(model, params, slots=2, max_len=64).serve(
        [Request(r.rid, r.prompt, r.max_new) for r in reqs])
    assert sorted(batched) == sorted(serial) == [r.rid for r in reqs]
    for r in reqs:
        np.testing.assert_array_equal(batched[r.rid], serial[r.rid],
                                      err_msg=f"request {r.rid}")
        assert len(batched[r.rid]) == r.max_new


@pytest.mark.parametrize("fixture,inplace", [("setup", 2), ("setup_ssm", 2)])
def test_inplace_cache_leaves(fixture, inplace, request):
    """Appended attention K/V, and the SSD state and convolution window
    (two leaves a layer kind each), ride the layer scan in place."""
    cfg, model, params = request.getfixturevalue(fixture)
    eng = ContinuousEngine(model, params, slots=2, max_len=32)
    assert eng.inplace_cache_leaves == inplace
    per_layer = {n: c for n, c in eng._stacked.items() if n != "len"}
    assert eng.cache_leaves == len(jax.tree.leaves(per_layer)) > 0


@pytest.mark.parametrize("engine_cls", [ContinuousEngine, SerialSlotEngine])
def test_max_new_one_emits_exactly_one_token(setup, engine_cls):
    """Regression: admit() samples the first token at prefill, so a
    max_new=1 request must finish WITHOUT a decode step (the old
    engine emitted 2 tokens)."""
    cfg, model, params = setup
    reqs = [Request(rid=0, prompt=np.arange(4, dtype=np.int32), max_new=1),
            Request(rid=1, prompt=np.arange(5, dtype=np.int32), max_new=3)]
    got = engine_cls(model, params, slots=2, max_len=32).serve(reqs)
    assert len(got[0]) == 1
    assert len(got[1]) == 3
    eng = Engine(model, params, EngineConfig(max_len=32))
    want = eng.generate(reqs[0].prompt[None, :], 1)[0, 4:]
    np.testing.assert_array_equal(got[0], want)


def test_submit_step_api_and_backpressure(setup):
    cfg, model, params = setup
    eng = ContinuousEngine(model, params, slots=2, max_len=32,
                           queue_limit=2)
    reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32), max_new=4)
            for i in range(5)]
    assert eng.submit(reqs[0])
    assert eng.submit(reqs[1])
    assert not eng.submit(reqs[2])       # queue full -> backpressure
    assert eng.queue_depth == 2
    eng.step()                           # admits into both slots + 1 decode
    assert eng.active_slots == 2 and eng.queue_depth == 0
    assert eng.submit(reqs[2]) and eng.submit(reqs[3])
    eng.drain()
    assert not eng.busy
    assert sorted(eng.results) == [0, 1, 2, 3]
    for v in eng.results.values():
        assert len(v) == 4


def test_batched_engine_records_metrics(setup):
    cfg, model, params = setup
    metrics = ServeMetrics(VirtualClock(), slots=2)
    eng = ContinuousEngine(model, params, slots=2, max_len=32,
                           metrics=metrics)
    reqs = [Request(rid=i, prompt=np.arange(3 + i, dtype=np.int32),
                    max_new=3) for i in range(4)]
    eng.serve(reqs)
    snap = metrics.snapshot()
    assert snap["requests"]["submitted"] == 4
    assert snap["requests"]["completed"] == 4
    assert snap["tokens"]["decode"] == 4 * 3
    assert snap["tokens"]["prefill"] == sum(3 + i for i in range(4))
    assert snap["ttft"]["count"] == 4
    assert snap["tpot"]["count"] == 4 * 2     # gaps between 3 tokens
    assert snap["slot_utilization"] > 0


def test_max_len_truncates_generation(setup):
    """A request whose prompt+output would overflow max_len finishes at
    the cache boundary instead of writing past it."""
    cfg, model, params = setup
    req = Request(rid=0, prompt=np.arange(8, dtype=np.int32), max_new=50)
    got = ContinuousEngine(model, params, slots=1, max_len=16).serve([req])
    ref = SerialSlotEngine(model, params, slots=1, max_len=16).serve(
        [Request(0, req.prompt, 50)])
    np.testing.assert_array_equal(got[0], ref[0])
    assert len(got[0]) < 50


def test_temperature_sampling_stays_in_vocab(setup):
    cfg, model, params = setup
    eng = ContinuousEngine(model, params, slots=2, max_len=32,
                           temperature=1.0, seed=3)
    reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32), max_new=4)
            for i in range(3)]
    got = eng.serve(reqs)
    for v in got.values():
        assert v.min() >= 0 and v.max() < cfg.vocab_size

    # per-slot keys are folded from (seed, rid): same seed -> same streams
    eng2 = ContinuousEngine(model, params, slots=2, max_len=32,
                            temperature=1.0, seed=3)
    got2 = eng2.serve([Request(i, np.arange(4, dtype=np.int32), 4)
                       for i in range(3)])
    for rid in got:
        np.testing.assert_array_equal(got[rid], got2[rid])


class _OrderLog(ServeMetrics):
    """Logs each request's admission and tokens beside the host's waits."""

    def __init__(self, log):
        super().__init__(VirtualClock(), slots=2)
        self.log = log

    def on_admit(self, rid, prompt_len):
        self.log.append(("admit", rid))
        super().on_admit(rid, prompt_len)

    def on_token(self, rid):
        self.log.append(("token", rid))
        super().on_token(rid)


class _WaitedToken:
    """A prefill's first token that logs when the host reads it."""

    def __init__(self, tok, rid, log):
        self.tok, self.rid, self.log = tok, rid, log

    def __getitem__(self, i):
        self.log.append(("wait", self.rid))
        return self.tok[i]


@pytest.mark.parametrize("max_new", [1, 3])
def test_first_token_hook_follows_host_wait(setup, max_new):
    """``on_token`` for a request's first token comes after the host has
    read it from the prefill, both where the request finishes at once
    (max_new=1) and where it takes a slot; ``on_admit`` comes before."""
    cfg, model, params = setup
    log = []
    eng = ContinuousEngine(model, params, slots=2, max_len=32,
                           metrics=_OrderLog(log))
    prefill, write = eng._prefill_one, eng._write_slot
    rids = iter(range(2))

    def logged_prefill(*args):
        tok0, cache = prefill(*args)
        return _WaitedToken(tok0, next(rids), log), cache

    eng._prefill_one = logged_prefill
    eng._write_slot = lambda st, tok, keys, cache, tok0, *rest: write(
        st, tok, keys, cache, tok0.tok, *rest)
    eng.serve([Request(rid=i, prompt=np.arange(4 + i, dtype=np.int32),
                       max_new=max_new) for i in range(2)])
    for rid in range(2):
        mine = [e for e, r in log if r == rid]
        assert mine[:3] == ["admit", "wait", "token"], mine
        assert mine.count("token") == max_new


def test_prefill_compiles_counts_new_lengths(setup):
    """The first prefill of each prompt length counts as a compile; a
    length seen before does not, in this engine's life."""
    cfg, model, params = setup
    eng = ContinuousEngine(model, params, slots=2, max_len=32)
    assert eng.prefill_compiles == 0
    eng.serve([Request(rid=i, prompt=np.arange(n, dtype=np.int32),
                       max_new=2) for i, n in enumerate([4, 5, 4, 5, 6])])
    assert eng.prefill_compiles == 3
    eng.serve([Request(rid=10 + i, prompt=np.arange(n, dtype=np.int32),
                       max_new=2) for i, n in enumerate([6, 4])])
    assert eng.prefill_compiles == 3
    assert eng._prefill_one._cache_size() == 3    # what jit compiled


def _pieces_of(model, n):
    """``model`` with its run prefilling prompts ``n`` tokens a step."""
    return Model(model.cfg, dataclasses.replace(model.run, prefill_chunk=n))


def _pieces_per_step(eng):
    """Wrap ``eng``'s prefill programs to count the pieces each step
    runs; returns the list of [slots decoding at the step's start,
    pieces] the counts go to."""
    counts, prefill, extend = [], eng._prefill_one, eng._extend_one

    def counted(fn):
        def run(*a):
            counts[-1][1] += 1
            return fn(*a)
        return run

    step = eng._step

    def stepped():
        counts.append([eng.active_slots, 0])
        return step()

    eng._prefill_one, eng._extend_one = counted(prefill), counted(extend)
    eng._step = stepped
    return counts


@pytest.mark.parametrize("fixture", ["setup", "setup_pallas"])
def test_piecewise_prefill_streams_equal_whole_prompts(fixture, request):
    """Prompts prefilled in pieces of 3 tokens give the same greedy
    streams as whole prompts, max_new=1 requests included."""
    cfg, model, params = request.getfixturevalue(fixture)
    reqs = _mixed_requests(cfg, n=7)
    whole = ContinuousEngine(model, params, slots=2, max_len=64)
    want = whole.serve([Request(r.rid, r.prompt, r.max_new) for r in reqs])
    eng = ContinuousEngine(_pieces_of(model, 3), params, slots=2, max_len=64)
    got = eng.serve([Request(r.rid, r.prompt, r.max_new) for r in reqs])
    assert sorted(got) == sorted(want)
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])


def test_piecewise_prefill_runs_one_piece_while_slots_decode(setup):
    """A step with no slot decoding prefills whole prompts, every free
    slot filled; while slots decode, a step runs one piece at most."""
    cfg, model, params = setup
    eng = ContinuousEngine(_pieces_of(model, 4), params, slots=3, max_len=64)
    prefill, extend = eng._prefill_one, eng._extend_one
    counts = _pieces_per_step(eng)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, (10,))
                    .astype(np.int32), max_new=3 + i) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    assert counts == [[0, 9]]             # 3 prompts of 10 tokens: 4+4+2
    assert eng.active_slots == 3
    eng.drain()
    assert max(n for active, n in counts if active) == 1
    assert any(n == 0 for active, n in counts if active)
    assert sum(n for _, n in counts) == 6 * 3
    assert sorted(eng.results) == list(range(6))
    assert [len(eng.results[i]) for i in range(6)] == [3 + i
                                                       for i in range(6)]
    # pieces of 4 and 2, first or later in a prompt: three programs
    assert eng.prefill_compiles == 3
    assert prefill._cache_size() == 1
    assert extend._cache_size() == 2


def test_prefill_chunk_comes_from_the_run(setup):
    cfg, model, params = setup
    assert ContinuousEngine(model, params, slots=2,
                            max_len=64).prefill_chunk == 0
    assert ContinuousEngine(_pieces_of(model, 16), params, slots=2,
                            max_len=64).prefill_chunk == 16
