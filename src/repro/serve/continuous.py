"""Truly batched continuous batching: one jit'd decode step for all slots.

The engine keeps ``slots`` independent KV caches *stacked* along a
leading slot axis (each slot is the exact ``cache_init(1, max_len)``
pytree, so per-slot ``len`` scalars become a ``(slots,)`` vector) and
decodes every occupied slot in ONE ``jax.vmap``-batched, jit'd step —
instead of the per-slot B=1 Python loop of
:class:`repro.serve.engine.SerialSlotEngine`, which dispatches ``slots``
separate XLA computations per generated token.

Admission is decoupled from decode through a bounded pending queue
(``submit`` returns ``False`` when the queue is full — backpressure the
load generator must absorb).  Admitting a request runs the same B=1
prefill the serial engine uses and writes the prefilled cache into the
slot's rows of the stacked pytree, so engine state after admission is
bit-identical to the serial engine's; greedy decode token streams are
therefore bit-identical too (differential-tested in
``tests/test_continuous_batching.py``).

With the model's ``run.prefill_chunk`` n > 0, a prompt is prefilled in
pieces of at most n tokens, each on the B=1 cache its earlier pieces
filled. While any slot decodes, a step runs at most one piece before its
decode step, so an admission holds the decoding slots back by one piece
a step however long its prompt is; a step in which no slot decodes
prefills whole prompts, as with n = 0.

With a mesh (``mesh``, else the model's own layout, ``Model.mesh``),
parameters are expected already placed on it (see ``Model.init``), the
stacked caches are allocated on it under the sharding rules, and every
jit'd piece traces under those rules.  ``param_bytes_per_chip`` and
``cache_bytes_per_chip`` count what one device holds of each.

Per-slot sampling keys are derived by ``fold_in(base_key, rid)`` so the
token stream of one request never depends on which slot it landed in or
on what else is resident — unlike the serial engine's single sequential
key stream, whose sampled (temperature > 0) outputs depend on
scheduling order.  Greedy decoding is unaffected.

The host's work is marked on the profiler's clock (``TraceAnnotation``):
``serve.step`` around a step; in it ``serve.admit`` per admission, or
per piece of one (with ``serve.prefill``, or ``serve.prefill.compile``
for the first piece of a length and place in the prompt,
``serve.first_token`` for the host's wait on it and ``serve.write``), ``serve.decode`` (dispatch), ``serve.sync`` (the wait
for the step's tokens) and ``serve.emit`` (the per-slot bookkeeping and
hooks).  With the profiler off a span costs under a microsecond.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Any, Deque, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.distributed.sharding import (axis_rules, per_device_bytes,
                                        tree_shardings)
from repro.models.model import Model, mask_padded_vocab
from repro.serve.metrics import ServeMetrics

# prefill / decode step costs for deterministic VirtualClock runs (time
# units; WallClock.advance ignores them)
VIRTUAL_STEP_COST = 1.0
VIRTUAL_PREFILL_COST = 1.0


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (P,) int32
    max_new: int
    out: Optional[np.ndarray] = None


@dataclasses.dataclass
class _Filling:
    """An admission whose prompt is being prefilled."""
    req: Request
    key: jax.Array                   # the slot's sampling key
    sub: jax.Array                   # the first token's key
    done: int = 0                    # prompt tokens prefilled so far
    cache: Any = None                # the B=1 cache they filled


class ContinuousEngine:
    """Slot-based continuous batching with a single batched decode step.

    API:
      ``submit(req)``   enqueue; ``False`` = queue full (backpressure).
      ``step()``        admit into free slots (one piece of a prompt
                        at most while slots decode, where
                        ``run.prefill_chunk`` is set), then one batched
                        decode step across all occupied slots; returns
                        the number of tokens emitted.
      ``serve(reqs)``   run a request list to completion (differential-
                        test convenience; bypasses the queue limit).
      ``results``       rid -> generated ids (np.int32) of finished
                        requests.
    """

    def __init__(self, model: Model, params, slots: int = 4,
                 max_len: int = 256, temperature: float = 0.0,
                 seed: int = 0, queue_limit: Optional[int] = None,
                 metrics: Optional[ServeMetrics] = None,
                 plan=None, mesh=None):
        self.model = model
        self.params = params
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.temperature = float(temperature)
        self.queue_limit = queue_limit
        self.metrics = metrics
        self.plan = plan                       # ServeCompilePlan or None
        self.mesh = model.mesh if mesh is None else mesh
        self.prefill_chunk = model.run.prefill_chunk
        self.base_key = jax.random.PRNGKey(seed)

        self.pending: Deque[Request] = collections.deque()
        self.results: Dict[int, np.ndarray] = {}
        self._slot_req: List[Optional[Request]] = [None] * self.slots
        self._slot_hist: List[List[int]] = [[] for _ in range(self.slots)]
        self._slot_left = np.zeros(self.slots, np.int64)
        self._slot_len = np.zeros(self.slots, np.int64)
        self._filling: Optional[_Filling] = None
        # prefill programs run so far, (first piece?, length): the first
        # run of each compiles
        self._prefill_programs: Set[Tuple[bool, int]] = set()
        self.prefill_compiles = 0
        # of the cache's per-layer leaves, those the decode step writes in
        # place as the layer scan's carry (appended attention K/V, the
        # SSD state and convolution window)
        self.inplace_cache_leaves, self.cache_leaves = \
            model.cache_leaf_counts()

        shapes = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((self.slots,) + l.shape, l.dtype),
            model.cache_shapes(1, self.max_len))
        zeros = lambda: jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype),
                                     shapes)
        if self.mesh is None:
            self._stacked = zeros()
        else:
            axes = jax.tree.map(lambda a: "- " + a,
                                model.cache_axes(1, self.max_len))
            self._stacked = jax.jit(zeros, out_shardings=tree_shardings(
                axes, shapes, self.mesh))()
        self.param_bytes_per_chip = per_device_bytes(params)
        self.cache_bytes_per_chip = per_device_bytes(self._stacked)
        self._tok = jnp.zeros((self.slots, 1), jnp.int32)
        self._keys = jnp.stack([jax.random.fold_in(self.base_key, s)
                                for s in range(self.slots)])

        self._prefill_one = jax.jit(self._prefill)
        self._extend_one = jax.jit(self._extend, donate_argnums=(1,))
        self._write_slot = jax.jit(self._write, donate_argnums=(0, 1, 2))
        self._decode_all = jax.jit(self._batched_step, donate_argnums=(1,))

    # ---- jit'd pieces ------------------------------------------------------

    def _rules(self):
        """The mesh's sharding rules, for tracing and allocation."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return axis_rules(self.mesh)

    def _prefill(self, params, tokens1, key):
        cache1 = self.model.cache_init(1, self.max_len)
        logits, cache1, _ = self.model.apply(params, tokens1, cache=cache1)
        tok = self._sample(logits[:, -1], key)
        return tok, cache1

    def _extend(self, params, cache1, tokens1, key):
        """The next piece of a prompt, on the B=1 cache its earlier
        pieces filled."""
        logits, cache1, _ = self.model.apply(params, tokens1, cache=cache1)
        return self._sample(logits[:, -1], key), cache1

    def _write(self, stacked, tok_all, keys_all, cache1, tok0, key, s):
        """Write one prefilled B=1 cache into slot ``s``'s rows."""
        new = jax.tree.map(
            lambda big, one: jax.lax.dynamic_update_index_in_dim(
                big, one.astype(big.dtype), s, 0), stacked, cache1)
        tok = jax.lax.dynamic_update_index_in_dim(
            tok_all, tok0.astype(jnp.int32), s, 0)
        keys = jax.lax.dynamic_update_index_in_dim(keys_all, key, s, 0)
        return new, tok, keys

    def _batched_step(self, params, stacked, tok, active, keys):
        """ONE decode step for all slots: vmap over the stacked caches.

        Each slot runs the exact B=1 decode computation (own scalar
        ``len`` inside the vmap), so slots stay fully independent; the
        active mask freezes ``len`` (and zeroes the sampled token) for
        empty slots, whose garbage rows the next admission overwrites.
        """
        def one(cache, tok1, key):
            logits, new_cache, _ = self.model.apply(params, tok1[None, :],
                                                    cache=cache)
            key, sub = jax.random.split(key)
            nxt = self._sample(logits[:, -1], sub)
            return nxt[0], new_cache, key

        nxt, new_stacked, new_keys = jax.vmap(one)(stacked, tok, keys)
        new_stacked = dict(new_stacked)
        new_stacked["len"] = jnp.where(active, new_stacked["len"],
                                       stacked["len"])
        nxt = jnp.where(active, nxt, 0)
        return nxt[:, None], new_stacked, new_keys

    def _sample(self, logits, key):
        logits = mask_padded_vocab(logits.astype(jnp.float32),
                                   self.model.cfg.vocab_size)
        if self.temperature <= 0.0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.temperature, axis=-1).astype(jnp.int32)

    # ---- queue / admission -------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return len(self.pending)

    @property
    def active_slots(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @property
    def busy(self) -> bool:
        return bool(self.pending) or self._filling is not None or \
            self.active_slots > 0

    def submit(self, req: Request, arrival: Optional[float] = None) -> bool:
        """Enqueue; ``False`` (and no enqueue) when the admission queue
        is at ``queue_limit`` — backpressure for the load generator."""
        if self.queue_limit is not None and \
                len(self.pending) >= self.queue_limit:
            if self.metrics:
                self.metrics.on_reject(req.rid)
            return False
        if self.metrics:
            self.metrics.on_submit(req.rid, arrival)
        self.pending.append(req)
        return True

    def _admit(self, s: int, whole: bool = True) -> bool:
        """Prefill the next pending request into free slot ``s``.  With
        ``whole`` False, only the next piece of its prompt, the rest left
        to later steps.  True once slot ``s`` holds the request."""
        while self.pending or self._filling is not None:
            with TraceAnnotation("serve.admit"):
                if self._filling is None:
                    req = self.pending.popleft()
                    key = jax.random.fold_in(self.base_key, req.rid)
                    key, sub = jax.random.split(key)
                    self._filling = _Filling(req, key, sub)
                f = self._filling
                req = f.req
                tok0 = self._prefill_piece(f)
                while whole and f.done < len(req.prompt):
                    tok0 = self._prefill_piece(f)
                if f.done < len(req.prompt):
                    return False
                self._filling = None
                if self.metrics:
                    self.metrics.on_admit(req.rid, len(req.prompt))
                with TraceAnnotation("serve.first_token"):
                    first = int(tok0[0])
                if self.metrics:
                    self.metrics.on_token(req.rid)  # after the host has it
                if req.max_new <= 1:
                    # the prefill already sampled the request's only token —
                    # finish without occupying a slot (max_new=1 regression)
                    self.results[req.rid] = np.asarray([first], np.int32)
                    if self.metrics:
                        self.metrics.on_finish(req.rid)
                    if whole:
                        continue
                    return False
                with TraceAnnotation("serve.write"):
                    self._stacked, self._tok, self._keys = self._write_slot(
                        self._stacked, self._tok, self._keys, f.cache, tok0,
                        f.key, jnp.int32(s))
                self._slot_req[s] = req
                self._slot_hist[s] = [first]
                self._slot_left[s] = req.max_new - 1
                self._slot_len[s] = len(req.prompt)
                return True
        return False

    def _prefill_piece(self, f: _Filling) -> jax.Array:
        """Prefill the next piece of ``f``'s prompt, the whole rest of it
        where ``prefill_chunk`` is 0; the token sampled after it."""
        n = len(f.req.prompt) - f.done
        if self.prefill_chunk > 0:
            n = min(n, self.prefill_chunk)
        piece = jnp.asarray(f.req.prompt[None, f.done:f.done + n])
        program = (f.done == 0, n)
        compiles = program not in self._prefill_programs
        if compiles:
            self._prefill_programs.add(program)
            self.prefill_compiles += 1
        with TraceAnnotation("serve.prefill.compile" if compiles
                             else "serve.prefill"):
            if f.done == 0:
                tok0, f.cache = self._prefill_one(self.params, piece, f.sub)
            else:
                tok0, f.cache = self._extend_one(self.params, f.cache,
                                                 piece, f.sub)
        f.done += n
        if self.metrics:
            self.metrics.clock.advance(VIRTUAL_PREFILL_COST)
        return tok0

    def _finish(self, s: int) -> None:
        req = self._slot_req[s]
        self.results[req.rid] = np.asarray(self._slot_hist[s], np.int32)
        self._slot_req[s] = None
        if self.metrics:
            self.metrics.on_finish(req.rid)

    # ---- the serving loop --------------------------------------------------

    def step(self) -> int:
        """Admissions + one batched decode step; returns tokens emitted."""
        with self._rules(), TraceAnnotation("serve.step"):
            return self._step()

    def _step(self) -> int:
        free = [s for s in range(self.slots) if self._slot_req[s] is None]
        if self.prefill_chunk > 0 and len(free) < self.slots:
            if free:                          # slots decode: one piece
                self._admit(free[0], whole=False)
        else:
            for s in free:
                self._admit(s)
        active = np.asarray([r is not None for r in self._slot_req])
        if self.metrics:
            self.metrics.on_step(len(self.pending), int(active.sum()))
        if not active.any():
            return 0
        with TraceAnnotation("serve.decode"):
            self._tok, self._stacked, self._keys = self._decode_all(
                self.params, self._stacked, self._tok, jnp.asarray(active),
                self._keys)
        if self.metrics:
            self.metrics.clock.advance(VIRTUAL_STEP_COST)
        with TraceAnnotation("serve.sync"):
            toks = np.asarray(self._tok[:, 0])
        emitted = 0
        with TraceAnnotation("serve.emit"):
            for s in range(self.slots):
                if self._slot_req[s] is None:
                    continue
                self._slot_hist[s].append(int(toks[s]))
                if self.metrics:
                    self.metrics.on_token(self._slot_req[s].rid)
                emitted += 1
                self._slot_left[s] -= 1
                self._slot_len[s] += 1
                if self._slot_left[s] <= 0 or \
                        self._slot_len[s] >= self.max_len - 1:
                    self._finish(s)
        return emitted

    def drain(self, max_steps: Optional[int] = None) -> Dict[int, np.ndarray]:
        """Step until queue and slots are empty (or ``max_steps``)."""
        steps = 0
        while self.busy and (max_steps is None or steps < max_steps):
            self.step()
            steps += 1
        return self.results

    def serve(self, requests) -> Dict[int, np.ndarray]:
        """Run ``requests`` to completion; rid -> generated ids."""
        self.pending.extend(requests)        # bypass the queue limit
        if self.metrics:
            for r in requests:
                self.metrics.on_submit(r.rid)
        return self.drain()
