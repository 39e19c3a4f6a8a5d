"""Continuous-batching serving plane over a fixed-capacity request
:class:`~repro.runtime.slots.SlotMap`.

The serving analogue of the churn-proof training runtime: the device
data plane keeps **one shape forever** — a (capacity,) request axis, a
slotted per-layer KV cache, and a per-slot position vector — and request
churn (a prompt arriving, a generation finishing) is an in-place row
write, never a re-stack or a retrace.  Prompt arrival = join (lowest
free slot, one batched prefill into a fresh B=1 cache, one row insert),
completion = leave (the slot's position is set to -1, which the whole
decode stack — :func:`repro.models.model.decode_step`,
:func:`repro.models.attention.cache_attention`, the Pallas
``flash_decode`` kernel — treats as an *empty slot*: zero attention
output, position frozen, row ready for the next tenant).

Slot lifecycle
--------------
::

    pending ──admit──► slot s: prefill(prompt) ─► pos[s] = len(prompt)
                         │ decode ticks: pos[s] += 1, token appended
                         ▼
    retire (max_new reached, or pos[s] would overflow cache_len)
                         │
                         ▼  pos[s] = -1  (empty; SlotMap frees s)

Admission policy is the whole continuous-vs-static story in one knob:
``policy="continuous"`` admits whenever a slot is free (requests join a
running batch mid-flight); ``policy="static"`` only admits into an
*empty* batch and then drains it completely — the classic static-batch
baseline ``benchmarks/serve_load.py`` measures against.

Zero-retrace contract: the prefill, insert, decode, and retire steps
are jitted once each via :func:`repro.runtime.loop.counting_jit`; slot
indices, positions, and tokens are traced device values, so occupancy
changes never retrace.  :attr:`ServeLoop.retraces` exposes the live
count (pinned to 0 after warmup by ``tests/test_serve.py``).

Position overflow is guarded host-side (a traced position cannot
``raise``): the loop tracks a host mirror of every slot's position and
force-retires a row before its next write would pass ``cache_len`` —
the eager/concrete decode path raises instead
(:func:`repro.models.attention.gqa_decode`).

Hot model reload: :meth:`ServeLoop.reload` swaps the parameter tree
between ticks (same treedef/shapes → no retrace);
:meth:`ServeLoop.reload_from_flat` lifts one client's row straight out
of the training loop's :class:`repro.dist.flat.FlatSpec` flat buffer
(``spec.unravel_row``) — the training→serving seam with no host
round-trip.

Telemetry: ``serve.*`` counters (admitted/completed/ticks/reloads),
occupancy/queue gauges, a ``serve.tick.ms`` span histogram, and one
:class:`repro.obs.rounds.RoundRecord` per batching tick on the ambient
round ledger, so the JSONL/summary plumbing is reused unchanged.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.model import decode_step, init_cache, prefill
from ..obs.events import get_telemetry
from ..obs.rounds import get_round_ledger
from .loop import counting_jit
from .slots import SlotMap

_CLOCK = time.perf_counter


@dataclasses.dataclass
class Request:
    """One generation request moving through the serving plane.

    ``prompt`` is the token prefix; ``max_new`` the number of tokens to
    generate (the token sampled from the prefill logits is the first).
    The loop fills ``tokens`` and the latency stamps: ``t_arrival``
    when the request became eligible (entered the queue), ``t_first``
    at its first sampled token, ``t_done`` at completion — all
    ``perf_counter`` seconds.

    Deadlines (straggler timeout): ``max_ticks`` bounds how many
    batching ticks the request may occupy a slot after admission;
    ``deadline_s`` is a wall-clock bound measured from ``t_arrival``.
    A request over either bound is force-retired with ``evicted=True``
    (and a ``serve.evictions`` counter) so a stuck generation can never
    occupy capacity forever."""

    rid: int
    prompt: np.ndarray
    max_new: int = 16
    arrival_tick: int = 0
    max_ticks: Optional[int] = None
    deadline_s: Optional[float] = None
    admit_tick: int = -1
    evicted: bool = False
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_arrival: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_arrival

    @property
    def done(self) -> bool:
        return self.t_done > 0.0


class ServeLoop:
    """Fixed-capacity continuous-batching decode loop (see module doc).

    Parameters
    ----------
    cfg, params : the model (``cfg.enc_dec`` is rejected — the serving
        plane is decoder-only).
    capacity : request slots (the static batch axis).
    cache_len : per-slot KV slots; every request's prompt+generation
        must fit (longer generations are force-retired).
    prompt_len : the static padded prompt width every admission is
        padded to (one prefill trace for all prompt lengths ≤ it).
    policy : ``"continuous"`` (admit into any free slot) or
        ``"static"`` (admit only into an empty batch, then drain).
    """

    def __init__(self, cfg, params, *, capacity: int, cache_len: int,
                 prompt_len: int, policy: str = "continuous"):
        if cfg.enc_dec:
            raise ValueError("ServeLoop is decoder-only")
        if policy not in ("continuous", "static"):
            raise ValueError(f"unknown policy {policy!r}")
        if prompt_len > cache_len:
            raise ValueError(f"prompt_len {prompt_len} > cache_len {cache_len}")
        if cfg.sliding_window and prompt_len > cfg.sliding_window:
            raise ValueError("padded prompts longer than the sliding window "
                             "are not servable (ragged ring prefill)")
        from ..models.model import layer_plan
        if any(k[0] == "mamba" for k in layer_plan(cfg)):
            raise ValueError("ServeLoop pads ragged prompts, which SSM "
                             "stacks cannot prefill; serve attention "
                             "models here")
        self.cfg = cfg
        self.params = params
        self.capacity = capacity
        self.cache_len = cache_len
        self.prompt_len = prompt_len
        self.policy = policy

        self.slots = SlotMap(capacity)
        # the KV cache holds K/V in the params' own dtype (a bf16 model
        # keeps a bf16 cache, half the HBM of an f32 one)
        cache_dtype = jnp.result_type(*jax.tree.leaves(params))
        self.cache = init_cache(cfg, params, capacity, cache_len,
                                dtype=cache_dtype, per_slot_pos=True)
        self._tok = jnp.zeros((capacity, 1), jnp.int32)
        self._pos_host = np.full((capacity,), -1, np.int64)
        self.pending: Deque[Request] = deque()
        self.active: Dict[int, Request] = {}
        self.completed: List[Request] = []
        self.tick_index = 0
        self._next_rid = 0

        cfg_ = cfg

        def _prefill_fn(params, tokens, lengths):
            c0 = init_cache(cfg_, params, 1, cache_len, dtype=cache_dtype,
                            per_slot_pos=True)
            logits, c1 = prefill(cfg_, params, c0, tokens, lengths=lengths)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return tok, c1

        def _insert_fn(cache, row, slot, tok, tokbuf):
            new = {"pos": jax.lax.dynamic_update_slice(
                cache["pos"], row["pos"].astype(cache["pos"].dtype), (slot,))}
            for key in cache:
                if key == "pos":
                    continue
                new[key] = jax.tree.map(
                    lambda d, s: jax.lax.dynamic_update_slice_in_dim(
                        d, s.astype(d.dtype), slot, axis=1),
                    cache[key], row[key])
            tokbuf = jax.lax.dynamic_update_slice(tokbuf, tok, (slot, 0))
            return new, tokbuf

        def _decode_fn(params, cache, tokbuf):
            logits, new_cache = decode_step(cfg_, params, cache, tokbuf)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return tok, new_cache

        def _retire_fn(cache, slot):
            new = dict(cache)
            new["pos"] = jax.lax.dynamic_update_slice(
                cache["pos"], jnp.full((1,), -1, cache["pos"].dtype), (slot,))
            return new

        # the cache is donated: every step rewrites it in place, so one
        # copy exists however many steps are queued ahead of the device
        self._prefill_j, self._tc_prefill = counting_jit(_prefill_fn)
        self._insert_j, self._tc_insert = counting_jit(_insert_fn,
                                                       donate_argnums=0)
        self._decode_j, self._tc_decode = counting_jit(_decode_fn,
                                                       donate_argnums=1)
        self._retire_j, self._tc_retire = counting_jit(_retire_fn,
                                                       donate_argnums=0)

    # ---- request intake --------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new: int = 16,
               arrival_tick: int = 0, max_ticks: Optional[int] = None,
               deadline_s: Optional[float] = None) -> Request:
        """Queue one request; returns its :class:`Request` handle.
        ``max_ticks`` / ``deadline_s`` set its eviction deadlines (see
        :class:`Request`)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError("prompt must be a non-empty 1-D token list")
        if prompt.size > self.prompt_len:
            raise ValueError(f"prompt length {prompt.size} > static "
                             f"prompt_len {self.prompt_len}")
        if max_ticks is not None and max_ticks < 1:
            raise ValueError("max_ticks must be >= 1")
        req = Request(rid=self._next_rid, prompt=prompt, max_new=max_new,
                      arrival_tick=arrival_tick, max_ticks=max_ticks,
                      deadline_s=deadline_s, t_arrival=_CLOCK())
        self._next_rid += 1
        self.pending.append(req)
        get_telemetry().count("serve.submitted")
        return req

    # ---- internals -------------------------------------------------------
    @property
    def retraces(self) -> int:
        """Fresh traces beyond each step's first — 0 after warmup is the
        zero-retrace-across-churn guarantee, observed live."""
        return (self._tc_prefill.retraces + self._tc_insert.retraces
                + self._tc_decode.retraces + self._tc_retire.retraces)

    @property
    def traces(self) -> int:
        return (self._tc_prefill.traces + self._tc_insert.traces
                + self._tc_decode.traces + self._tc_retire.traces)

    def _admit_one(self, req: Request) -> None:
        bus = get_telemetry()
        slot = self.slots.alloc(req.rid)
        P = self.prompt_len
        padded = np.zeros((1, P), np.int32)
        padded[0, :req.prompt.size] = req.prompt
        lengths = jnp.asarray([req.prompt.size], jnp.int32)
        tok, row = self._prefill_j(self.params, jnp.asarray(padded), lengths)
        self.cache, self._tok = self._insert_j(
            self.cache, row, jnp.asarray(slot, jnp.int32), tok, self._tok)
        self._pos_host[slot] = req.prompt.size
        req.admit_tick = self.tick_index
        req.t_first = _CLOCK()
        req.tokens.append(int(tok[0, 0]))
        self.active[slot] = req
        bus.count("serve.admitted")
        if req.max_new <= 1:
            self._retire(slot, req)

    def _retire(self, slot: int, req: Request) -> None:
        req.t_done = _CLOCK()
        self.slots.free(req.rid)
        self.cache = self._retire_j(self.cache,
                                    jnp.asarray(slot, jnp.int32))
        self._pos_host[slot] = -1
        del self.active[slot]
        self.completed.append(req)
        get_telemetry().count("serve.completed")

    # ---- the batching tick -----------------------------------------------
    def tick(self) -> int:
        """One batching tick: admissions, then one decode step for the
        whole slot axis.  Returns the number of live requests after the
        tick.  Emits one round-ledger record."""
        bus = get_telemetry()
        completed_before = len(self.completed)
        n_admit = 0
        n_evict = self._evict_overdue()
        # static batching = the one-line policy difference: only an
        # EMPTY batch may admit, and then it drains completely
        allow = self.policy == "continuous" or len(self.slots) == 0
        with bus.span("serve.tick"):
            while allow and self.pending and self.slots.num_free > 0:
                self._admit_one(self.pending.popleft())
                n_admit += 1
            if self.active:
                tok, self.cache = self._decode_j(self.params, self.cache,
                                                 self._tok)
                self._tok = tok
                toks = np.asarray(tok[:, 0])
                self._pos_host[self._pos_host >= 0] += 1
                for slot, req in list(self.active.items()):
                    req.tokens.append(int(toks[slot]))
                    # host-side overflow guard: the *next* decode would
                    # write at pos == cache_len → retire now
                    if (len(req.tokens) >= req.max_new
                            or self._pos_host[slot] >= self.cache_len):
                        self._retire(slot, req)
        self.tick_index += 1
        bus.count("serve.ticks")
        bus.gauge("serve.occupancy", len(self.slots))
        bus.gauge("serve.queue_depth", len(self.pending))
        ledger = get_round_ledger()
        if ledger is not None:
            ledger.record(round=self.tick_index, loop="serve",
                          num_alive=len(self.slots),
                          participating=len(self.slots),
                          retraces=self.retraces,
                          admitted=n_admit,
                          completed=len(self.completed) - completed_before,
                          evicted=n_evict,
                          queue_depth=len(self.pending))
        return len(self.active)

    def _evict_overdue(self) -> int:
        """Force-retire active requests past their deadlines (straggler
        timeout): the slot frees before this tick's admissions, so a
        stuck generation yields capacity the moment it expires."""
        bus = get_telemetry()
        n = 0
        now = _CLOCK()
        for slot, req in list(self.active.items()):
            over_ticks = (req.max_ticks is not None
                          and self.tick_index - req.admit_tick
                          >= req.max_ticks)
            over_wall = (req.deadline_s is not None
                         and now - req.t_arrival >= req.deadline_s)
            if over_ticks or over_wall:
                req.evicted = True
                self._retire(slot, req)
                bus.count("serve.evictions")
                n += 1
        return n

    def run(self, max_ticks: int = 100_000) -> List[Request]:
        """Tick until every submitted request has completed (or
        ``max_ticks``).  Returns the completed requests."""
        t = 0
        while (self.pending or self.active) and t < max_ticks:
            self.tick()
            t += 1
        if self.pending or self.active:
            raise RuntimeError(f"serving did not drain in {max_ticks} ticks")
        return self.completed

    # ---- hot model reload ------------------------------------------------
    def reload(self, params) -> None:
        """Swap the serving parameters between ticks.  Same
        treedef/shapes/dtypes → the jitted steps are cache hits (no
        retrace); in-flight requests continue on the new weights."""
        self.params = params
        get_telemetry().count("serve.reloads")

    def reload_from_flat(self, buf: jnp.ndarray, spec, row: int = 0) -> None:
        """Hot-reload from the training loop's (B, N) flat buffer: lift
        client ``row`` via ``spec.unravel_row`` and serve it."""
        self.reload(spec.unravel_row(buf[row]))
