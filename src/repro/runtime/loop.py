"""The static-shape sibling of :class:`repro.overlay.runtime.ChurnTrainLoop`.

:class:`SlotTrainLoop` trains against a **fixed-capacity** client axis:
the jitted local step sees (capacity, ...) shapes on every step of the
run, no matter how membership churns — one trace ever per capacity,
versus the re-stack loop's one trace per distinct alive count.  The
moving parts:

* the :class:`~repro.overlay.controller.OverlayController` runs in
  capacity mode (it owns the :class:`~repro.runtime.slots.SlotMap`,
  pads rebuilt schedules so dead slots self-loop with weight 1, and
  compiles mask-aware mixers ``(params, mask) -> params``);
* membership changes become **in-place row writes** at the step
  boundary: joiners are written into their assigned slot (donor copy
  from the highest-confidence surviving neighbor — the paper's Fig. 18
  catch-up — or fresh init for all-joiner cohorts), leavers simply go
  dead in the mask;
* the local step is mask-aware (``(params, opt_state, batch, mask)``,
  e.g. :func:`repro.runtime.masked.masked_local_step` or
  :func:`repro.launch.steps.dfl_train_bundle` with ``masked=True``):
  dead slots compute but their updates are discarded;
* multirate participation (``periods``) rides the same mask: a slow
  client trains locally every step but only joins the mixing collective
  when ``step % k_u == 0`` — the mask is a runtime input, so this costs
  zero retraces.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.mixing import multirate_participation
from ..faults.plan import DataFaults, edge_mask_for
from ..overlay.controller import OverlayController
from ..overlay.events import ChurnTrace
from ..overlay.runtime import joiner_donors
from .slots import RemapPlan, plan_reset_slots


@dataclasses.dataclass
class TraceCount:
    """Counts Python re-executions of a jitted function's body — i.e.
    XLA traces.  ``retraces`` excludes the unavoidable first trace."""

    traces: int = 0

    @property
    def retraces(self) -> int:
        return max(0, self.traces - 1)


def counting_jit(fn: Callable, **jit_kwargs) -> Tuple[Callable, TraceCount]:
    """``jax.jit(fn, **jit_kwargs)`` plus a :class:`TraceCount` that
    ticks once per trace (compiled executions skip the Python body, so
    they don't count).  The retrace-tax instrumentation used by
    ``benchmarks/slot_runtime``, ``benchmarks/cohort_stream``, and the
    per-round ``retrace_delta`` of :class:`repro.obs.rounds.RoundLedger`.

    ``jit_kwargs`` pass straight through to ``jax.jit``
    (``donate_argnums``, ``static_argnums``, ...).  The counter ticks
    per trace of the *wrapped* body: calling the result from inside
    another jitted function counts that one (inlined) trace, and
    distinct static-arg values or donated-buffer shapes each count
    their own trace, exactly like jax's own cache."""
    import jax

    counter = TraceCount()

    def counted(*args, **kwargs):
        counter.traces += 1
        return fn(*args, **kwargs)
    return jax.jit(counted, **jit_kwargs), counter


# ---- capacity-row surgery (shared by SlotTrainLoop and the cohort
# streaming runtime, repro.scale.cohort) ----------------------------------

def stack_rows(trees):
    """Stack per-client trees into one capacity-stacked tree."""
    import jax
    return jax.tree.map(lambda *ls: jax.numpy.stack(ls), *trees)


def build_rows(make_row: Callable[[int], object], capacity: int,
               sharding=None):
    """Stack ``make_row(i)`` for every row ``i < capacity``.  With
    ``sharding`` (whose first dim splits the rows over devices) each
    device makes and stacks only the rows it holds, under
    ``jax.default_device``: the population is never gathered on one
    device, where published-width models do not fit together."""
    import jax
    if sharding is None:
        return stack_rows([make_row(i) for i in range(capacity)])
    made, parts = {}, []
    for dev, (rows, *_) in sharding.devices_indices_map(
            (capacity,)).items():
        lo, hi, _ = rows.indices(capacity)
        if (lo, hi) not in made:        # rows replicated on other axes
            with jax.default_device(dev):
                made[lo, hi] = stack_rows([jax.device_put(make_row(i), dev)
                                           for i in range(lo, hi)])
        parts.append(jax.device_put(made[lo, hi], dev))
    return jax.tree.map(
        lambda *ls: jax.make_array_from_single_device_arrays(
            (capacity,) + ls[0].shape[1:], sharding, list(ls)), *parts)


def tree_row(tree, i: int):
    """Row ``i`` of every leaf (one client's unstacked state)."""
    import jax
    return jax.tree.map(lambda l: l[i], tree)


def set_tree_row(tree, i: int, row):
    """Functionally write ``row`` into leaf row ``i`` (dtype-cast to the
    destination — the in-place membership write of the slot runtimes)."""
    import jax
    return jax.tree.map(
        lambda l, r: l.at[i].set(r.astype(l.dtype)), tree, row)


@dataclasses.dataclass
class SlotStepRecord:
    """One training step of the slot runtime."""

    step: int
    time: float
    num_alive: int
    participating: int
    loss: float
    swapped: bool
    cache_hit: bool
    joined: Tuple[int, ...]
    left: Tuple[int, ...]


class SlotTrainLoop:
    """Drive a mask-aware local step under churn with static shapes.

    Same host contract as :class:`~repro.overlay.runtime.ChurnTrainLoop`
    — ``make_params(node_id)`` one client's unstacked param tree,
    ``make_batch(node_ids, step)`` a stacked batch for the given alive
    set keyed by node identity — so the two loops are drop-in
    comparable on the same churn trace (the ``benchmarks/slot_runtime``
    parity check).  ``local_step`` is the mask-aware step ``(params,
    opt_state, batch, mask) -> (params, opt_state, metrics)``.

    ``periods`` (optional, node id → MEP period) enables multirate
    participation: the mixing mask at step t is ``alive & (t % k_u ==
    0)``; the local-step mask stays pure aliveness (slow clients keep
    training locally, per the paper's asynchrony model).

    With a wire codec on the controller (``OverlayController(codec=...)``)
    the loop matches the compiled mixer's signature automatically; for an
    **error-feedback** codec it also owns the residual leaf of the slot
    runtime state — a (capacity, N) f32 buffer threaded through every
    mixing round (``mixed, residual = mixer(params, mask, residual)``)
    and zeroed at joiner *and* leaver slots when a remap plan lands
    (:func:`repro.runtime.slots.plan_reset_slots`), so no slot ever
    inherits a previous tenant's compression error.

    With ``OverlayController(flat_io=True)`` the loop keeps the
    parameters **resident in flat form**: ``self.params`` is the raveled
    (capacity, N) buffer across steps, the mixer consumes and produces
    it directly, and the tree view exists only transiently inside the
    jitted local step (unravel → step → ravel in one program) and in
    host-side row surgery — the steady-state round never pays a
    host-visible ravel/unravel.

    ``mesh`` (optional) places the capacity axis on a real device mesh:
    every capacity-stacked row tree (params, optimizer state, batches,
    masks) is sharded over ``client_axis``, so with ``capacity = G ×
    devices`` each device hosts a block-contiguous group of G client
    slots — the grouped layout of :mod:`repro.dist.sync` — and the
    controller must declare the same factor
    (``OverlayController(clients_per_device=G)``).  After each step the
    loop re-pins params/opt state to that canonical row sharding, so
    the jitted local step sees identical shardings every step and the
    zero-retrace guarantee survives whatever layout GSPMD picks for the
    mixer output.

    The step counter persists across :meth:`run` calls, so churn traces
    and participation phases stay consistent when driven incrementally.
    """

    def __init__(self, controller: OverlayController, *,
                 local_step: Callable,
                 make_params: Callable[[int], object],
                 optimizer,
                 make_batch: Callable[[Sequence[int], int], object],
                 periods: Optional[Dict[int, float]] = None,
                 step_time: float = 1.0,
                 jit_local_step: bool = True,
                 mesh=None, client_axis: str = "data",
                 telemetry=None, ledger=None, trace_count=None,
                 health=None):
        """``telemetry`` / ``ledger`` opt into the :mod:`repro.obs`
        plane: an explicit bus / :class:`~repro.obs.rounds.RoundLedger`
        to report into (default: the process globals, which are the
        no-op bus / no ledger until enabled).  With ``jit_local_step``
        the step is jitted through :func:`counting_jit` and
        :attr:`trace_count` tracks its traces; callers that jit their
        own step (``jit_local_step=False``) may pass the matching
        ``trace_count`` so per-round retrace deltas stay observable.

        When the controller's simulator is a
        :class:`repro.faults.ChaosEngine` (it exposes ``data_faults()``)
        the loop runs **degraded rounds**: every step it lowers the
        active link outages / stragglers / partition to the (capacity,
        2L) unreachable-edge mask and passes it to the masked mixer's
        keyword-only ``edge_mask`` — a runtime input, so fault storms
        cost zero retraces.  ``health`` (a
        :class:`repro.faults.HealthTracker`) folds locally-observed
        suspect/evicted peers into the same mask through the versioned
        suspect → evict → heal lifecycle."""
        import jax

        if controller.slots is None:
            raise ValueError(
                "SlotTrainLoop needs a capacity-mode controller "
                "(OverlayController(..., capacity=C))")
        self.controller = controller
        self.capacity = controller.capacity
        self.mesh = mesh
        self.client_axis = client_axis
        if mesh is not None:
            devices = mesh.shape[client_axis]
            expect = controller.clients_per_device * devices
            if self.capacity != expect:
                raise ValueError(
                    f"capacity {self.capacity} != clients_per_device "
                    f"{controller.clients_per_device} × {devices} "
                    f"devices on axis {client_axis!r}")
        self.optimizer = optimizer
        self.make_params = make_params
        self.make_batch = make_batch
        self.periods = periods
        self.step_time = step_time
        self._jax = jax
        self._step = 0
        self._telemetry = telemetry
        self._ledger = ledger
        self.health = health
        # degraded-round plumbing: a ChaosEngine (or anything exposing
        # data_faults()) wrapped around the controller's simulator
        self._chaos_engine = (controller.sim
                              if hasattr(controller.sim, "data_faults")
                              else None)
        self._faults_on = self._chaos_engine is not None or health is not None
        self._last_fault_count = 0
        self.trace_count = (trace_count if trace_count is not None
                            else TraceCount())
        self._last_traces = 0
        # closed-form wire/payload bytes memo keyed on (strategy, L,
        # participating) — _record_round runs every step on the host
        self._bytes_cache: Dict[tuple, tuple] = {}

        # capacity-stacked state: live slots get their node's init, dead
        # slots zeros (their rows are masked and mixed as self-loops);
        # params and optimizer state are born sharded over the mesh
        nodes = [controller.slots.node_at(s) for s in range(self.capacity)]
        first = next((u for u in nodes if u is not None), None)
        if first is None:
            raise ValueError("controller has no live nodes")
        if None in nodes:
            # dead rows take a live row's shapes (that row is dropped
            # before the population is built)
            template = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(np.shape(l), l.dtype),
                make_params(first))

        def make_row(slot):
            if nodes[slot] is not None:
                return make_params(nodes[slot])
            return jax.tree.map(
                lambda l: jax.numpy.zeros(l.shape, l.dtype), template)
        stacked = build_rows(make_row, self.capacity, None if mesh is None
                             else self._row_sharding(np.empty(self.capacity)))
        init = jax.vmap(optimizer.init)
        if mesh is not None:
            init = jax.jit(init, out_shardings=jax.tree.map(
                self._row_sharding, jax.eval_shape(init, stacked)))
        self.opt_state = init(stacked)

        self.codec = controller.codec
        self.ef = self.codec is not None and self.codec.error_feedback
        self.flat_io = controller.flat_io
        self._spec = self._row_spec = None
        if self.flat_io or self.ef:
            from ..dist.flat import FlatSpec
            self._spec = FlatSpec.for_tree(stacked)
            self._row_spec = FlatSpec.for_tree(
                jax.tree.map(lambda l: l[:1], stacked))
        if self.flat_io:
            # params live raveled; the tree view exists only inside the
            # jitted step and in host-side row surgery
            self.params = self._shard_rows(self._spec.ravel(stacked))
            spec = self._spec

            def flat_step(buf, opt_state, batch, mask):
                p, o, m = local_step(spec.unravel(buf), opt_state,
                                     batch, mask)
                return spec.ravel(p), o, m
            if jit_local_step:
                self.local_step, self.trace_count = counting_jit(flat_step)
            else:
                self.local_step = flat_step
        else:
            self.params = self._shard_rows(stacked)
            if jit_local_step:
                self.local_step, self.trace_count = counting_jit(local_step)
            else:
                self.local_step = local_step
        # per-client flat-row element count, for the ledger's closed-form
        # wire accounting (lane-padded when a FlatSpec exists — that is
        # what a codec actually ships)
        self._row_elems = (self._spec.size if self._spec is not None
                           else sum(int(np.prod(l.shape[1:], dtype=np.int64))
                                    for l in jax.tree.leaves(stacked)))
        self.residual = (self._shard_rows(jax.numpy.zeros(
            (self.capacity, self._spec.size), jax.numpy.float32))
            if self.ef else None)
        self.records: List[SlotStepRecord] = []

    # ---- state surgery ---------------------------------------------------
    def _shard_rows(self, tree):
        """Pin capacity-stacked leaves to the canonical row sharding
        over ``mesh``'s client axis (no-op without a mesh; leaves
        without the leading capacity dim are replicated)."""
        if self.mesh is None:
            return tree
        return self._jax.tree.map(
            lambda l: self._jax.device_put(l, self._row_sharding(l)), tree)

    def _row_sharding(self, leaf):
        """Capacity rows over the client axis; anything else
        replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == self.capacity:
            spec = P(self.client_axis, *([None] * (leaf.ndim - 1)))
        else:
            spec = P()
        return NamedSharding(self.mesh, spec)

    def _row(self, tree, i: int):
        return tree_row(tree, i)

    def _set_row(self, tree, i: int, row):
        return set_tree_row(tree, i, row)

    def _tree_of_row(self, slot: int):
        """The (unstacked) param tree held at ``slot`` — a direct row
        read, or an unravel of one flat row in resident-flat mode."""
        if self.flat_io:
            return tree_row(
                self._row_spec.unravel(self.params[slot][None]), 0)
        return self._row(self.params, slot)

    def client_params(self, node_id: int):
        """The (unstacked) current model of one live client."""
        return self._tree_of_row(self.controller.slots.slot_of[node_id])

    def _apply_plan(self, plan: RemapPlan) -> Tuple[Tuple[int, ...],
                                                    Tuple[int, ...]]:
        """Membership change as in-place row writes: joiners get a donor
        copy (Fig. 18 catch-up from the highest-confidence surviving
        neighbor) or a fresh init when every neighbor is itself a
        joiner; leavers' rows just go dead in the mask.  Error-feedback
        residual rows at joiner and leaver slots are zeroed."""
        ctl = self.controller
        joiners = tuple(u for u, _ in plan.joiners)
        survivors = tuple(u for u, _ in plan.survivors)
        donors = (joiner_donors(ctl.alive_schedule, ctl.alive, joiners,
                                survivors) if joiners else {})
        for node, slot in plan.joiners:
            donor = donors.get(node)
            if donor is not None:
                row = self._tree_of_row(ctl.slots.slot_of[donor])
            else:
                row = self.make_params(node)
            if self.flat_io:
                flat = self._row_spec.ravel(
                    self._jax.tree.map(lambda l: l[None], row))[0]
                self.params = self.params.at[slot].set(flat)
            else:
                self.params = self._set_row(self.params, slot, row)
            self.opt_state = self._jax.tree.map(
                lambda l, r: l.at[slot].set(r.astype(l.dtype)),
                self.opt_state, self.optimizer.init(row))
        if joiners:
            self.params = self._shard_rows(self.params)
            self.opt_state = self._shard_rows(self.opt_state)
        if self.ef:
            reset = plan_reset_slots(plan)
            if reset:
                self.residual = self._shard_rows(
                    self.residual.at[np.asarray(reset)].set(0.0))
        return joiners, tuple(u for u, _ in plan.leavers)

    # ---- per-step masks and batches --------------------------------------
    def _mix_mask(self, alive: Tuple[int, ...],
                  alive_mask: np.ndarray, step: int) -> np.ndarray:
        if self.periods is None:
            return alive_mask
        part = multirate_participation(
            [self.periods.get(u, 1.0) for u in alive], step)
        mask = alive_mask.copy()
        slot_of = self.controller.slots.slot_of
        for i, u in enumerate(alive):
            mask[slot_of[u]] *= part[i]
        return mask

    def _edge_mask(self, now: float) -> Tuple[Optional[np.ndarray], int]:
        """The round's (capacity, 2L) unreachable-edge mask, or (None,
        0) when no fault plumbing is configured.  Chaos-engine
        data-plane faults and HealthTracker verdicts are unioned; the
        mask is host-built numpy, consumed as a runtime input."""
        if not self._faults_on:
            return None, 0
        df = (self._chaos_engine.data_faults()
              if self._chaos_engine is not None else DataFaults())
        if self.health is not None:
            self.health.poll(now)
            bad = self.health.unhealthy()
            if bad:
                df = DataFaults(down_pairs=df.down_pairs,
                                slow_nodes=df.slow_nodes | bad,
                                groups=df.groups)
        ctl = self.controller
        slot_nodes = [ctl.slots.node_at(s) for s in range(self.capacity)]
        em = edge_mask_for(ctl.schedule, slot_nodes, df)
        return em, int((em == 0.0).sum())

    def _faults_injected(self) -> int:
        """Chaos-engine injections since the previous round."""
        if self._chaos_engine is None or not hasattr(self._chaos_engine,
                                                     "counts"):
            return 0
        total = sum(self._chaos_engine.counts.values())
        delta, self._last_fault_count = (total - self._last_fault_count,
                                         total)
        return delta

    def _capacity_batch(self, alive: Tuple[int, ...], step: int):
        """Scatter the alive-set batch onto capacity rows (dead slots
        replay row 0's data; their compute is discarded by the mask)."""
        jnp = self._jax.numpy
        batch = self.make_batch(alive, step)
        pos = {u: i for i, u in enumerate(alive)}
        idx = np.zeros((self.capacity,), dtype=np.int32)
        for slot in range(self.capacity):
            node = self.controller.slots.node_at(slot)
            if node is not None:
                idx[slot] = pos[node]
        gather = jnp.asarray(idx)
        return self._jax.tree.map(
            lambda l: jnp.take(l, gather, axis=0), batch)

    # ---- crash/resume ----------------------------------------------------
    def state_dict(self) -> Dict[str, object]:
        """The full slot-runtime training state: the capacity-stacked
        params (flat (capacity, N) buffer in resident-flat mode), the
        optimizer state, and — for an error-feedback codec — the
        residual leaf.  Everything else (schedules, mixers, slot map)
        is a pure function of the controller's simulator, which the
        resume path reconstructs by replaying the control plane."""
        state = {"params": self.params, "opt_state": self.opt_state}
        if self.ef:
            state["residual"] = self.residual
        return state

    def save(self, path: str) -> None:
        """Checkpoint the training state + step counter + slot
        occupancy to ``path`` (:mod:`repro.ckpt.checkpoint` npz).

        The state is saved as its flattened leaf list (optimizer states
        are often NamedTuples/dataclasses the checkpoint treedef spec
        doesn't cover); :meth:`restore` unflattens against the live
        loop's own structure, so a resume must build the loop the same
        way (same capacity, codec, flat_io, optimizer)."""
        from ..ckpt.checkpoint import save as ckpt_save
        state = self.state_dict()
        leaves = [np.asarray(l) for l in self._jax.tree.leaves(state)]
        occupancy = [(-1 if self.controller.slots.node_at(s) is None
                      else int(self.controller.slots.node_at(s)))
                     for s in range(self.capacity)]
        ckpt_save(path, {"leaves": leaves},
                  metadata={"step": int(self._step), "slots": occupancy,
                            "ef": bool(self.ef),
                            "flat_io": bool(self.flat_io)})

    def restore(self, path: str) -> dict:
        """Exact resume from :meth:`save`: restores params / optimizer
        state / EF residual bit-for-bit and the step counter, after
        validating that this loop's slot occupancy matches the
        checkpoint's (the caller replays the control plane — same
        simulator seed and control windows — before restoring, see
        ``tests/test_faults.py``).  Returns the checkpoint metadata."""
        from ..ckpt.checkpoint import load as ckpt_load
        tree, meta = ckpt_load(path)
        if bool(meta.get("ef")) != self.ef or \
                bool(meta.get("flat_io")) != self.flat_io:
            raise ValueError(
                "checkpoint was written by a loop with a different "
                f"wire configuration (ef={meta.get('ef')}, "
                f"flat_io={meta.get('flat_io')})")
        occupancy = [(-1 if self.controller.slots.node_at(s) is None
                      else int(self.controller.slots.node_at(s)))
                     for s in range(self.capacity)]
        if list(meta.get("slots", ())) != occupancy:
            raise ValueError(
                "slot occupancy mismatch: replay the control plane to "
                f"the checkpoint step first (ckpt {meta.get('slots')} "
                f"vs live {occupancy})")
        template = self.state_dict()
        treedef = self._jax.tree.structure(template)
        want = self._jax.tree.leaves(template)
        leaves = tree["leaves"]
        if len(leaves) != len(want):
            raise ValueError(f"checkpoint has {len(leaves)} leaves, "
                             f"this loop expects {len(want)}")
        jnp = self._jax.numpy
        restored = []
        for have, exp in zip(leaves, want):
            arr = jnp.asarray(have)
            if arr.shape != exp.shape or arr.dtype != exp.dtype:
                raise ValueError(
                    f"leaf mismatch: checkpoint {arr.shape}/{arr.dtype} "
                    f"vs live {exp.shape}/{exp.dtype}")
            restored.append(arr)
        state = self._jax.tree.unflatten(treedef, restored)
        self.params = self._shard_rows(state["params"])
        self.opt_state = self._shard_rows(state["opt_state"])
        if self.ef:
            self.residual = self._shard_rows(state["residual"])
        self._step = int(meta["step"])
        # retrace accounting restarts at the live counter: the resumed
        # process pays its own (unavoidable) first traces
        self._last_traces = self.trace_count.traces
        self._last_fault_count = (
            sum(self._chaos_engine.counts.values())
            if self._chaos_engine is not None
            and hasattr(self._chaos_engine, "counts") else 0)
        return meta

    # ---- telemetry -------------------------------------------------------
    def _record_round(self, ledger, step: int, report, participating: int,
                      loss: float, joined, left, faults_injected: int = 0,
                      degraded_edges: int = 0) -> None:
        """One :class:`repro.obs.rounds.RoundRecord`: the closed-form
        wire/payload bytes for this round's participation, the retrace
        delta, and the control-plane latencies (repair = the schedule
        rebuild NDMP churn forced, commit = the staged-swap flip)."""
        from ..dist.sync import sync_bytes_per_client
        ctl = self.controller
        key = (ctl.strategy, ctl.schedule.num_spaces,
               max(int(participating), 1))
        cached = self._bytes_cache.get(key)
        if cached is None:
            row_bytes = 4 * self._row_elems
            kwargs = dict(num_spaces=key[1],
                          clients_per_device=ctl.clients_per_device,
                          active_clients=key[2])
            wire = sync_bytes_per_client(ctl.strategy, row_bytes,
                                         self.capacity, codec=ctl.codec,
                                         **kwargs)
            payload = (sync_bytes_per_client(ctl.strategy, row_bytes,
                                             self.capacity, **kwargs)
                       if ctl.codec is not None else wire)
            cached = self._bytes_cache[key] = (wire, payload)
        wire, payload = cached
        traces = self.trace_count.traces
        delta, self._last_traces = traces - self._last_traces, traces
        ledger.record(
            round=step, time=report.time, loop="slot",
            num_alive=len(report.alive), participating=int(participating),
            loss=loss, wire_bytes_per_client=wire,
            payload_bytes_per_client=payload,
            retraces=self.trace_count.retraces, retrace_delta=delta,
            swapped=report.swapped, rebuilt=report.rebuilt,
            cache_hit=report.cache_hit, joined=joined, left=left,
            repair_ms=report.rebuild_ms, commit_ms=ctl.last_commit_ms,
            faults_injected=faults_injected, degraded_edges=degraded_edges)

    # ---- the loop --------------------------------------------------------
    def run(self, num_steps: int,
            trace: Optional[ChurnTrace] = None) -> List[SlotStepRecord]:
        """``num_steps`` training steps, one control interval each.

        An explicit ``telemetry=``/``ledger=`` override on the loop is
        installed as the process bus/ledger for the duration of the run,
        so the whole stack underneath (controller ``overlay.*``
        counters, codec trace ticks) reports to the same place."""
        import contextlib

        jnp = self._jax.numpy
        ctl = self.controller
        from ..obs import get_telemetry, telemetry
        from ..obs.rounds import get_round_ledger, round_ledger
        stack = contextlib.ExitStack()
        if self._telemetry is not None:
            stack.enter_context(telemetry(self._telemetry))
        if self._ledger is not None:
            stack.enter_context(round_ledger(self._ledger))
        with stack:
            return self._run(num_steps, trace, jnp, ctl,
                             get_telemetry, get_round_ledger)

    def _run(self, num_steps, trace, jnp, ctl,
             get_telemetry, get_round_ledger) -> List[SlotStepRecord]:
        # each phase is a span of the bus (a profiler annotation even
        # with the bus off); ``slot.loss_wait`` is the one place the
        # host waits on the device
        bus = get_telemetry()
        for _ in range(num_steps):
            step = self._step
            with bus.span("slot.round", round=step):
                report = ctl.step(self.step_time, trace=trace)
                plan = ctl.commit()      # swap lands at the step boundary
                joined, left = ((), ())
                if plan is not None and plan.changed:
                    with bus.span("slot.apply_plan"):
                        joined, left = self._apply_plan(plan)
                with bus.span("slot.batch"):
                    alive = ctl.alive
                    alive_mask = ctl.alive_mask()
                    mix_np = self._mix_mask(alive, alive_mask, step)
                    mask = self._shard_rows(jnp.asarray(alive_mask))
                    mix_mask = self._shard_rows(jnp.asarray(mix_np))
                    batch = self._shard_rows(
                        self._capacity_batch(alive, step))
                    em_np, degraded = self._edge_mask(report.time)
                with bus.span("slot.step"):
                    params, opt_state, metrics = self.local_step(
                        self.params, self.opt_state, batch, mask)
                # the hot-swap seam: the controller's mask-aware mixer;
                # slow or dead slots pass through untouched.  EF codecs
                # thread the residual leaf through the round.  Under a
                # fault plane the edge mask is passed every round (even
                # all-ones, so the arity — and thus the trace — never
                # changes mid-run).
                with bus.span("slot.mix"):
                    mkw = ({} if em_np is None else
                           {"edge_mask": self._shard_rows(
                               jnp.asarray(em_np))})
                    if self.ef:
                        mixed, res = ctl.mixer(params, mix_mask,
                                               self.residual, **mkw)
                        self.residual = self._shard_rows(res)
                    else:
                        mixed = ctl.mixer(params, mix_mask, **mkw)
                    self.params = self._shard_rows(mixed)
                    self.opt_state = self._shard_rows(opt_state)
                with bus.span("slot.loss_wait"):
                    loss = float(np.asarray(metrics["loss"]))
                with bus.span("slot.record"):
                    part = int(mix_np.sum())
                    self.records.append(SlotStepRecord(
                        step=step, time=report.time, num_alive=len(alive),
                        participating=part, loss=loss,
                        swapped=report.swapped, cache_hit=report.cache_hit,
                        joined=joined, left=left))
                    if bus.enabled:
                        bus.count("slot.steps")
                        bus.gauge("slot.num_alive", len(alive))
                        bus.gauge("slot.participating", part)
                    ledger = (self._ledger if self._ledger is not None
                              else get_round_ledger())
                    if ledger is not None:
                        self._record_round(
                            ledger, step, report, part, loss, joined, left,
                            faults_injected=self._faults_injected(),
                            degraded_edges=degraded)
            self._step += 1
        return self.records
