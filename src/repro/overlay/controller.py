"""The live overlay controller: NDMP deltas → recompiled, hot-swapped mixers.

This is the host-side loop that makes the reproduction *practical* DFL
(paper §III-B deployment story): training proceeds on the compiled data
plane while NDMP maintains the overlay under churn; between training
steps the controller

1. advances the discrete-event simulator (and applies any scheduled
   churn events),
2. polls the :class:`~repro.overlay.events.DeltaTracker` for
   neighbor-table deltas,
3. on a delta, rebuilds the :class:`~repro.core.mixing.PermuteSchedule`
   for the current alive set
   (:func:`repro.core.mixing.schedule_from_addresses` over the live
   NDMP coordinates), and
4. hot-swaps the compiled mixer behind a schedule-keyed compile cache —
   an unchanged topology (or a revisited one) never retraces.

Two mixer kinds, matching the two device paths in
:mod:`repro.dist.sync`:

* ``"global"`` (default) — ``jax.jit(global_mixer("fedlay", sched))``,
  a ``params -> params`` program over the leading client axis (what
  :func:`repro.launch.steps.dfl_train_bundle` composes with);
* ``"shard_map"`` — the :func:`repro.dist.sync.make_mixer` shard_map
  body for callers that embed mixing in an explicit shard_map program.
  The cached callable has stable identity per schedule, so the caller's
  enclosing ``jax.jit`` also avoids retracing on cache hits.

**Grouped layout** (``clients_per_device = G > 1``): the client
population is ``G ×`` the device count, laid out block-contiguously
(client slot ``i`` → device ``i // G``, the ``(G, ...)`` per-device
contract of :mod:`repro.dist.sync`).  The group factor threads through
every layer the controller owns: shard_map mixer factories build
grouped programs, capacity mode requires ``capacity % G == 0`` so
padded schedules always map onto whole device groups, and the
:class:`MixerCache` needs no G in its keys — G is fixed per controller,
so the schedule digest alone still uniquely identifies a compiled
program.
"""

from __future__ import annotations

import dataclasses
import time as _time
from collections import OrderedDict
from typing import Callable, Dict, Iterable, Optional, Tuple

import numpy as np

from ..core.coords import NodeAddress
from ..core.mep import ClientProfile
from ..core.mixing import PermuteSchedule, schedule_from_addresses
from ..core.ndmp import SimulatorProtocol
from ..core.topology import Topology, fedlay_topology
from ..obs import get_telemetry
from .events import ChurnEvent, ChurnTrace, DeltaTracker, TableDelta

MIXER_KINDS = ("global", "shard_map")


class MixerCache:
    """Schedule-keyed LRU compile cache for mixers.

    Keys are ``(PermuteSchedule, fuse, codec)`` triples — schedules are
    hashable by perms+weights digest and codecs are frozen dataclasses,
    so two control epochs that converge to the same topology (including
    the common no-op delta) share one compiled program, while the same
    topology compiled for different mixing-round execution modes
    (``fuse=None`` tree walk vs ``fuse="flat"`` Pallas fused,
    :data:`repro.dist.sync.FUSE_MODES`) or different wire codecs
    (:mod:`repro.wire.codec`) never collides.
    ``maxsize`` bounds the pinned jit closures under sustained churn
    (fresh joiner ids mint a new schedule per membership change); the
    fail→rejoin zero-retrace win only needs the recent past.
    """

    def __init__(self, factory: Callable[[PermuteSchedule], Callable],
                 maxsize: int = 64):
        if maxsize < 1:
            raise ValueError("maxsize must be >= 1")
        self._factory = factory
        self._cache: "OrderedDict[Tuple, Callable]" = OrderedDict()
        self.maxsize = maxsize
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, sched: PermuteSchedule,
            fuse: Optional[str] = None,
            codec=None) -> Tuple[Callable, bool]:
        """(mixer, was_hit) for a (schedule, fuse mode, wire codec),
        compiling on first sight."""
        key = (sched, fuse, codec)
        mixer = self._cache.get(key)
        if mixer is not None:
            self.hits += 1
            self._cache.move_to_end(key)
            return mixer, True
        self.misses += 1
        mixer = self._factory(sched)
        self._cache[key] = mixer
        while len(self._cache) > self.maxsize:
            self._cache.popitem(last=False)
            self.evictions += 1
        return mixer, False

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def __len__(self) -> int:
        return len(self._cache)


def _global_mixer_factory(strategy: str = "fedlay", masked: bool = False,
                          fuse: Optional[str] = None, codec=None,
                          flat_io: bool = False):
    import jax
    from ..dist.sync import global_mixer

    def build(sched: PermuteSchedule) -> Callable:
        return jax.jit(global_mixer(strategy, sched, masked=masked,
                                    fuse=fuse, codec=codec,
                                    flat_io=flat_io))
    return build


def _shard_map_mixer_factory(axis_name: str, strategy: str = "fedlay",
                             clients_per_device: int = 1,
                             fuse: Optional[str] = None, codec=None):
    from ..dist.sync import make_mixer

    def build(sched: PermuteSchedule) -> Callable:
        return make_mixer(strategy, sched, axis_name, sched.num_clients,
                          clients_per_device=clients_per_device, fuse=fuse,
                          codec=codec)
    return build


@dataclasses.dataclass(frozen=True)
class _StagedSwap:
    """A fully built (but not yet live) data-plane state: what a control
    step produced, waiting for :meth:`OverlayController.commit` at the
    next step boundary."""

    alive: Tuple[int, ...]
    alive_schedule: PermuteSchedule
    schedule: PermuteSchedule            # == alive_schedule unless capacity
    mixer: Callable
    plan: Optional[object]               # RemapPlan in capacity mode


@dataclasses.dataclass(frozen=True)
class ControlReport:
    """What one control step did."""

    epoch: int                     # delta epoch after this step
    time: float                    # simulator clock after this step
    alive: Tuple[int, ...]         # slot order: sorted live node ids
    delta: TableDelta
    swapped: bool                  # a different mixer is now live
    rebuilt: bool                  # a schedule was (re)compiled host-side
    cache_hit: bool                # the mixer came out of the compile cache
    rebuild_ms: float              # host time spent building the schedule
    correctness: Optional[float] = None


class OverlayController:
    """Closes the loop between an NDMP engine (control plane) and the
    compiled mixer (data plane).

    The engine is anything satisfying
    :class:`repro.core.ndmp.SimulatorProtocol` — the exact discrete-event
    :class:`~repro.core.ndmp.Simulator` or the flat-array
    :class:`repro.scale.ndmp_vec.VectorSimulator`; the controller only
    consumes the delta API (alive_ids / neighbor_tables / tables_version
    / advance) plus the three membership calls.

    ``step(dt)`` advances NDMP by ``dt`` of simulated time, detects
    table deltas, and exposes the current compiled mixer via
    :attr:`mixer` (hot-swapped only when the topology actually changed).
    ``profiles_fn`` supplies MEP confidence profiles for an alive set;
    default: uniform profiles (simple ablation-style weights).  Profiles
    are assumed stable for a given alive set — schedules rebuild on
    membership change, not on profile drift.
    """

    def __init__(self, sim: SimulatorProtocol, *,
                 mixer_kind: str = "global",
                 strategy: str = "fedlay",
                 axis_name: str = "data",
                 alpha_d: float = 0.5, alpha_c: float = 0.5,
                 confidence_weighted: bool = True,
                 profiles_fn: Optional[
                     Callable[[Tuple[int, ...]],
                              Dict[int, ClientProfile]]] = None,
                 mixer_factory: Optional[
                     Callable[[PermuteSchedule], Callable]] = None,
                 cache_size: int = 64,
                 measure_correctness: bool = False,
                 capacity: Optional[int] = None,
                 double_buffered: bool = False,
                 clients_per_device: int = 1,
                 fuse: Optional[str] = None,
                 codec=None,
                 flat_io: bool = False,
                 repair_policy=None,
                 swap_barrier: Optional[Callable[[], None]] = None):
        """``capacity`` switches the controller into fixed-capacity slot
        mode (:mod:`repro.runtime`): it owns a
        :class:`~repro.runtime.slots.SlotMap`, pads every rebuilt
        schedule to ``capacity`` (dead slots self-loop with weight 1),
        and compiles **mask-aware** mixers ``(params, mask) -> params``
        so the data-plane shapes never change under churn.

        ``clients_per_device`` (G) declares the grouped data-plane
        layout: client slot ``i`` lives on device ``i // G``.  shard_map
        mixer factories compile grouped programs for it, and capacity
        mode requires ``capacity`` to be a multiple of G so the padded
        schedule always fills whole device groups (capacity = G × the
        mesh's client-axis size is the intended deployment,
        e.g. via :class:`repro.runtime.SlotTrainLoop`'s ``mesh=``).

        ``double_buffered`` defers the hot swap to the step boundary:
        ``step()`` stages the rebuilt schedule + compiled mixer (and, in
        capacity mode, the slot remap plan) without touching the live
        ones; :meth:`commit` flips the buffers.  This lets a training
        loop overlap the control step with the in-flight training step
        and still swap at a well-defined boundary.

        ``fuse`` selects the mixing-round execution mode for the
        default mixer factories (``"flat"`` = the Pallas flat-buffer
        fused hot path, :mod:`repro.dist.sync` docs); the compile cache
        keys on it alongside the schedule digest, so fused and unfused
        programs for the same topology coexist without collisions.
        Ignored when an explicit ``mixer_factory`` is supplied (the
        factory owns its execution mode) — except that it still
        participates in the cache key.

        ``codec`` (a :mod:`repro.wire.codec` name or instance) makes the
        default factories compile wire-compressed mixers (implies
        ``fuse="flat"``); it keys the compile cache alongside the
        schedule and fuse mode.  For an error-feedback codec the
        compiled mixer signature grows a trailing residual (see
        :func:`repro.dist.sync.global_mixer`) — the slot train loop
        owns that state.  ``flat_io`` compiles mixers that consume and
        produce the raveled (capacity, N) flat buffer directly
        (resident flat params; global kind + fedlay/ring only), skipping
        the per-round ravel/unravel.

        ``repair_policy`` (a :class:`repro.faults.RepairPolicy`) makes
        NDMP repair *bounded instead of assumed*: after each control
        window, while ``sim.correctness()`` is below the policy target
        the controller re-advances the simulator by decorrelated-jitter
        backoff delays (giving repair traffic time to land) up to
        ``max_retries`` times, then proceeds degraded — tallied as
        ``faults.repair_retries`` / ``repair_recovered`` /
        ``repair_gave_up``.

        ``swap_barrier`` is the multi-process-mesh fault hook: a
        callable invoked in :meth:`commit` *before* a staged swap goes
        live (all processes must flip mixers at the same step
        boundary).  If it raises, the swap stays staged for the next
        boundary — the live mixer keeps serving — and
        ``faults.swap_barrier_aborts`` increments.  Single-process
        callers leave it None (no barrier, today's behavior).
        """
        if mixer_kind not in MIXER_KINDS:
            raise ValueError(f"unknown mixer kind {mixer_kind!r}; "
                             f"choose from {MIXER_KINDS}")
        self.sim = sim
        self.tracker = DeltaTracker(sim)
        self.strategy = strategy
        self.alpha_d, self.alpha_c = alpha_d, alpha_c
        self.confidence_weighted = confidence_weighted
        self.profiles_fn = profiles_fn
        self.measure_correctness = measure_correctness
        self.capacity = capacity
        self.double_buffered = double_buffered
        if clients_per_device < 1:
            raise ValueError("clients_per_device must be >= 1")
        if capacity is not None and capacity % clients_per_device:
            raise ValueError(
                f"capacity {capacity} is not a multiple of "
                f"clients_per_device {clients_per_device}")
        from ..dist.sync import resolve_wire
        self.codec, self.fuse = resolve_wire(codec, fuse)
        self.flat_io = bool(flat_io)
        if self.flat_io and (mixer_kind != "global"
                             or self.fuse != "flat"):
            raise ValueError(
                "flat_io mixers need mixer_kind='global' and the flat "
                "fuse mode (fuse='flat' or a codec)")
        self.clients_per_device = clients_per_device
        self.slots = None
        if capacity is not None:
            if mixer_kind != "global" and mixer_factory is None:
                raise ValueError(
                    "capacity mode compiles mask-aware global mixers; "
                    "use mixer_kind='global' or pass a mixer_factory")
            from ..runtime.slots import SlotMap  # lazy: avoids the
            self.slots = SlotMap(capacity)       # runtime<->overlay cycle
        if mixer_factory is None:
            mixer_factory = (_global_mixer_factory(
                strategy, masked=capacity is not None, fuse=self.fuse,
                codec=self.codec, flat_io=self.flat_io)
                if mixer_kind == "global"
                else _shard_map_mixer_factory(axis_name, strategy,
                                              clients_per_device,
                                              fuse=self.fuse,
                                              codec=self.codec))
        self.cache = MixerCache(mixer_factory, maxsize=cache_size)
        self.repair_policy = repair_policy
        self.swap_barrier = swap_barrier
        self.repair_retries = 0
        self.repair_recovered = 0
        self.repair_gave_up = 0
        self.swap_barrier_aborts = 0
        self.rebuilds = 0
        self.swaps = 0
        self.last_commit_ms = 0.0
        self._alive: Tuple[int, ...] = ()
        self._schedule: Optional[PermuteSchedule] = None
        self._alive_schedule: Optional[PermuteSchedule] = None
        self._mixer: Optional[Callable] = None
        self._staged: Optional[_StagedSwap] = None
        self.last_plan = None
        # trace cursor: end of the last processed control window.  Starts
        # at -inf so events scheduled at or before the first window's
        # start (e.g. t=0 mass churn) are applied rather than silently
        # falling outside the half-open (t0, t1] window.
        self._applied_until = float("-inf")
        # initial build for the seed network (not counted as churn-driven
        # rebuild/swap activity; its compile-cache miss is kept).  The
        # initial swap commits immediately even when double-buffered.
        self._refresh(force=True)
        self.commit()
        self.last_plan = None
        self.rebuilds = 0
        self.swaps = 0

    # ---- public state ----------------------------------------------------
    @property
    def alive(self) -> Tuple[int, ...]:
        """Sorted live node ids — slot ``i`` of the schedule hosts
        ``alive[i]``."""
        return self._alive

    @property
    def schedule(self) -> PermuteSchedule:
        """The live schedule — capacity-padded in capacity mode."""
        assert self._schedule is not None
        return self._schedule

    @property
    def alive_schedule(self) -> PermuteSchedule:
        """The live schedule over the alive set only (unpadded) —
        slot ``i`` hosts ``alive[i]``.  Donor selection
        (:func:`~repro.overlay.runtime.joiner_donors`) works in this
        space."""
        assert self._alive_schedule is not None
        return self._alive_schedule

    def alive_mask(self):
        """(capacity,) 0/1 float32 alive mask (capacity mode only)."""
        assert self.slots is not None, "alive_mask needs capacity mode"
        return self.slots.alive_mask()

    @property
    def mixer(self) -> Callable:
        """The currently live compiled mixer."""
        assert self._mixer is not None
        return self._mixer

    @property
    def epoch(self) -> int:
        return self.tracker.epoch

    def topology(self) -> Topology:
        """The ideal FedLay graph over the current alive set (for the
        host-simulation engine and correctness accounting)."""
        return fedlay_topology(self._alive_addresses())

    # ---- the control step ------------------------------------------------
    def step(self, dt: float,
             events: Iterable[ChurnEvent] = (),
             trace: Optional[ChurnTrace] = None) -> ControlReport:
        """One control interval: apply churn scheduled up to ``now+dt``
        and not yet processed (the first window reaches back to -inf, so
        t=0 events fire), advance NDMP to ``now+dt``, then reconcile the
        data plane with the observed tables.

        The schedule is a pure function of the alive set (+ profiles),
        so only *membership* deltas force a rebuild; pointer-only deltas
        (NDMP repair in flight) advance the epoch without paying the
        host-side rebuild for a byte-identical schedule.

        The interval is the ``overlay.step`` span of the bus (a profiler
        annotation even with the bus off)."""
        bus = get_telemetry()
        with bus.span("overlay.step"):
            t_end = self.sim.now + dt
            due = list(events)
            if trace is not None:
                due.extend(trace.between(self._applied_until, t_end))
            self._applied_until = max(self._applied_until, t_end)
            ChurnTrace.apply(self.sim, sorted(due, key=lambda e: e.time))
            self.sim.run_until(t_end)
            if self.repair_policy is not None:
                self._repair_retry()
            delta = self.tracker.poll()
            if self._staged is None:
                self.last_plan = None
            swapped, rebuilt, cache_hit, rebuild_ms, alive = self._refresh(
                force=bool(delta.joined or delta.left))
            # host-side, step-boundary only (repro.obs contract)
            if bus.enabled:
                if delta.joined:
                    bus.count("overlay.churn_joins", len(delta.joined))
                if delta.left:
                    bus.count("overlay.churn_leaves", len(delta.left))
                if rebuilt:
                    bus.count("overlay.rebuilds")
                    bus.observe("overlay.rebuild_ms", rebuild_ms)
                if swapped:
                    bus.count("overlay.swaps")
                bus.count("overlay.cache_hits" if cache_hit
                          else "overlay.cache_misses")
            return ControlReport(
                epoch=self.tracker.epoch, time=self.sim.now,
                alive=alive, delta=delta, swapped=swapped,
                rebuilt=rebuilt, cache_hit=cache_hit, rebuild_ms=rebuild_ms,
                correctness=(self.sim.correctness()
                             if self.measure_correctness else None))

    def commit(self):
        """Apply the staged swap at the step boundary (no-op unless
        ``double_buffered`` staged one).  Returns the
        :class:`~repro.runtime.slots.RemapPlan` of the most recent
        applied membership change (None when membership is unchanged or
        outside capacity mode) so slot train loops can turn it into
        in-place row writes.

        :attr:`last_commit_ms` afterwards holds the host time the swap
        took (0 when nothing was staged) — the per-round commit-latency
        fact the :class:`repro.obs.rounds.RoundLedger` records.  The
        whole call is the ``overlay.commit`` span."""
        bus = get_telemetry()
        with bus.span("overlay.commit"):
            if self._staged is not None:
                if self.swap_barrier is not None:
                    try:
                        self.swap_barrier()
                    except Exception:
                        # a peer missed the boundary: keep serving the live
                        # mixer, leave the swap staged for the next commit
                        self.swap_barrier_aborts += 1
                        bus.count("faults.swap_barrier_aborts")
                        self.last_commit_ms = 0.0
                        return self.last_plan
                staged, self._staged = self._staged, None
                t0 = _time.perf_counter()
                self._apply(staged)
                self.last_commit_ms = (_time.perf_counter() - t0) * 1e3
                if bus.enabled:
                    bus.count("overlay.commits")
                    bus.observe("overlay.commit_ms", self.last_commit_ms)
            else:
                self.last_commit_ms = 0.0
            return self.last_plan

    # ---- internals -------------------------------------------------------
    def _repair_retry(self) -> bool:
        """Bounded wait-for-repair: advance the simulator by backoff
        delays until correctness recovers or the retry budget runs out.
        Returns True when the overlay met the target."""
        pol = self.repair_policy
        if self.sim.correctness() >= pol.correctness_target:
            pol.backoff.reset()
            return True
        bus = get_telemetry()
        for _ in range(pol.max_retries):
            self.repair_retries += 1
            bus.count("faults.repair_retries")
            self.sim.run_until(self.sim.now + pol.backoff.next_delay())
            if self.sim.correctness() >= pol.correctness_target:
                self.repair_recovered += 1
                bus.count("faults.repair_recovered")
                pol.backoff.reset()
                return True
        self.repair_gave_up += 1
        bus.count("faults.repair_gave_up")
        return False

    def _alive_addresses(self) -> Tuple[NodeAddress, ...]:
        return tuple(sorted(self.sim.alive_addresses(),
                            key=lambda a: a.node_id))

    def _refresh(self, force: bool) -> Tuple[bool, bool, bool, float,
                                             Tuple[int, ...]]:
        """Reconcile schedule+mixer with the live tables.

        Returns (swapped, rebuilt, cache_hit, rebuild_ms, alive).
        Without ``force`` (empty delta) the current mixer stays live and
        the step counts as a cache hit with no rebuild.  When
        ``double_buffered`` the rebuilt state is staged (``swapped``
        then means "a different mixer is pending") and goes live only at
        :meth:`commit`.
        """
        if not force and self._schedule is not None:
            # quiescent step: same schedule, genuine cache lookup, no
            # host-side rebuild and no retrace
            self._mixer, hit = self.cache.get(self._schedule, self.fuse,
                                              self.codec)
            alive = (self._staged.alive if self._staged is not None
                     else self._alive)
            return False, False, hit, 0.0, alive
        with get_telemetry().span("overlay.rebuild"):
            t0 = _time.perf_counter()
            addrs = self._alive_addresses()
            alive = tuple(a.node_id for a in addrs)
            profiles = (self.profiles_fn(alive)
                        if self.profiles_fn is not None else None)
            alive_sched = schedule_from_addresses(
                addrs, profiles=profiles, alpha_d=self.alpha_d,
                alpha_c=self.alpha_c,
                confidence_weighted=self.confidence_weighted)
            plan = None
            sched = alive_sched
            if self.slots is not None:
                from ..core.mixing import pad_schedule
                plan = self.slots.plan(alive)
                slot_of = plan.slot_of
                sched = pad_schedule(alive_sched,
                                     [slot_of[u] for u in alive],
                                     self.capacity)
            rebuild_ms = (_time.perf_counter() - t0) * 1e3
            self.rebuilds += 1
            mixer, hit = self.cache.get(sched, self.fuse, self.codec)
            swapped = sched != self._schedule
            if swapped:
                self.swaps += 1
            staged = _StagedSwap(alive=alive, alive_schedule=alive_sched,
                                 schedule=sched, mixer=mixer, plan=plan)
            if self.double_buffered:
                self._staged = staged
            else:
                self._apply(staged)
            return swapped, True, hit, rebuild_ms, alive

    def _apply(self, staged: _StagedSwap) -> None:
        """Make a staged swap live (slot remap, schedule, mixer)."""
        if staged.plan is not None:
            self.slots.apply(staged.plan)
            self.last_plan = staged.plan if staged.plan.changed else None
        self._alive = staged.alive
        self._alive_schedule = staged.alive_schedule
        self._schedule = staged.schedule
        self._mixer = staged.mixer
