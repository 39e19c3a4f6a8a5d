"""`repro.obs` — the unified telemetry plane.

Every runtime layer of the stack reports into this package: the
:class:`~repro.obs.events.Telemetry` bus carries typed counters,
gauges, histograms, and span events; the
:class:`~repro.obs.rounds.RoundLedger` joins them with data-plane facts
into one record per training round; :mod:`repro.obs.profile` labels
device timelines and captures profiles.  The paper's practicality
claims (per-round communication cost, repair latency under churn,
convergence progress — FedLay §V/§VI) are all observable live through
this plane.

Observability contract
======================

**Disabled by default, near zero-cost when disabled.**  The global
bus is the :data:`~repro.obs.events.NULL` no-op singleton and the
global ledger is ``None`` until a caller opts in (:func:`enable`,
``telemetry=...``, ``--telemetry-out``).  Instrumented code pays a
no-op method call (or a single ``is not None`` test) per *round*, never
per device op.  A span is the exception that still does one thing: it
enters a profiler annotation of its name (one span, two sinks — the
bus's ``<name>.ms`` histogram and the profiler's host plane), which
costs under a microsecond with no profile being captured; the
:class:`~repro.runtime.loop.SlotTrainLoop` opens about ten a round.

**Host-side only, at step/swap boundaries.**  Instruments are plain
Python updates recorded where the host already runs — controller
steps, commits, remaps, loop-step boundaries.  Nothing is branched or
called inside jitted code, so enabling telemetry cannot change traced
programs: the 0-retrace and kernel-fusion guarantees are byte-for-byte
untouched (the only in-trace construct is ``jax.named_scope``, which
exists at trace time only and names the ops of the step
(``step.fwd_bwd``, ``step.optimizer``) and of the model (``model.ssd``,
``model.attention``) on the profiler's device planes).  Measured on a
TPU v5e host in the gossip-training benchmark's ``mamba2-370m.c2.t256``
cell (``chipbench/``, 20 s windows of ~104 rounds): the median round was
193.435 ms with the loop's spans and the bus off, 193.436 ms with the
bus on, and 193.420 ms in the same program without the spans — under
0.01 % of the round either way.

**Event taxonomy.**  Names are ``<layer>.<signal>`` with unit suffixes
(``_ms``, ``_bytes``).  The layers currently emitting:

========================  ================================================
prefix                    signals
========================  ================================================
``overlay.*``             ``rebuilds``, ``swaps``, ``cache_hits``,
                          ``cache_misses``, ``churn_joins``,
                          ``churn_leaves``, ``rebuild_ms`` (histogram),
                          ``commit_ms`` (histogram); spans ``step``,
                          ``rebuild``, ``commit``
``slot.*`` / ``churn.*``  ``steps``, ``remaps``, ``num_alive`` /
  / ``cohort.*``          ``participating`` (gauges), ``wire_bytes``
                          counter; ``slot.*`` spans, each carrying
                          ``round``: ``round`` (the parent), ``batch``,
                          ``apply_plan``, ``step``, ``mix``,
                          ``loss_wait``, ``record``
``engine.*``              ``bytes_sent``, ``msgs_sent``, ``local_steps``,
                          ``suppressed``, ``evals``
``wire.*``                ``encodes``, ``decodes`` — ticked at *trace*
                          time (codec paths run inside jit), so they
                          count codec-program (re)compiles; zero in
                          steady state with a warm MixerCache
========================  ================================================

**Adding a counter** is one line at a host boundary::

    from ..obs import get_telemetry
    get_telemetry().count("overlay.my_signal")

No registration: the name shows up in :meth:`Telemetry.summary`, in
BENCH JSON telemetry blocks, and — as a per-round delta — in any
:class:`RoundLedger` bound to the bus.  Keep the ``<layer>.<signal>``
convention and unit suffixes so downstream joins stay mechanical.

**Per-round ledger.**  Loops accept ``ledger=`` (or pick up the global
one) and emit one :class:`RoundRecord` per round: wire/payload bytes
from the :func:`repro.dist.sync.sync_bytes_per_client` closed forms,
retrace deltas from :class:`~repro.runtime.loop.TraceCount`, cache
hit/miss and swap flags from the :class:`~repro.overlay.controller.
ControlReport`, repair (schedule rebuild) and commit latencies, churn
membership, masked loss/participation.  Export as JSONL
(``--telemetry-out``) or a terminal table (``summary_table()``).
"""

from .events import (NULL, NullTelemetry, Telemetry, TelemetryEvent,
                     disable, enable, get_telemetry, set_telemetry,
                     telemetry)
from .profile import annotation, capture, scope
from .rounds import (RoundLedger, RoundRecord, disabled, get_round_ledger,
                     round_ledger, set_round_ledger)

__all__ = [
    "NULL", "NullTelemetry", "Telemetry", "TelemetryEvent",
    "disable", "enable", "get_telemetry", "set_telemetry", "telemetry",
    "annotation", "capture", "scope",
    "RoundLedger", "RoundRecord", "disabled", "get_round_ledger",
    "round_ledger", "set_round_ledger",
]
