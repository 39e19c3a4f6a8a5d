"""Profiler hooks: labelled device timelines and opt-in trace capture.

Two kinds of label, matching where the cost lives:

* :func:`scope` — ``jax.named_scope``.  A **trace-time** label: it
  names the HLO ops emitted under it, so profiler timelines and HLO
  dumps show ``fedlay_mix/round0`` or ``codec/int8-block/encode``
  instead of anonymous fusions.  Zero runtime cost — it exists only
  while tracing, so it is safe on the hottest path and cannot disturb
  fusion or retrace behavior.
* :func:`annotation` — ``jax.profiler.TraceAnnotation``.  A **runtime**
  host-side label for the profiler timeline (host rows), on the same
  clock as the device planes.  Every
  :meth:`~repro.obs.events.Telemetry.span` enters one, so the loop's and
  the controller's spans land in any profile being captured.  Used at
  step/swap boundaries only, never inside jitted code.

:func:`capture` wraps ``jax.profiler.trace``: pass a directory to get a
TensorBoard-loadable profile of the ``with`` body, pass None to no-op —
the shape behind ``launch/train.py --profile-dir``.

Everything degrades to a null context when jax (or the specific
profiler API) is unavailable, so importing this module never introduces
a hard jax dependency at module scope.
"""

from __future__ import annotations

from contextlib import contextmanager, nullcontext
from typing import ContextManager, Iterator, Optional


def scope(name: str) -> ContextManager:
    """``jax.named_scope(name)`` — label HLO emitted while tracing the
    ``with`` body.  Null context if jax is missing."""
    try:
        import jax
        return jax.named_scope(name)
    except Exception:
        return nullcontext()


def annotation(name: str, **kwargs) -> ContextManager:
    """``jax.profiler.TraceAnnotation`` — label a host-side block on
    the profiler timeline.  Null context when no profiler backend."""
    try:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name, **kwargs)
    except Exception:
        return nullcontext()


@contextmanager
def capture(log_dir: Optional[str]) -> Iterator[None]:
    """Profile the ``with`` body into ``log_dir`` (TensorBoard format)
    via ``jax.profiler.trace``; no-op when ``log_dir`` is None/empty."""
    if not log_dir:
        yield
        return
    import jax
    with jax.profiler.trace(str(log_dir)):
        yield
