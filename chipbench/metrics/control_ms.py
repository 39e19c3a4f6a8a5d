"""Host time per round of the control plane: the program's
``overlay.step`` span (NDMP's ``run_until``, the tracker poll, the
schedule refresh) plus its ``overlay.commit`` span (the staged swap)."""

from chipbench import scopes


def read(ctx):
    lo, hi = ctx.window
    ns = scopes.span_ns(scopes.of(ctx), ("overlay.step", "overlay.commit"),
                        lo, hi)
    return ns / ctx.rounds / 1e6 or None
