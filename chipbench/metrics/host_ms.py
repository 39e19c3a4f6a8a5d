"""Host time per round outside the loss wait: the program's
``slot.round`` span less its ``slot.loss_wait`` span.  It holds the
controller, the batch, the dispatches of the step and the mixer and the
records; a dispatch that blocks on the device counts here too (the
qwen3 mixer's, about 34 ms a round)."""

from chipbench import scopes


def read(ctx):
    lo, hi = ctx.window
    prog = scopes.of(ctx)
    ns = (scopes.span_ns(prog, ("slot.round",), lo, hi)
          - scopes.span_ns(prog, ("slot.loss_wait",), lo, hi))
    return ns / ctx.rounds / 1e6 or None
