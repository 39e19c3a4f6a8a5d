"""Device time per round of the local step's forward and backward
pass: the step program's device time less its operations under the
named scope ``step.optimizer``, averaged over the cell's chips.  The
step has these two phases; an operation XLA made without a scope
(13 % of the t2048 step) falls here.  Reads nothing where the step
carries no ``step.fwd_bwd`` scope."""

from chipbench import scopes
from chipbench.trace import program_ns


def read(ctx):
    def fwd_bwd(ops, dev, lo, hi):
        if not scopes.scoped_ns(ops, ("step.fwd_bwd",), lo, hi):
            return 0.0
        return (program_ns(dev, ctx.step_module, lo, hi)
                - scopes.scoped_ns(ops, ("step.optimizer",), lo, hi))

    return scopes.per_device_ms(ctx, fwd_bwd)
