"""Device time per round of the local step's optimizer: the operations
under the named scope ``step.optimizer`` (gradient clipping, the AdamW
update, applying it, and the masked select of live rows), averaged over
the cell's chips."""

from chipbench import scopes


def read(ctx):
    return scopes.per_device_ms(
        ctx, lambda ops, dev, lo, hi: scopes.scoped_ns(
            ops, ("step.optimizer",), lo, hi))
