"""Device time per round of the SSD chunked scan, forward, recomputed
and backward: the operations under the named scope ``model.ssd``,
averaged over the cell's chips."""

from chipbench import scopes


def read(ctx):
    return scopes.per_device_ms(
        ctx, lambda ops, dev, lo, hi: scopes.scoped_ns(
            ops, ("model.ssd",), lo, hi))
