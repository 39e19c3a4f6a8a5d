"""Share of the roofline reached by the flat mixer's ``gather_mix``
kernel (the operations of its named scope ``kernels.gather_mix``): the
least time of the round's calls (bytes over HBM bandwidth or flops over
peak, whichever is larger; see ``trace.gather_mix_least_s``) over their
summed device time."""

from chipbench.trace import kernel_ns


def read(ctx):
    per = ctx.per_device(
        lambda d, lo, hi: kernel_ns(d, "kernels.gather_mix", lo, hi))
    measured_s = sum(per) / len(per) / 1e9
    if not measured_s:
        return None
    return 100.0 * ctx.gather_mix_least_s * ctx.rounds / measured_s
