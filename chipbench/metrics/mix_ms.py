"""Device time of the mixer program per round, averaged over the
cell's chips."""

from chipbench.trace import nonempty, program_ns


def read(ctx):
    per = ctx.per_device(lambda d, lo, hi: program_ns(d, ctx.mix_module,
                                                      lo, hi))
    return nonempty(sum(per) / len(per) / ctx.rounds / 1e6)
