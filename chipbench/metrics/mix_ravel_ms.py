"""Device time per round of the mixer program outside its
``kernels.gather_mix`` kernel: packing the parameter tree into the flat
(C, N) buffers the kernel mixes and unpacking them again (the
concatenate XLA lowers to ``dynamic-update-slice``, layout copies,
slices, converts), plus the round's (C, C) matrix, averaged over the
cell's chips.  Read from whole-program and kernel intervals, so it
needs no operation to carry a scope."""

from chipbench.trace import kernel_ns, nonempty, program_ns


def read(ctx):
    per = ctx.per_device(
        lambda d, lo, hi: program_ns(d, ctx.mix_module, lo, hi)
        - kernel_ns(d, "kernels.gather_mix", lo, hi))
    return nonempty(sum(per) / len(per) / ctx.rounds / 1e6)
