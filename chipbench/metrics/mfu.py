"""Model FLOPs utilization of the whole round: the configuration's
forward + backward FLOPs per token times the traced window's tokens per
second, over the chips' bf16 peak."""


def read(ctx):
    cell = ctx.cell
    flops = ctx.family.flops_per_token(cell.config["model"],
                                       cell.traffic["seq_len"])
    peak = cell.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops * ctx.tokens_per_s / peak
