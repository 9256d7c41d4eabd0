"""Roofline share of the Stage-2 statistics kernel (``factor_sum``, any
backend) on the im2col patches of every conv and the head's inputs and
output cotangents: its work per capture step, over its device time."""

from chipbench import readers


def read(ctx):
    w = ctx.family.factor_sum_work(ctx.config, ctx.traffic)
    return readers.kernel_roofline(ctx, w * ctx.captures,
                                   "repro.kernels.factor_sum[")
