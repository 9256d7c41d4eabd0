"""Roofline share of the causal flash-attention kernels
(``swa_attention_fwd_res`` + ``swa_attention_bwd``): forward and backward
work per step from the shapes, over their device time."""

from chipbench import readers


def read(ctx):
    w = ctx.family.attention_work(ctx.config, ctx.traffic)
    return readers.kernel_roofline(
        ctx, w * ctx.steps, "repro.kernels.swa_attention_fwd_res[",
        "repro.kernels.swa_attention_bwd[")
