"""Roofline share of the Stage-4 preconditioning kernels
(``block_precond_left`` / ``_right``, any backend): their work per step
from the configuration's shapes and blocks, over their device time."""

from chipbench import readers


def read(ctx):
    return readers.kernel_roofline(
        ctx, readers.precond_work(ctx) * ctx.steps,
        "repro.kernels.block_precond_left[",
        "repro.kernels.block_precond_right[")
