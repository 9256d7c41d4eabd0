"""Model FLOP/s utilization of the traced window: forward and backward
FLOPs per item (the family's ``model_flops_per_item``) times items per
second, over the peak of the cell's chips."""

from chipbench import readers


def read(ctx):
    return readers.mfu(ctx)
