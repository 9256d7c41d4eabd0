"""Set-up: process start to the first timed step (loading, weights and
batches from the seed, compiling, the checked steps and warm-up)."""


def read(ctx):
    return ctx.setup_s
