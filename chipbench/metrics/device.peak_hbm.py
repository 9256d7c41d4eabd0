"""Peak device memory of the run, in GB: buffers in use plus the region
the TPU runtime reserves for the programs' temporaries."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
