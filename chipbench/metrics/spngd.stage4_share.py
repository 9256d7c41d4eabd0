"""Share of device busy time in SP-NGD Stage 4: ops under the
``spngd.stage4.*`` scopes (inversion, gather, preconditioning) and the
refresh pipeline's drain chunks (``spngd.pipeline.chunk``)."""

from chipbench import readers


def read(ctx):
    return readers.busy_share(ctx, "spngd.stage4.", "spngd.pipeline.chunk")
