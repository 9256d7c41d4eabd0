"""Share of device busy time in SP-NGD Stage 3: the gradient psum and the
factors' reduce-scatter, every op under ``spngd.stage3.reduce`` (the
``FactorReducer``'s per-statistic scopes nest under it). A program on one
chip opens no such scope, and the reader reads nothing there."""

from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, scopes.under("spngd.stage3.reduce"))
