"""Share of device busy time in the parameter update outside the
preconditioning (``spngd.update``): the first-order fallback, the norms,
momentum and the new parameters."""

from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, scopes.under("spngd.update"))
