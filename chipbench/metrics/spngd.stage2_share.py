"""Share of device busy time in SP-NGD Stage 2 outside the forward and
backward: the factor sums and sample counts (``spngd.stage2.stats``) and
the normalization, Algorithm-2 similarities and history shift
(``spngd.stage2.history``)."""

from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, scopes.under("spngd.stage2.stats",
                                          "spngd.stage2.history"))
