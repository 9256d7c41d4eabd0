"""Share of device busy time in SP-NGD Stage 1, the forward and backward:
ops under ``spngd.stage1.`` and not under ``spngd.stage2.stats`` (the
factor sums the capture's backward emits nest inside Stage 1)."""

from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, lambda o: "spngd.stage1." in o.scope
                        and "spngd.stage2.stats" not in o.scope)
