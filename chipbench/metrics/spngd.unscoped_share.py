"""Share of device busy time in which a step program's op runs under no
``spngd.`` scope (``scopes.unscoped_share``): time no stage metric sees."""

from chipbench import scopes


def read(ctx):
    return scopes.unscoped_share(ctx)
