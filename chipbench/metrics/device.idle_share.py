"""Share of the traced window in which no op runs on the device."""

from chipbench import readers


def read(ctx):
    return readers.idle_share(ctx)
