"""Share of device busy time in which a collective op (``trace.COLLECTIVE``:
all-reduce, reduce-scatter, all-gather, collective-permute, their -start /
-done halves, by XLA's or JAX's name) runs and no other op does: the
collectives' time that compute does not hide, per chip
(``trace.Reduction.exposed_collective_s``), averaged over the chips.
Nothing to read without a trace or where no collective ran."""


def read(ctx):
    red = ctx.trace
    if red is None or red.collective_s() <= 0:
        return None
    return 100.0 * red.exposed_collective_s() / red.busy_s
