"""Arithmetic the metric readers share: each reader in ``metrics/`` is a
file of its own and calls these. A reader returns None where the run
holds nothing to read (no trace, or no device time under its scopes).
What a family's model costs comes from ``ctx.family``, its module in
``families/``."""

from __future__ import annotations

from chipbench import work


def mfu(ctx):
    f = ctx.family.model_flops_per_item(ctx.config, ctx.traffic)
    return 100.0 * f * ctx.items / ctx.window_s / \
        ctx.peaks["bf16_flops_per_s"]


def busy_share(ctx, *scopes):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.scope_s(*scopes) / ctx.trace.busy_s


def idle_share(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share


def precond_work(ctx) -> work.Work:
    return work.precond_work(ctx.family.dense_sites(ctx.config),
                             ctx.config["optimizer"]["kfac_max_dim"])


def kernel_roofline(ctx, w: work.Work, *scopes):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.scope_s(*scopes)
    if seconds <= 0:
        return None
    return work.roofline_share(w, seconds, ctx.peaks)
