"""Arithmetic the metric readers share: each reader in ``metrics/`` is a
file of its own and calls these. A reader returns None where the run
holds nothing to read (no trace, or no device time under its scopes).
What a family's model costs comes from ``ctx.family``, its module in
``families/``."""

from __future__ import annotations

from chipbench import work


def mfu(ctx):
    """The model's FLOPs over the window, over the peak of all its chips
    (``ctx.items`` counts every chip's items)."""
    f = ctx.family.model_flops_per_item(ctx.config, ctx.traffic)
    return 100.0 * f * ctx.items / ctx.window_s / \
        (ctx.chips * ctx.peaks["bf16_flops_per_s"])


def busy_share(ctx, *scopes):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.scope_s(*scopes) / ctx.trace.busy_s


def idle_share(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share


def precond_work(ctx) -> work.Work:
    """One chip's preconditioning work a step. On several chips the
    preconditioner state lies split along its leading axis (the layers;
    the vocabulary of the embedding's and the head's diagonal factors), and
    the compiler splits the products with it: a chip does 1/chips of them
    (per-device shapes of the step programs compiled for a v5e 2x2)."""
    return work.precond_work(ctx.family.dense_sites(ctx.config),
                             ctx.config["optimizer"]["kfac_max_dim"]) * \
        (1.0 / ctx.chips)


def kernel_roofline(ctx, w: work.Work, *scopes):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.scope_s(*scopes)
    if seconds <= 0:
        return None
    return work.roofline_share(w, seconds, ctx.peaks)
