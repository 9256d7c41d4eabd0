"""Shares of device busy time by the program's own ``spngd.`` scopes.

The step programs name every op they run with a ``jax.named_scope``
(``repro/obs/tracing.py``); a traced op carries that path as its
``tf_op``. The readers in ``metrics/`` that split busy time by stage call
these. Each takes ``ctx.trace`` (a ``trace.Reduction``) and returns a
percentage of busy time, or None where the run holds nothing to read.
"""

from __future__ import annotations

from chipbench import trace

# the two jitted step programs, by the name their ops' tf_op starts with
PROGRAMS = ("jit(train_step)", "jit(fast_step)")


def under(*needles: str):
    """An op filter: its scope path holds one of ``needles``."""
    return lambda o: any(n in o.scope for n in needles)


def _busy_s(red, keep) -> float:
    """Device seconds in which an op ``keep`` accepts runs (the union of
    their intervals), averaged over the chips."""
    per = [sum(e - s for s, e in trace._union(
        [(o.start, o.end) for o in ops if keep(o)]))
        for ops in red.ops.values()]
    return sum(per) / len(per) * 1e-9


def share(ctx, keep):
    """Percent of busy time under the ops ``keep`` accepts; None without a
    trace or where no op is accepted (a program without the scope)."""
    red = ctx.trace
    if red is None or not any(keep(o) for ops in red.ops.values()
                              for o in ops):
        return None
    return 100.0 * _busy_s(red, keep) / red.busy_s


def unscoped_share(ctx):
    """Percent of busy time in which a leaf op of a step program (a named
    ``tf_op``, not a ``while``/``cond`` wrapper) runs and no op under an
    ``spngd.`` scope does. Compiler-inserted ops (no ``tf_op``) are left
    out; so is what runs outside the step programs."""
    red = ctx.trace
    if red is None:
        return None
    scoped = under("spngd.")

    def leaf(o):
        return (o.scope.startswith(PROGRAMS) and not scoped(o)
                and not trace.CONTROL_FLOW.match(o.name))

    either = _busy_s(red, lambda o: leaf(o) or scoped(o))
    return 100.0 * (either - _busy_s(red, scoped)) / red.busy_s
