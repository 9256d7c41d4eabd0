"""Stage-level tracing: device scopes and host spans on one clock.

The paper's negligible-overhead claim (§5.2) is a *time-accounting* claim:
Stage-2 statistics construction, the Stage-3 ReduceScatterV and the Stage-4
inversions must disappear behind the forward/backward. This module gives
every SP-NGD stage a stable name in both timelines:

* the ``STAGE_*`` names — ``jax.named_scope`` s the step programs open
  where the work happens. A scope is HLO metadata only (the compiled
  program is the same without it); it becomes each op's ``tf_op`` path in
  a profiler trace, so a trace reduction finds the stages however XLA
  fuses them. Every op of ``make_train_step`` / ``make_fast_step`` lies
  under one of them, but for the loop invariants JAX hoists out of a
  differentiated ``scan``, which it traces with no name (they depend on
  no input of the step). Instrumentation sites must use the constants
  so traces stay comparable across changes.
* :func:`kernel_scope` — the per-op/backend scope the kernel dispatch layer
  opens, so a ``ref`` vs ``pallas`` A/B of the same op lines up by name in
  the viewer (``repro.kernels.damped_inverse[pallas]`` vs ``[...ref]``).
* :class:`Span` — a host-side phase timer (``time.perf_counter``) that also
  opens a ``jax.profiler.TraceAnnotation``, so the host phase lands on the
  profiler's clock beside the device ops. The ``HOST_*`` names are the
  spans of one training step (``repro.launch.train.take_step``).
* :class:`ProfileCapture` — the opt-in ``--profile-dir`` window: a real
  ``jax.profiler`` trace of whole refresh cycles in steady state.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax

# Canonical scope names for the SP-NGD stages (paper Fig. 2 / §5).
# The capture step's forward/backward runs under STAGE_CAPTURE with
# STAGE_FWD_BWD nested inside it; the factor sums its backward emits nest
# further, under STAGE_STATS. The fast step opens STAGE_FWD_BWD alone.
STAGE_FWD_BWD = "spngd.stage1.fwd_bwd"     # forward + backward
STAGE_CAPTURE = "spngd.stage2.capture"     # grads + raw factor sums
STAGE_STATS = "spngd.stage2.stats"         # factor sums, sample counts
STAGE_HISTORY = "spngd.stage2.history"     # normalize, similarities, shift
STAGE_REDUCE = "spngd.stage3.reduce"       # factor ReduceScatterV
STAGE_INVERSE = "spngd.stage4.inverse"     # damped factor inversion
STAGE_GATHER = "spngd.stage4.gather"       # preconditioner all-gather
STAGE_PRECOND = "spngd.stage4.precond"     # A^-1 dW G^-1 apply
# An embedding's A^-1 dW G^-1 at the rows its step touched, nested in
# STAGE_PRECOND (gather, the row product, scatter into zeros).
STAGE_PRECOND_ROWS = "spngd.stage4.precond.rows"
STAGE_UPDATE = "spngd.update"              # fallback, norms, momentum, step
# Chunked refresh pipeline (repro.core.pipeline): one drain chunk fused
# into a fast step. STAGE_INVERSE / STAGE_GATHER nest under it, so trace
# filters on the stage-4 scopes keep working when the refresh is chunked.
# The drain's cursor and branch select run under STAGE_DRAIN, the
# activation of a finished drain under STAGE_FLIP; neither is Stage 4.
STAGE_CHUNK = "spngd.pipeline.chunk"       # drain chunk inside a fast step
STAGE_DRAIN = "spngd.pipeline.drain"       # the drain's cursor and select
STAGE_FLIP = "spngd.pipeline.flip"         # precond_next -> precond

# Host spans of one training step (repro.launch.train.take_step).
HOST_STEP = "spngd.host.step"              # the whole step; kind, step_num
HOST_FLAGS = "spngd.host.flags"            # refresh flags, moved to device
HOST_DISPATCH = "spngd.host.dispatch"      # the call of the jitted program
HOST_SIMS = "spngd.host.sims"              # similarity readback (waits)
HOST_CONTROLLER = "spngd.host.controller"  # IntervalController.update


def kernel_scope(op: str, which: str):
    """Stable trace-viewer name for one dispatched kernel op instance:
    ``repro.kernels.<op>[<backend>]``, so backend A/Bs line up by name."""
    return jax.named_scope(f"repro.kernels.{op}[{which}]")


@dataclasses.dataclass
class SpanRecord:
    """One finished span, as emitted to a sink (the metrics stream)."""
    name: str
    start: float          # perf_counter seconds (monotonic, process epoch)
    dur: float            # seconds
    depth: int            # nesting depth at entry (0 = top level)
    parent: Optional[str]  # enclosing span's name, None at top level


# Host-side span stack. The training/dryrun loops are single-threaded
# drivers, so a module-level stack is sufficient (and keeps Span allocation
# trivial); concurrent host threads would each want their own Tracer, which
# nothing here needs yet.
_ACTIVE: list["Span"] = []


class Span:
    """Host-side phase timer, nestable, with a profiler annotation.

    ``sink`` (a ``SpanRecord -> None`` callable, e.g.
    ``MetricsLogger._span_sink``) receives the record at exit; without a
    sink the span still times itself (``.dur``) for ad-hoc use, at the cost
    of two ``perf_counter`` calls and one annotation. The annotation puts
    the host phase on the profiler's clock in a captured trace. Keyword
    arguments become the annotation's arguments (the counts a trace viewer
    shows beside the span); :meth:`set` adds those known only inside the
    span. ``step_num`` makes it a ``StepTraceAnnotation``, which the
    profiler groups a step's device work by.
    """

    def __init__(self, name: str,
                 sink: Optional[Callable[[SpanRecord], None]] = None,
                 **args):
        self.name = name
        self.sink = sink
        self.start = 0.0
        self.dur = 0.0
        self.depth = 0
        self.parent: Optional[str] = None
        annotation = (jax.profiler.StepTraceAnnotation if "step_num" in args
                      else jax.profiler.TraceAnnotation)
        self._ann = annotation(name, **args)

    def set(self, **args) -> None:
        """Add arguments to the open span's annotation."""
        self._ann.set_metadata(**args)

    def __enter__(self) -> "Span":
        self.depth = len(_ACTIVE)
        self.parent = _ACTIVE[-1].name if _ACTIVE else None
        _ACTIVE.append(self)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.dur = time.perf_counter() - self.start
        self._ann.__exit__(exc_type, exc, tb)
        _ACTIVE.pop()
        if self.sink is not None:
            self.sink(SpanRecord(self.name, self.start, self.dur,
                                 self.depth, self.parent))
        return False


class ProfileCapture:
    """Opt-in ``jax.profiler`` trace of steady-state steps (--profile-dir).

    The loop calls :meth:`step_start` at the top of every iteration and
    :meth:`step_end` after the step, with its metrics. ``is_capture(t)``
    says, before step ``t`` runs, whether it is a capture step.

    The window opens at the first capture step after the first refresh has
    activated: by then both step programs have run (so compiled) and the
    preconditioners are live. ``settle`` is the number of steps from a
    capture until its refresh activates (``refresh_chunks + 1`` under the
    chunked pipeline, 1 with a double buffer, else 0). The window closes
    on the first capture boundary after at least ``steps`` steps, once the
    last step's device work has finished, so it holds whole refresh
    cycles. Inert when ``trace_dir`` is None, so call sites need no
    conditionals. :meth:`stop` is the end-of-run safety net for runs
    shorter than the window.
    """

    def __init__(self, trace_dir: Optional[str],
                 is_capture: Callable[[int], bool], steps: int = 3,
                 settle: int = 0):
        self.trace_dir = trace_dir
        self.steps = max(1, steps)
        self.settle = settle
        self.is_capture = is_capture
        self.done = trace_dir is None
        self._capture = False        # whether the current step captures
        self._first: Optional[int] = None   # the first capture step
        self._fast_seen = False
        self._active = False
        self._seen = 0
        self._last = None            # the last traced step's metrics

    def step_start(self, t: int) -> None:
        if self.done:
            return
        self._capture = bool(self.is_capture(t))
        if not self._capture:
            return
        if self._active:
            if self._seen >= self.steps:
                self.stop()
        elif (self._first is not None and self._fast_seen
              and t >= self._first + self.settle):
            jax.profiler.start_trace(self.trace_dir)
            self._active = True

    def step_end(self, t: int, metrics=None) -> None:
        if self.done:
            return
        if self._active:
            self._seen += 1
            self._last = metrics
        elif self._capture:
            if self._first is None:
                self._first = t
        else:
            self._fast_seen = True

    def stop(self) -> None:
        if self._active:
            jax.block_until_ready(self._last)
            jax.profiler.stop_trace()
            self._active = False
        self.done = True
