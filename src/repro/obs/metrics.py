"""Per-step JSONL metrics stream with a near-zero-cost disabled path.

One :class:`MetricsLogger` owns all run-time telemetry output:

* the **JSONL event stream** (``--metrics-jsonl``): one JSON object per
  line, every event carrying ``{"v": schema version, "type": ..., "t_wall":
  unix time}``. Event types emitted by the launchers:

  - ``run_config``   — once at start: arch, flags, param count
  - ``precond_rows`` — once per step program, at start: for each
                       embedding family the rows its preconditioning
                       covers, ``d_in``, and their ``share``
  - ``step``         — per training step: loss, lr, refresh decisions,
                       grad/update norms, step-time EMA + p50/p99 from a
                       rolling window, the IntervalController's drained
                       byte-ledger deltas, NS/eigh inversion tallies.
                       Under the chunked refresh pipeline
                       (``--refresh-chunks K>1``) the ``kind`` field
                       distinguishes ``capture`` (refresh trigger, no
                       inline inversions) from ``refresh``/``fast``, and
                       ``refresh_inflight`` counts the steps until the
                       in-flight refresh activates: K+1 on the capture and
                       again on the first drain step (the capture does not
                       advance the chunk cursor), counting down to 1 on
                       the flip/activation step, 0 when idle
  - ``span``         — host-side phase timings (:class:`~repro.obs.tracing.Span`).
                       Pipeline drains additionally emit one
                       ``spngd.pipeline.chunk[i]`` span per chunk step
                       (``[flip]`` for the activation step) whose ``dur``
                       is the full fused step's wall time and whose
                       ``stats`` field lists the statistics the chunk
                       inverted — make_report derives the amortized
                       overlapped cost from these plus the fast-step dt
                       baseline
  - ``probe``        — the overhead-accounting probe (stage-isolated
                       timings the report's decomposition table consumes)
  - ``console``      — mirror of every console line
  - ``summary``      — once at end: the controller's flat counter totals
  - ``dryrun_case``  — one per dry-run record (launch.dryrun)

* the **console sink**: :meth:`console` prints byte-identically to the
  ``print()`` calls it replaced (log-scraping workflows keep working) and
  mirrors the line into the stream when enabled.

Disabled (no path/stream — the default), every emit method is a single
attribute check and return: no file is created, no event is built, and the
loss scalars the step events would force off-device are never fetched
(call sites gate those conversions on ``logger.enabled``). The
``obs.enabled_over_disabled`` benchmark row holds the enabled path to
<3% step-time overhead.

Loss values are written via ``json.dumps`` of the Python float, whose
repr round-trips bit-exactly — the stream's losses are bit-identical to
the returned step metrics (pinned by tests/test_obs.py).
"""

from __future__ import annotations

import collections
import json
import time
from typing import IO, Optional

from repro.obs.tracing import Span, SpanRecord

SCHEMA_VERSION = 1

_EMA_BETA = 0.9           # step-time EMA decay
_HIST_WINDOW = 256        # rolling window for p50/p99


class MetricsLogger:
    def __init__(self, path: Optional[str] = None,
                 stream: Optional[IO[str]] = None,
                 hist_window: int = _HIST_WINDOW):
        """``path`` opens (truncates) a JSONL file; ``stream`` writes to an
        existing file object (tests); neither = disabled."""
        if path is not None and stream is not None:
            raise ValueError("pass path or stream, not both")
        self.path = path
        self._own = path is not None
        self._stream = open(path, "w") if path is not None else stream
        self.enabled = self._stream is not None
        self.events_written = 0
        self._dts = collections.deque(maxlen=hist_window)
        self._ema: Optional[float] = None

    # ---- lifecycle ----

    def close(self) -> None:
        if self._stream is not None and self._own:
            self._stream.close()
            self._stream = None
            self.enabled = False

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # ---- raw event emission ----

    def emit(self, type_: str, **fields) -> None:
        """Write one event line. No-op (one attribute check) when disabled."""
        if not self.enabled:
            return
        evt = {"v": SCHEMA_VERSION, "type": type_, "t_wall": time.time()}
        evt.update(fields)
        self._stream.write(json.dumps(evt) + "\n")
        self._stream.flush()
        self.events_written += 1

    # ---- console sink ----

    def console(self, text: str = "", *, flush: bool = True) -> None:
        """Print ``text`` exactly as the bare ``print()`` it replaces would
        have, and mirror it into the stream as a ``console`` event."""
        print(text, flush=flush)
        if self.enabled:
            self.emit("console", text=text)

    # ---- spans ----

    def span(self, name: str) -> Span:
        """A Span whose record lands in the stream (no sink when disabled,
        so the span costs two perf_counter calls and nothing else)."""
        return Span(name, sink=self._span_sink if self.enabled else None)

    def _span_sink(self, rec: SpanRecord) -> None:
        self.emit("span", name=rec.name, start=rec.start, dur=rec.dur,
                  depth=rec.depth, parent=rec.parent)

    # ---- the per-step event ----

    def log_step(self, step: int, *, loss: float, dt: Optional[float] = None,
                 **fields) -> None:
        """One ``step`` event. ``dt`` (seconds) feeds the rolling step-time
        EMA and p50/p99; extra keyword fields (lr, kind, refresh decisions,
        drained comm ledger, inversion tallies, norms) pass through as-is."""
        if not self.enabled:
            return
        evt = {"step": step, "loss": loss}
        if dt is not None:
            self._dts.append(dt)
            self._ema = (dt if self._ema is None
                         else _EMA_BETA * self._ema + (1 - _EMA_BETA) * dt)
            evt.update(dt=dt, dt_ema=self._ema, **self._quantiles())
        evt.update(fields)
        self.emit("step", **evt)

    def _quantiles(self) -> dict:
        srt = sorted(self._dts)
        n = len(srt)
        return {"dt_p50": srt[n // 2],
                "dt_p99": srt[min(n - 1, (99 * n) // 100)]}


# ---------------------------------------------------------------------------
# NS/eigh inversion tallies (the Stage-4 return_info consumer)
# ---------------------------------------------------------------------------

def inverse_tally(inverse_info: dict, block_sizes: dict) -> dict:
    """Fold the per-block ``{"ns_res", "ns_converged"}`` arrays that
    ``metrics["inverse_info"]`` carries (both Stage-4 call sites:
    ``ngd._damped_inv`` and ``comm.stage4.Stage4Inverter``) into JSON-ready
    per-statistic counters, keyed for a per-block-size rollup.

    ``ns_res < 0`` is the not-refreshed-this-step sentinel (the refresh
    cond's keep branch); those blocks are excluded from the tallies.
    ``fallback_blocks`` counts blocks that re-solved via eigh (residual
    above tol or SPD loss — the dispatch fallback contract); for the direct
    methods the residual is identically 0 so fallbacks are 0.
    """
    import numpy as np
    stats = {}
    by_b: dict = {}
    for name, info in inverse_info.items():
        res = np.asarray(info["ns_res"], dtype=np.float64).reshape(-1)
        conv = np.asarray(info["ns_converged"], dtype=bool).reshape(-1)
        refreshed = res >= 0.0
        n_ref = int(refreshed.sum())
        n_fb = int((~conv[refreshed]).sum()) if n_ref else 0
        b = int(block_sizes.get(name, 0))
        stats[name] = {
            "b": b,
            "blocks": int(res.size),
            "refreshed_blocks": n_ref,
            "fallback_blocks": n_fb,
            "max_res": float(res[refreshed].max()) if n_ref else 0.0,
        }
        if n_ref:
            agg = by_b.setdefault(b, {"refreshed_blocks": 0,
                                      "fallback_blocks": 0})
            agg["refreshed_blocks"] += n_ref
            agg["fallback_blocks"] += n_fb
    return {"stats": stats,
            "by_block_size": {str(b): v for b, v in sorted(by_b.items())}}
