"""Reduction of a profiler trace to the benchmark's device numbers.

``jax.profiler`` writes, beside the ``.xplane.pb``, the trace viewer's
JSON export (``*.trace.json.gz``). The JSON is what this reads: it carries
each XLA op's HLO metadata, whose ``tf_op`` argument is the op's name-scope
path (``jit(train_step)/spngd.stage4.precond/...``), while
``jax.profiler.ProfileData`` exposes only the events' own stats
(``device_offset_ps``, ``device_duration_ps``), without the scope path.

The trace holds, per TPU, a line of XLA op events and, per host thread,
the ``TraceAnnotation`` spans the harness opens around each phase of its
loop and around the whole timed window (``chipbench.window``).

* busy time: the union of the op intervals inside the window, per chip,
  averaged over the chips;
* time under a scope: the summed duration of ops whose scope path holds
  the scope's name;
* idle gaps: the stretches inside the window where no op runs, each named
  by the host annotation that overlaps it most;
* exposed collective time: per chip, the stretches in which a collective
  op (:data:`COLLECTIVE`, by HLO op name) runs and no other op does,
  averaged over the chips.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

WINDOW = "chipbench.window"
HOST_PREFIX = "chipbench."
OP_LINE = "XLA Ops"
# collectives by HLO op name, each with its async halves: XLA's names,
# the names JAX gives a shard_map's collectives (psum, reduce_scatter), and
# the TPU's async collective wrapper (a v5e 2x2 trace holds all of these)
COLLECTIVE = re.compile(r"(all-reduce|reduce-scatter|all-gather|"
                        r"collective-permute|psum|reduce_scatter|all_gather|"
                        r"async-collective)(-start|-done)?(\.\d+)*$")
# a control-flow op's event spans the ops of its body
CONTROL_FLOW = re.compile(r"(while|cond|conditional)(\.\d+)*$")


@dataclasses.dataclass
class Op:
    name: str
    scope: str
    start: int              # ns
    end: int


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals: list[tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def _minus(a: list[tuple[int, int]], b: list[tuple[int, int]]
           ) -> list[tuple[int, int]]:
    """The parts of the disjoint sorted intervals ``a`` that no interval
    of the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Reduction:
    window: tuple[int, int]
    ops: dict[str, list[Op]]          # device plane -> ops in the window
    host: list[tuple[str, int, int]]  # harness annotations

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device: str) -> list[tuple[int, int]]:
        return _union([(o.start, o.end) for o in self.ops[device]])

    @property
    def busy_s(self) -> float:
        per = [sum(e - s for s, e in self.busy_intervals(d))
               for d in self.ops]
        return sum(per) / len(per) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def scope_s(self, *needles: str) -> float:
        """Device seconds in which an op whose scope path holds any needle
        runs (the union of their intervals, so an op nested in another
        counts once), averaged over the chips."""
        per = [sum(e - s for s, e in _union([
            (o.start, o.end) for o in ops
            if any(n in o.scope for n in needles)]))
            for ops in self.ops.values()]
        return sum(per) / len(per) * 1e-9

    def collective_s(self) -> float:
        """Device seconds in which a collective op runs, averaged over the
        chips."""
        per = [_length(_union([(o.start, o.end) for o in ops
                               if COLLECTIVE.match(o.name)]))
               for ops in self.ops.values()]
        return sum(per) / len(per) * 1e-9

    def exposed_collective_s(self) -> float:
        """Device seconds in which a collective op runs and no other op
        does (control-flow wrappers, which span their bodies, left out),
        averaged over the chips."""
        per = []
        for ops in self.ops.values():
            comm = _union([(o.start, o.end) for o in ops
                           if COLLECTIVE.match(o.name)])
            other = _union([(o.start, o.end) for o in ops
                            if not COLLECTIVE.match(o.name)
                            and not CONTROL_FLOW.match(o.name)])
            per.append(_length(_minus(comm, other)))
        return sum(per) / len(per) * 1e-9

    def gaps(self, device: str) -> list[tuple[int, int]]:
        busy = self.busy_intervals(device)
        out, cur = [], self.window[0]
        for s, e in busy:
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if cur < self.window[1]:
            out.append((cur, self.window[1]))
        return out

    def _host_name(self, s: int, e: int) -> str:
        best, name = 0, "no annotation"
        for n, hs, he in self.host:
            if n == WINDOW:
                continue
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, n[len(HOST_PREFIX):]
        return name

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (by op name, summed) and the
        longest idle gaps by what the host was doing, on the first chip."""
        dev = sorted(self.ops)[0]
        by_name: dict[str, int] = {}
        for o in self.ops[dev]:
            key = _short(o)
            by_name[key] = by_name.get(key, 0) + o.end - o.start
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(dev), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[self._host_name(s, e), (e - s) * 1e-9]
                              for s, e in gaps]}


_SCOPE = re.compile(r"repro\.kernels\.\w+\[\w+\]|spngd\.[\w.]+(?:\[\d+/\d+\])?")


def _short(o: Op) -> str:
    """An op's name for the breakdown: its innermost benchmark-relevant
    scope (a kernel or an SP-NGD stage), if any, and the op's own name."""
    scopes = _SCOPE.findall(o.scope)
    base = re.sub(r"\.\d+$", "", o.name)
    return f"{scopes[-1]}:{base}" if scopes else base


def reduce_events(trace: dict) -> Reduction:
    """``trace``: the trace viewer's JSON object (``traceEvents``, times
    in microseconds)."""
    events = trace["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e.get("tid")): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}

    def ns(us) -> int:
        return int(round(float(us) * 1000))

    host, window = [], None
    spans = [e for e in events if e.get("ph") == "X"]
    for e in spans:
        if procs.get(e["pid"], "").startswith("/host:") and \
                str(e.get("name", "")).startswith(HOST_PREFIX):
            s = ns(e["ts"])
            host.append((e["name"], s, s + ns(e["dur"])))
            if e["name"] == WINDOW:
                window = (s, s + ns(e["dur"]))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    ops: dict[str, list[Op]] = {}
    for e in spans:
        dev = procs.get(e["pid"], "")
        if not dev.startswith("/device:") or \
                threads.get((e["pid"], e.get("tid"))) != OP_LINE:
            continue
        s = ns(e["ts"])
        t = s + ns(e["dur"])
        if t <= window[0] or s >= window[1]:
            continue
        args = e.get("args", {})
        ops.setdefault(dev, []).append(Op(
            e["name"], args.get("tf_op", ""), max(s, window[0]),
            min(t, window[1])))
    if not ops:
        raise ValueError("no device op ran inside the traced window")
    return Reduction(window, ops, host)


def find_trace(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**",
                                          "*.trace.json.gz"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.trace.json.gz under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str) -> Reduction:
    with gzip.open(find_trace(trace_dir), "rt") as f:
        return reduce_events(json.load(f))
