"""Chunked refresh pipeline: hide Stage-4 behind training compute.

The paper's headline overhead claim (§5.2, Fig. 10) is that SP-NGD fast
steps cost what SGD costs because the curvature refresh is *hidden behind
training compute*: stale statistics (Alg. 2) make it legitimate to spread
one refresh over the whole staleness interval instead of paying it inline
on the refresh step. PR 7 shipped the staging seam (``precond_next`` +
activation at t+1); this module spreads the work.

Decomposition
-------------
A refresh splits into a **capture** step and ``K = NGDConfig.refresh_chunks``
**drain** chunks:

* The capture step (the step where Algorithm 1 raises refresh flags) runs
  fwd/bwd + Stage-2 capture + the Stage-3 reduce + normalization, measures
  the Frobenius similarities the IntervalController needs *that step*, and
  shifts the X₋₁/X₋₂ history — but performs NO inversions. The normalized
  f32 statistics are parked in the optimizer state
  (``opt_state["pipeline"]["raw"]``).
* Each of the next K fast steps executes one **chunk** — a set of whole
  (family, stat) inversion + gather units, LPT-balanced by a flop model —
  inside the same jitted program as that step's fwd/bwd, so XLA overlaps
  the chunk's eigh/NS compute and its gather collective with training
  compute. Full-kind factors route through the attached
  :class:`repro.comm.Stage4Inverter` exactly as the inline refresh does.
* The step after the last chunk **flips** ``precond_next -> precond``
  atomically per statistic — the same activation contract as
  ``SPNGD._activate``, just ``K+1`` steps after the capture instead of 1.

Chunks recompute from the parked raw statistics with the same ops as
``SPNGD._refresh_family``'s inline recompute (same pi split, same damping,
same inverse dispatch), so a drained refresh is bit-identical to an inline
double-buffered refresh of the same statistics — only the activation step
moves. The interval controller's ``min_interval = K + 1`` floor guarantees
a drain finishes before the next capture can start; a capture arriving
mid-drain (possible when per-stat schedules are offset) simply restarts the
cursor, re-deriving the in-flight chunks from the refreshed raw store —
idempotent, never wrong, at worst ``K`` duplicate chunk executions.

State machine
-------------
``opt_state["pipeline"] = {"cursor", "raw", "valid"}`` — all jnp leaves, so
the whole machine checkpoints/donates/shards like any other optimizer
state. ``cursor`` semantics (K = refresh_chunks):

    0..K-1   next drain step executes chunk ``cursor``
    K        all chunks written; next step flips precond_next -> precond
    K+1      idle (init / after the flip)

``valid[fam][key]`` latches once a statistic has been captured at least
once; the flip is gated on it so a never-captured statistic's identity
preconditioner is never replaced by an inverse of zeros.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kfac
from repro.obs.tracing import STAGE_CHUNK, STAGE_DRAIN, STAGE_FLIP


def _unit_cost(shape: tuple, kind: str) -> int:
    """Relative flop cost of one inversion unit (the LPT balance weight):
    blocked eigh/NS ~ lead x b^3 for full kinds, elementwise ~ n for the
    diagonal/unit-wise kinds (copies, effectively free)."""
    if kind == "full" and len(shape) >= 2:
        lead = int(np.prod(shape[:-2], dtype=np.int64))
        return max(1, lead * int(shape[-1]) ** 3)
    return max(1, int(np.prod(shape, dtype=np.int64)))


class RefreshPipeline:
    """Owns chunk scheduling for one :class:`repro.core.ngd.SPNGD`.

    Construction is host-side and static: the (family, stat) -> chunk
    assignment is pure shape arithmetic over the ``fstats`` template, so
    the drain's ``lax.switch`` branches are fixed at trace time. The traced
    entry points are :meth:`flip` (activation) and :meth:`drain` (one chunk
    + cursor advance), both called from the optimizer's fast path.
    """

    def __init__(self, opt, chunks: int):
        if chunks < 1:
            raise ValueError("refresh_chunks must be >= 1")
        self.opt = opt
        self.chunks = int(chunks)
        template = jax.eval_shape(opt.fstats_fn)
        from repro.core.ngd import _dense_leaf_shape
        units = []                      # (fam, key, cost)
        self._shapes: dict[str, tuple] = {}
        for fam, stats in sorted(template.items()):
            info = opt.infos[fam]
            for key, leaf in sorted(stats.items()):
                shape = _dense_leaf_shape(leaf)
                self._shapes[f"{fam}.{key}"] = shape
                if key in ("a", "g"):
                    kind = (info.spec.a_kind if key == "a"
                            else info.spec.g_kind)
                elif key == "uwf":
                    kind = "full"
                else:                   # "d" / "uw": stats pass through
                    kind = "elem"
                units.append((fam, key, _unit_cost(shape, kind)))
        # LPT (longest processing time first): heaviest unit to the
        # lightest chunk — near-optimal makespan, deterministic tiebreaks
        units.sort(key=lambda u: (-u[2], u[0], u[1]))
        loads = [0] * self.chunks
        self.schedule: list[list[tuple[str, str]]] = [
            [] for _ in range(self.chunks)]
        for fam, key, cost in units:
            i = loads.index(min(loads))
            self.schedule[i].append((fam, key))
            loads[i] += cost
        self.loads = loads

    # ---- host-side views ----

    def chunk_names(self, i: int) -> list[str]:
        """The statistics chunk ``i`` inverts (metrics span labels)."""
        return [f"{fam}.{key}" for fam, key in self.schedule[i]]

    # ---- state ----

    def init_state(self) -> dict:
        """Fresh (idle) pipeline state: cursor parked at K+1, raw store
        zeroed, nothing valid."""
        raw, valid = {}, {}
        for name, shape in self._shapes.items():
            fam, key = name.split(".", 1)
            raw.setdefault(fam, {})[key] = jnp.zeros(shape, jnp.float32)
            valid.setdefault(fam, {})[key] = jnp.zeros((), bool)
        return {"cursor": jnp.full((), self.chunks + 1, jnp.int32),
                "raw": raw, "valid": valid}

    # ---- traced entry points ----

    @jax.named_scope(STAGE_FLIP)
    def flip(self, curv: dict, pipe: dict) -> dict:
        """Activate a completed drain: when ``cursor == K`` every valid
        statistic's ``precond_next`` becomes ``precond`` (atomic per stat —
        a chunk never half-activates). No-op at any other cursor."""
        do = pipe["cursor"] == self.chunks
        out = {}
        for fam, entry in curv.items():
            pc = {}
            for key, cur in entry["precond"].items():
                on = jnp.logical_and(do, pipe["valid"][fam][key])
                pc[key] = jnp.where(on, entry["precond_next"][key], cur)
            out[fam] = {**entry, "precond": pc}
        return out

    def drain(self, curv: dict, pipe: dict, lam):
        """One fast step's pipeline work: flip if the drain just completed,
        execute chunk ``cursor`` (no-op when idle), advance the cursor.

        Returns ``(curv', pipe', inflight)`` where ``inflight`` is the
        int32 number of steps until the in-flight refresh is live (K+1
        right after a capture, 1 on the flip step, 0 when idle) — the
        metrics stream's ``refresh_inflight`` field. ``lam`` is the
        drain-time damping; under the stock schedules lambda is constant
        over a run, so it equals the capture-time value.
        """
        k = self.chunks
        curv = self.flip(curv, pipe)
        with jax.named_scope(STAGE_DRAIN):
            cursor = pipe["cursor"]
            pnext = {fam: entry["precond_next"]
                     for fam, entry in curv.items()}

            def wrap(i, fn):
                def branch(op):
                    with jax.named_scope(f"{STAGE_CHUNK}[{i}/{k}]"):
                        return fn(*op)
                return branch

            branches = [wrap(i, self._chunk_fn(i)) for i in range(k)]
            branches.append(lambda op: op[0])      # idle / flip-step no-op
            pnext = jax.lax.switch(jnp.minimum(cursor, k), branches,
                                   (pnext, pipe["raw"], lam))
            curv = {fam: {**entry, "precond_next": pnext[fam]}
                    for fam, entry in curv.items()}
            inflight = jnp.clip(k + 1 - cursor, 0, k + 1).astype(jnp.int32)
            pipe = {**pipe, "cursor": jnp.minimum(cursor + 1, k + 1)}
        return curv, pipe, inflight

    # ---- chunk bodies ----

    def _pi(self, fam: str, raw: dict) -> jax.Array:
        """The family's pi = sqrt(mean_eig(A)/mean_eig(G)) damping split —
        same formula as the inline recompute; both factors read from the
        raw store, so pi is chunk-assignment invariant."""
        from repro.core.ngd import _mean_eig
        info = self.opt.infos[fam]
        a = raw[fam].get("a")
        g = raw[fam].get("g")
        if a is not None and g is not None:
            ea = _mean_eig(a, info.spec.a_kind, info.d_in)
            eg = _mean_eig(g, info.spec.g_kind, info.d_out)
            return jnp.sqrt(jnp.maximum(ea, 1e-12) / jnp.maximum(eg, 1e-12))
        ref = a if a is not None else g
        return jnp.ones(ref.shape[:len(info.lead)])

    def _chunk_fn(self, i: int):
        """Branch body for chunk ``i``: invert this chunk's units from the
        raw store and write them (whole stats) into ``precond_next``."""
        units = self.schedule[i]

        def run(pnext, raw, lam):
            sl = jnp.sqrt(jnp.asarray(lam, jnp.float32))
            out = {fam: dict(stats) for fam, stats in pnext.items()}
            for fam, key in units:
                info = self.opt.infos[fam]
                v = raw[fam][key]
                if key in ("a", "g"):
                    kind = (info.spec.a_kind if key == "a"
                            else info.spec.g_kind)
                    pi = self._pi(fam, raw)
                    damp = pi * sl if key == "a" else sl / pi
                    # routes through the attached Stage4Inverter when
                    # inverse_sharding is on — shard-local + gather, one
                    # collective per chunk unit
                    out[fam][key] = self.opt._stat_inverse(fam, key, v,
                                                           kind, damp)
                elif key == "uwf":
                    out[fam][key] = kfac.damped_inverse(
                        v, jnp.asarray(lam, jnp.float32))
                else:                   # "d" / "uw": stats pass through
                    out[fam][key] = v
            return out

        return run
