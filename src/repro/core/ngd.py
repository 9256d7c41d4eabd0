"""SP-NGD optimizer: the paper's update rule (Eq. 6/12/23/24) end to end.

Decoupled from any model class: the constructor takes

    loss_fn(params, fstats, batch) -> (loss, aux)
    site_infos: {family: SiteInfo}
    fstats_fn() -> zero statistics pytree (structure {family: {"a": ..., ...}})
    counts_fn(batch) -> {family: (n_a, n_g)}

Two jittable steps:

* ``step``      — full step with curvature capture; per-statistic refresh
                  flags gate the (communication + inversion) work via
                  ``lax.cond`` (Algorithm 1's skip).
* ``step_fast`` — no capture at all (every statistic within its interval):
                  a plain backward + stale-preconditioned update. This is the
                  path whose cost approaches SGD, the paper's headline claim.

The caller drives refresh scheduling with ``stale.IntervalController``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import kfac
from repro.core.fisher import SiteInfo, emp_fisher_grads, mc_fisher_grads, get_path, set_path
from repro.obs.tracing import (STAGE_CAPTURE, STAGE_DRAIN, STAGE_FWD_BWD,
                               STAGE_HISTORY, STAGE_INVERSE, STAGE_PRECOND,
                               STAGE_PRECOND_ROWS, STAGE_STATS, STAGE_UPDATE)


@dataclasses.dataclass(frozen=True)
class NGDConfig:
    damping: float = 2.5e-4          # paper Table 2 lambda
    stale: bool = True
    alpha: float = 0.1               # Frobenius similarity threshold
    estimator: str = "emp"           # "emp" | "1mc"
    inverse_method: str = "eigh"     # "eigh" | "cholesky" | "newton_schulz"
    ns_iters: int = kfac.NS_ITERS    # newton_schulz: iteration cap
    ns_tol: float = kfac.NS_TOL      # newton_schulz: relative fixed-point
                                     # residual for early exit; blocks still
                                     # above it at the cap re-solve via eigh
    factor_dtype: Any = jnp.float32  # storage dtype for X_-1/X_-2 history:
                                     # a jnp dtype (dense), or "fp8_e4m3" /
                                     # "fp8_e5m2" (sym-packed payload +
                                     # per-block scales; repro.quant)
    fp8_scale_mode: str = "fp32"     # "fp32" | "pow2" per-block scales
    weight_rescale: bool = False     # Eq. 24 (on for the conv/paper configs)
    rescale_eps: float = 1e-9
    history: int = 2                 # 2 = full Algorithm 2; 1 = cheap variant
    sgd_fallback_scale: float = 1.0  # lr scale for non-sited params
    backend: str = "auto"            # kernel backend for the hot paths
                                     # ("ref" | "pallas" | "auto";
                                     #  repro.kernels.dispatch)
    inverse_sharding: bool = False   # Stage-4 distribution: each device
                                     # inverts only its FactorReducer-owned
                                     # chunk of every full-kind factor and
                                     # the preconditioners all-gather
                                     # (repro.comm.stage4). Takes effect
                                     # under the shard_map schedule, which
                                     # attaches the Stage4Inverter; the jit
                                     # schedule ignores it (replicated).
    double_buffer: bool = False      # pipeline refreshes behind training
                                     # compute: inverses produced by the
                                     # refresh at step t are STAGED and
                                     # activate at t+1.., while step t
                                     # still consumes the previous buffer
                                     # (paper §5.2 overlap; the staleness
                                     # itself is still Algorithm 2's)
    inverse_info: bool = False       # surface per-block Stage-4 inversion
                                     # diagnostics (ns_res / ns_converged)
                                     # in step metrics["inverse_info"] —
                                     # blocks not refreshed this step carry
                                     # the ns_res=-1 sentinel (repro.obs
                                     # consumes this; off by default so the
                                     # metric tree is unchanged)
    refresh_chunks: int = 1          # chunked refresh pipeline
                                     # (repro.core.pipeline): >1 splits
                                     # every refresh's Stage-4 inversions
                                     # + gathers into this many chunks,
                                     # executed one per subsequent fast
                                     # step and activated atomically
                                     # K+1 steps after the capture.
                                     # Requires double_buffer; the
                                     # IntervalController must run with
                                     # min_interval = refresh_chunks + 1
                                     # so a drain finishes before the
                                     # next capture. 1 = inline refresh
                                     # (the pre-pipeline behaviour).


def _dense_leaf_shape(leaf) -> tuple:
    """Template-leaf shape in dense f32 terms: wire-format capture dicts
    (fused SYRK epilogue) report the shape their payload decodes to, so the
    optimizer's history / preconditioner state is capture-format invariant."""
    from repro import quant
    if quant.is_wire(leaf):
        return quant.wire_dense_shape(leaf)
    return tuple(leaf.shape)


def _mean_eig(stat: jax.Array, kind: str, d: int) -> jax.Array:
    """Average eigenvalue of a factor (full blocked or diagonal)."""
    if kind == "full":
        return jnp.trace(stat, axis1=-2, axis2=-1).sum(-1) / d
    return stat.sum(-1) / d


def _damped_inv(stat: jax.Array, kind: str, damp: jax.Array,
                method: str, backend: str = "auto",
                ns_iters: int = kfac.NS_ITERS,
                ns_tol: float = kfac.NS_TOL,
                return_info: bool = False):
    """Apply-ready inverse: blocked matrix inverse or elementwise 1/(x+d).

    ``return_info=True`` additionally returns the dispatch layer's
    per-block ``{"ns_res", "ns_converged"}`` for full-kind stats (None for
    the elementwise kinds, which have no fallback to report)."""
    if kind == "full":
        from repro.kernels import dispatch
        return dispatch.damped_inverse(stat, damp[..., None], method=method,
                                       ns_iters=ns_iters, ns_tol=ns_tol,
                                       backend=backend,
                                       return_info=return_info)  # bcast over blocks
    inv = 1.0 / (jnp.maximum(stat, 0.0) + damp[..., None])
    return (inv, None) if return_info else inv


def _by_rows(info: SiteInfo, ids: Optional[jax.Array]) -> bool:
    """Whether an embedding family's gradient is preconditioned at the rows
    ``ids`` alone: its rows outside them are zero, ``A`` is diagonal and
    ``G^-1`` acts from the right, so ``A^-1 dW G^-1`` is zero there too.
    Worth it only with fewer ids than rows."""
    return info.kind == "embed" and ids is not None and ids.size < info.d_in


class SPNGD:
    def __init__(self, loss_fn: Callable, site_infos: dict[str, SiteInfo],
                 fstats_fn: Callable, counts_fn: Callable,
                 cfg: NGDConfig = NGDConfig(),
                 sharding_hook: Optional[Callable] = None):
        """``sharding_hook(family, stat_key, array) -> array`` lets the launch
        layer pin factor arrays to the (data x model) mesh — this is where the
        paper's Stage-3 ReduceScatterV materializes under GSPMD (DESIGN §7)."""
        self.loss_fn = loss_fn
        self.infos = site_infos
        self.fstats_fn = fstats_fn
        self.counts_fn = counts_fn
        self.cfg = cfg
        self.sharding_hook = sharding_hook or (lambda fam, key, x: x)
        self.stage4 = None            # Stage4Inverter, set by the shard_map
                                      # step builder (set_stage4)
        from repro.quant import parse_factor_dtype
        self._fp8 = parse_factor_dtype(cfg.factor_dtype)  # fmt key or None
        self.pipeline = None          # RefreshPipeline when refresh_chunks>1
        if cfg.refresh_chunks > 1:
            if not cfg.double_buffer:
                raise ValueError("refresh_chunks > 1 needs double_buffer: "
                                 "the drain writes precond_next while the "
                                 "fast path consumes precond")
            if cfg.inverse_info:
                raise ValueError("inverse_info is unavailable with "
                                 "refresh_chunks > 1: the capture step "
                                 "runs no inversions to report on")
            from repro.core.pipeline import RefreshPipeline
            self.pipeline = RefreshPipeline(self, cfg.refresh_chunks)

    def set_stage4(self, inverter) -> None:
        """Attach (or detach, with None) a
        :class:`repro.comm.Stage4Inverter`: full-kind factor inverses then
        run shard-locally over the reducer's chunk layout and all-gather.
        The step builder calls this when ``cfg.inverse_sharding`` is on —
        the optimizer itself stays schedule-agnostic."""
        self.stage4 = inverter

    def sym_stat(self, fam: str, key: str) -> bool:
        """Whether a stat is a symmetric blocked factor (sym-packable) —
        shared by the fp8 history codec and the Stage-3 reducer
        (:class:`repro.comm.FactorReducer`), so packing decisions cannot
        drift between storage and wire."""
        if key in ("a", "g"):
            info = self.infos[fam]
            kind = info.spec.a_kind if key == "a" else info.spec.g_kind
            return kind == "full"
        return key == "uwf"                  # full BN Fisher is symmetric

    _sym_stat = sym_stat                     # pre-PR-5 spelling

    # ---- fp8 history codec (dequantize-on-read; repro.quant) ----

    def _encode_hist(self, fam: str, key: str, x: jax.Array):
        if self._fp8 is None:
            return x.astype(self.cfg.factor_dtype)
        from repro import quant
        return quant.encode_stat(x, self._fp8,
                                 symmetric=self.sym_stat(fam, key),
                                 scale_mode=self.cfg.fp8_scale_mode,
                                 backend=self.cfg.backend)

    def _decode_hist(self, fam: str, key: str, stored, shape) -> jax.Array:
        if self._fp8 is None:
            return stored.astype(jnp.float32)
        from repro import quant
        return quant.decode_stat(stored, shape,
                                 symmetric=self.sym_stat(fam, key),
                                 backend=self.cfg.backend)

    # ---- statistic naming for the interval controller ----

    def stat_names(self) -> list[str]:
        names = []
        template = jax.eval_shape(self.fstats_fn)
        for fam, stats in template.items():
            for key in stats:
                names.append(f"{fam}.{key}")
        return sorted(names)

    def stat_bytes(self, dtype_bytes: Optional[int] = None) -> dict[str, int]:
        """Symmetric-packed communication payload per statistic (§5.2).

        By default the payload dtype follows ``cfg.factor_dtype`` — fp32 /
        bf16 dense elements, or fp8 payload + per-block f32 scales — so the
        IntervalController's byte ledger reports what the Stage-3
        reduce-scatter would actually move. Pass ``dtype_bytes`` to force a
        fixed element size (e.g. 4 for an fp32-communication baseline)."""
        from repro.core.stale import stat_payload_bytes, sym_packed_bytes
        template = jax.eval_shape(self.fstats_fn)
        out = {}
        for fam, stats in template.items():
            for key, leaf in stats.items():
                shape = _dense_leaf_shape(leaf)
                if dtype_bytes is not None:
                    out[f"{fam}.{key}"] = sym_packed_bytes(shape,
                                                           dtype_bytes)
                else:
                    out[f"{fam}.{key}"] = stat_payload_bytes(
                        shape, self.cfg.factor_dtype,
                        symmetric=self.sym_stat(fam, key))
        return out

    def wire_bytes(self, comm=None, group_size=None) -> dict[str, int]:
        """Per-statistic Stage-3 collective payload under a
        :class:`repro.comm.CommConfig` — the wire-bytes column of the
        IntervalController ledger. Unlike :meth:`stat_bytes` (storage dtype)
        this reflects what the configured collective actually moves: dense
        f32 for ``dense``, sym-packed f32 for ``ring``, fp8 payload +
        per-block scales for ``ring_fp8``. Assumes the paper's layout where
        every statistic scatters; a mesh-specific reducer's
        ``wire_bytes_per_stat()`` additionally prices replication
        fallbacks at dense f32."""
        from repro import comm as comm_mod
        return comm_mod.template_wire_bytes(
            jax.eval_shape(self.fstats_fn), self.sym_stat,
            comm or comm_mod.CommConfig(), group_size=group_size)

    def gather_bytes(self) -> dict[str, int]:
        """Per-statistic Stage-4 preconditioner all-gather payload — the
        gather column of the IntervalController ledger when
        ``cfg.inverse_sharding`` distributes the inversions. Sym-packed f32
        triangles for the full-kind factors, 0 for everything else (only
        sharded inverses gather; the wire never quantizes). Mesh-less
        everything-scatters assumption, like :meth:`wire_bytes`; a
        mesh-specific reducer's ``gather_bytes_per_stat()`` additionally
        zeroes replication fallbacks."""
        from repro import comm as comm_mod
        return comm_mod.template_gather_bytes(
            jax.eval_shape(self.fstats_fn), self.sym_stat)

    def wire_level_bytes(self, comm=None,
                         group_size=None) -> dict[str, tuple[int, int]]:
        """Per-statistic (intra-host, inter-host) Stage-3 wire bytes — the
        ``hier`` level breakdown feeding the IntervalController's per-level
        ledger. Flat strategies report (0, 0) everywhere (same mesh-less
        everything-scatters assumption as :meth:`wire_bytes`).
        ``group_size`` models the scatter-group size for the hier split
        (default: this process's local device count)."""
        from repro import comm as comm_mod
        return comm_mod.template_wire_level_bytes(
            jax.eval_shape(self.fstats_fn), self.sym_stat,
            comm or comm_mod.CommConfig(), group_size=group_size)

    # ---- state ----

    def init(self, params) -> dict:
        template = jax.eval_shape(self.fstats_fn)
        curv = {}
        for fam, stats in template.items():
            info = self.infos[fam]
            entry = {"prev": {}, "prev2": {}, "precond": {}}
            for key, leaf in stats.items():
                shape = _dense_leaf_shape(leaf)

                def zero_hist():
                    return self._encode_hist(fam, key,
                                             jnp.zeros(shape, jnp.float32))

                # every leaf gets a buffer of its own: the step programs
                # donate the state, and one buffer cannot be donated twice
                entry["prev"][key] = zero_hist()
                if self.cfg.history >= 2:
                    entry["prev2"][key] = zero_hist()
                if key in ("a", "g"):
                    kind = info.spec.a_kind if key == "a" else info.spec.g_kind
                    if kind == "full":
                        eye = jnp.broadcast_to(jnp.eye(shape[-1], dtype=jnp.float32),
                                               shape)
                        entry["precond"][key] = eye
                    else:
                        entry["precond"][key] = jnp.ones(shape, jnp.float32)
                else:                       # "d" (bias) / "uw" (2x2): store stats
                    entry["precond"][key] = jnp.zeros(shape, jnp.float32)
            if self.cfg.double_buffer:
                # staged buffer: what the NEXT step will activate. Seeding
                # it from the active init makes step 1 a plain identity-
                # preconditioned step (the pipeline's one-step warm-up).
                entry["precond_next"] = jax.tree.map(jnp.copy,
                                                     entry["precond"])
            curv[fam] = entry
        state = {
            "step": jnp.zeros((), jnp.int32),
            "velocity": jax.tree.map(jnp.zeros_like, params),
            "curv": curv,
        }
        if self.pipeline is not None:
            state["pipeline"] = self.pipeline.init_state()
        return state

    # ---- curvature refresh (Algorithm 1's on-refresh work) ----

    @jax.named_scope(STAGE_HISTORY)
    def _shift_history(self, fam: str, raw: dict, curv: dict,
                       flags: dict, n_a, n_g):
        """Per-family pre-inversion refresh work: decode + normalize the raw
        sums, measure the Algorithm-2 similarities against history, and
        shift X₋₁/X₋₂ for the flagged statistics. Shared by the inline
        refresh (:meth:`_refresh_family`) and the pipeline's capture step
        (:meth:`_apply_capture`), which parks the normalized statistics and
        defers the inversions. Returns ``(normalized, new_prev, new_prev2,
        sims)`` — ``normalized[key]`` is the post-select view (the fresh
        statistic when flagged, the decoded X₋₁ otherwise)."""
        cfg = self.cfg
        new_prev, new_prev2, sims = {}, {}, {}
        normalized = {}
        for key, v in raw.items():
            from repro import quant
            if quant.is_wire(v):
                # fused wire capture under the plain-jit schedule: ONE
                # dequant here (the counterpart of the shard_map reducer's
                # post-collective decode) and the refresh math below is
                # byte-identical to the dense path
                v = quant.decode_wire_stat(v)
            norm = (v / n_a) if key == "a" else (v * n_g)
            norm = self.sharding_hook(fam, key, norm)
            flag = flags[f"{fam}.{key}"]
            # dequantize-on-read: fp8 history decodes to f32 here and only
            # here; Algorithm 2's similarity and the inverse recompute both
            # consume the decoded view
            prev = self._decode_hist(fam, key, curv["prev"][key], norm.shape)
            # similarity of the *fresh* statistic vs history (Algorithm 2 input)
            d1 = jnp.where(flag, kfac.frob_distance(norm, prev), -1.0)
            if cfg.history >= 2:
                prev2 = self._decode_hist(fam, key, curv["prev2"][key],
                                          norm.shape)
                d2 = jnp.where(flag, kfac.frob_distance(norm, prev2), -1.0)
            else:
                d2 = d1
            sims[f"{fam}.{key}"] = jnp.stack([d1, d2])
            # history shift happens only when refreshed
            x = jnp.where(flag, norm, prev)
            normalized[key] = x
            if self._fp8 is None:
                new_prev[key] = x.astype(cfg.factor_dtype)
                if cfg.history >= 2:
                    new_prev2[key] = jnp.where(flag, prev,
                                               prev2).astype(cfg.factor_dtype)
            else:
                # select at the ENCODED level: payload and scale shift
                # together, so an un-refreshed stat keeps its stored bits
                # (no re-quantization drift across skipped steps)
                enc = self._encode_hist(fam, key, norm)
                sel = lambda a, b: jax.tree.map(
                    functools.partial(jnp.where, flag), a, b)
                new_prev[key] = sel(enc, curv["prev"][key])
                if cfg.history >= 2:
                    new_prev2[key] = sel(curv["prev"][key],
                                         curv["prev2"][key])
        if cfg.history < 2:
            new_prev2 = curv["prev2"]
        return normalized, new_prev, new_prev2, sims

    def _refresh_family(self, fam: str, raw: dict, curv: dict,
                        flags: dict, lam, n_a, n_g):
        info = self.infos[fam]
        cfg = self.cfg
        normalized, new_prev, new_prev2, sims = self._shift_history(
            fam, raw, curv, flags, n_a, n_g)

        with jax.named_scope(STAGE_INVERSE):
            any_flag = functools.reduce(
                jnp.logical_or, [flags[f"{fam}.{k}"] for k in raw],
                jnp.asarray(False))

            # which stats carry per-block inversion diagnostics: the full-kind
            # a/g factors (static set — the cond's branch trees must match)
            want_info = cfg.inverse_info
            info_keys = [k for k in ("a", "g") if k in normalized and
                         (info.spec.a_kind if k == "a" else
                          info.spec.g_kind) == "full"] if want_info else []

            def recompute(_):
                pc, inv_info = {}, {}
                if "a" in normalized or "g" in normalized:
                    a = normalized.get("a")
                    g = normalized.get("g")
                    if a is not None and g is not None:
                        ea = _mean_eig(a, info.spec.a_kind, info.d_in)
                        eg = _mean_eig(g, info.spec.g_kind, info.d_out)
                        pi = jnp.sqrt(jnp.maximum(ea, 1e-12)
                                      / jnp.maximum(eg, 1e-12))
                    else:
                        pi = jnp.ones(a.shape[:len(info.lead)] if a is not None
                                      else g.shape[:len(info.lead)])
                    sl = jnp.sqrt(jnp.asarray(lam, jnp.float32))
                    for key, stat, d in (("a", a, pi * sl),
                                         ("g", g, sl / pi)):
                        if stat is None:
                            continue
                        kind = (info.spec.a_kind if key == "a"
                                else info.spec.g_kind)
                        if key in info_keys:
                            pc[key], inv_info[key] = self._stat_inverse(
                                fam, key, stat, kind, d, want_info=True)
                        else:
                            pc[key] = self._stat_inverse(fam, key, stat,
                                                         kind, d)
                for key in ("d", "uw"):
                    if key in normalized:
                        pc[key] = normalized[key]
                if "uwf" in normalized:
                    # full BN Fisher (2C x 2C): invert directly with lam
                    # damping
                    pc["uwf"] = kfac.damped_inverse(
                        normalized["uwf"], jnp.asarray(lam, jnp.float32))
                return pc, inv_info

            def keep(_):
                # not-refreshed sentinels: ns_res=-1 (no inversion ran
                # this step), converged=True — shape-matched to recompute's
                # info so the cond branches return identical pytrees
                inv_info = {k: {"ns_res": jnp.full(normalized[k].shape[:-2],
                                                   -1.0, jnp.float32),
                                "ns_converged": jnp.full(
                                    normalized[k].shape[:-2], True)}
                            for k in info_keys}
                kept = curv["precond_next" if cfg.double_buffer
                            else "precond"]
                return kept, inv_info

            precond, inv_info = jax.lax.cond(any_flag, recompute, keep, None)
        if cfg.double_buffer:
            # pipeline: the fresh inverses are STAGED (precond_next) and the
            # buffer staged by the latest earlier refresh activates for this
            # step — refresh at t produces inverses consumed from t+1 on
            out = {"prev": new_prev, "precond": curv["precond_next"],
                   "precond_next": precond}
        else:
            out = {"prev": new_prev, "precond": precond}
        out["prev2"] = new_prev2
        return out, sims, inv_info

    def _stat_inverse(self, fam: str, key: str, stat: jax.Array, kind: str,
                      damp: jax.Array, want_info: bool = False):
        """One factor's Stage-4 inverse: shard-local + all-gather when a
        :class:`~repro.comm.Stage4Inverter` is attached (full-kind factors
        only — diagonal kinds are elementwise and not worth a collective),
        the replicated path otherwise.

        ``want_info=True`` returns ``(inv, info)`` where info is the
        per-block ``{"ns_res", "ns_converged"}`` dict (None for non-full
        kinds). The sharded path's extra ``owner`` vector is dropped so the
        info pytree is identical across both Stage-4 call sites — the
        refresh ``lax.cond`` requires matched branch trees."""
        cfg = self.cfg
        if kind == "full" and self.stage4 is not None:
            out = self.stage4.invert(stat, damp, fam=fam, key=key,
                                     return_info=want_info)
            if not want_info:
                return out
            inv, info = out
            return inv, {"ns_res": info["ns_res"],
                         "ns_converged": info["ns_converged"]}
        out = _damped_inv(stat, kind, damp, cfg.inverse_method, cfg.backend,
                          cfg.ns_iters, cfg.ns_tol, return_info=want_info)
        return out

    # ---- preconditioned update for one family ----

    def precond_rows(self, rows: Optional[dict]) -> dict[str, tuple]:
        """``{family: (rows preconditioned, d_in)}`` of every embedding
        family, for the ids ``rows`` (a model's ``site_rows``, or their
        shapes) that a step program is given."""
        rows = rows or {}
        return {fam: ((rows[fam].size if _by_rows(info, rows.get(fam))
                       else info.d_in), info.d_in)
                for fam, info in self.infos.items() if info.kind == "embed"}

    def _apply_precond(self, fam: str, grads, curv: dict, lam,
                       ids: Optional[jax.Array] = None):
        """``ids``: for an embedding family, the token ids of the step (any
        shape), whose rows are the only ones its gradient can hold."""
        info = self.infos[fam]
        pc = curv["precond"]
        if _by_rows(info, ids):
            with jax.named_scope(STAGE_PRECOND_ROWS):
                dw = get_path(grads, info.param)
                ids = ids.reshape(-1)
                a = pc.get("a")
                u = kfac.precondition(dw[ids], None if a is None else a[ids],
                                      pc.get("g"), backend=self.cfg.backend)
                # a repeated id writes the same row again
                return {info.param: jnp.zeros_like(dw).at[ids].set(u)}
        if info.kind in ("dense", "grouped", "embed"):
            dw = get_path(grads, info.param)
            u = kfac.precondition(dw, pc.get("a"), pc.get("g"),
                                  backend=self.cfg.backend)
            return {info.param: u}
        if info.kind == "conv":
            dw = get_path(grads, info.param)       # (kh, kw, cin, cout)
            kh, kw, cin, cout = dw.shape[-4:]
            lead = dw.shape[:-4]
            d2 = jnp.transpose(dw, tuple(range(len(lead))) +
                               tuple(len(lead) + i for i in (2, 0, 1, 3)))
            d2 = d2.reshape(lead + (cin * kh * kw, cout))
            u = kfac.precondition(d2, pc.get("a"), pc.get("g"),
                                  backend=self.cfg.backend)
            u = u.reshape(lead + (cin, kh, kw, cout))
            u = jnp.transpose(u, tuple(range(len(lead))) +
                              tuple(len(lead) + i for i in (1, 2, 0, 3)))
            return {info.param: u}
        if info.kind == "bias":
            g = get_path(grads, info.param)
            return {info.param: kfac.diag_solve(pc["d"], g, lam)}
        if info.kind == "scale_bias":
            gg = get_path(grads, info.param)
            if "uwf" in pc:                    # full BN Fisher baseline
                gb = get_path(grads, info.beta_param)
                gcat = jnp.concatenate([gg, gb], axis=-1)
                u = jnp.einsum("...ab,...b->...a", pc["uwf"],
                               gcat.astype(jnp.float32))
                c = gg.shape[-1]
                return {info.param: u[..., :c], info.beta_param: u[..., c:]}
            if info.beta_param is not None:
                gb = get_path(grads, info.beta_param)
                ug, ub = kfac.unitwise_solve(pc["uw"], gg, gb, lam)
                return {info.param: ug, info.beta_param: ub}
            ug = kfac.diag_solve(pc["uw"][..., 0], gg, lam)
            return {info.param: ug}
        raise ValueError(info.kind)

    # ---- full update assembly ----

    def _finish(self, params, state, grads, curv, lam, lr, mom, loss, aux,
                sims, inverse_info: Optional[dict] = None,
                extra_metrics: Optional[dict] = None,
                rows: Optional[dict] = None):
        """``rows``: ``{family: ids}`` from the model's ``site_rows`` of the
        whole step's batch, or None, which preconditions every row (the
        shard_map schedules: their gradients sum the rows of all shards)."""
        cfg = self.cfg
        rows = rows or {}
        # preconditioned updates for sited params
        updates = {}
        with jax.named_scope(STAGE_PRECOND):
            for fam, c in curv.items():
                updates.update(self._apply_precond(fam, grads, c, lam,
                                                   rows.get(fam)))

        # the first-order fallback, the norms, momentum and the parameter
        # update: disjoint from the preconditioning above
        with jax.named_scope(STAGE_UPDATE):
            sited = set(updates)

            def leaf_update(path_str, g):
                if path_str in updates:
                    return updates[path_str]
                return g * cfg.sgd_fallback_scale     # non-sited: first-order

            flat_g = _flatten_paths(grads)
            flat_p = _flatten_paths(params)
            flat_v = _flatten_paths(state["velocity"])
            new_p, new_v = {}, {}
            gsq = usq = jnp.zeros((), jnp.float32)
            for path_str, g in flat_g.items():
                u = leaf_update(path_str, g)
                gsq += jnp.sum(jnp.square(g.astype(jnp.float32)))
                usq += jnp.sum(jnp.square(u.astype(jnp.float32)))
                v_dtype = flat_v[path_str].dtype
                # strongly typed f32 lr/mom would promote a bf16 velocity: keep
                # its storage dtype so the donated state buffers alias in place
                v = (mom * flat_v[path_str] - lr * u.astype(v_dtype)
                     ).astype(v_dtype)
                w = flat_p[path_str] + v.astype(flat_p[path_str].dtype)
                new_v[path_str] = v
                new_p[path_str] = w

            # Eq. 24 weight rescaling on dense/conv/grouped weights
            if cfg.weight_rescale:
                for fam, info in self.infos.items():
                    if info.kind in ("dense", "conv", "grouped"):
                        w = new_p[info.param]
                        naxes = 2 if info.kind in ("dense", "grouped") else 4
                        axes = tuple(range(w.ndim - naxes, w.ndim))
                        norm = jnp.sqrt(jnp.sum(w.astype(jnp.float32) ** 2,
                                                axis=axes, keepdims=True))
                        target = jnp.sqrt(2.0 * info.d_out)
                        new_p[info.param] = (
                            w * (target / (norm + cfg.rescale_eps))
                        ).astype(w.dtype)

            params_out = _unflatten_paths(new_p, like=params)
            vel_out = _unflatten_paths(new_v, like=params)
            # spread: auxiliary state (e.g. the refresh pipeline's cursor/raw
            # store, already advanced by the caller) rides through unchanged
            state_out = {**state, "step": state["step"] + 1,
                         "velocity": vel_out, "curv": curv}
            grad_norm, update_norm = jnp.sqrt(gsq), jnp.sqrt(usq)
        metrics = {"loss": loss, "sims": sims,
                   "grad_norm": grad_norm, "update_norm": update_norm}
        if inverse_info:
            metrics["inverse_info"] = inverse_info
        if extra_metrics:
            metrics.update(extra_metrics)
        if isinstance(aux, dict):
            metrics.update({k: v for k, v in aux.items()
                            if isinstance(v, jax.Array) and v.ndim == 0})
        return params_out, state_out, metrics

    def grads_and_raw(self, params, batch,
                      rng: Optional[jax.Array] = None):
        """One backward pass: (loss, aux, grads, raw factor sums). Exposed
        separately so the launch layer can accumulate over microbatches —
        the paper's own method for mimicking BS=65K/131K (§7.1)."""
        with jax.named_scope(STAGE_CAPTURE), jax.named_scope(STAGE_FWD_BWD):
            fstats = self.fstats_fn()
            if self.cfg.estimator == "1mc":
                return mc_fisher_grads(self.loss_fn, params, fstats, batch,
                                       rng)
            return emp_fisher_grads(self.loss_fn, params, fstats, batch)

    def apply_update(self, params, state, grads, raw, counts, flags,
                     lam, lr, mom, loss, aux, rows: Optional[dict] = None):
        """Refresh curvature from raw sums (per ``flags``) + apply Eq. 23.

        With the chunked pipeline on (``refresh_chunks > 1``) this is the
        CAPTURE step: history/similarities update as usual but the
        inversions are deferred to the next K fast steps' drains."""
        if self.pipeline is not None:
            return self._apply_capture(params, state, grads, raw, counts,
                                       flags, lam, lr, mom, loss, aux, rows)
        curv, sims, inv_info = {}, {}, {}
        for fam in raw:
            n_a, n_g = counts[fam]
            curv[fam], s, fi = self._refresh_family(
                fam, raw[fam], state["curv"][fam], flags, lam, n_a, n_g)
            sims.update(s)
            for key, v in fi.items():
                inv_info[f"{fam}.{key}"] = v
        return self._finish(params, state, grads, curv, lam, lr, mom,
                            loss, aux, sims, inverse_info=inv_info,
                            rows=rows)

    def _apply_capture(self, params, state, grads, raw, counts, flags,
                       lam, lr, mom, loss, aux, rows=None):
        """Pipeline-mode refresh trigger: normalize + measure sims + shift
        history (so Algorithm 2 sees this step's similarities), park the
        normalized statistics in the raw store, and restart the drain
        cursor. No inversion runs here — this step's cost over a fast step
        is capture + Stage-3 reduce only. A pending (fully drained, not yet
        activated) refresh flips first so it is consumed, not lost."""
        pipe = state["pipeline"]
        curv_in = self.pipeline.flip(state["curv"], pipe)
        curv, sims = {}, {}
        new_raw, new_valid = {}, {}
        for fam in raw:
            n_a, n_g = counts[fam]
            normalized, new_prev, new_prev2, s = self._shift_history(
                fam, raw[fam], curv_in[fam], flags, n_a, n_g)
            sims.update(s)
            curv[fam] = {**curv_in[fam], "prev": new_prev,
                         "prev2": new_prev2}
            new_raw[fam] = normalized
        with jax.named_scope(STAGE_DRAIN):
            for fam in raw:
                new_valid[fam] = {
                    k: jnp.logical_or(pipe["valid"][fam][k],
                                      flags[f"{fam}.{k}"])
                    for k in raw[fam]}
            pipe = {"cursor": jnp.zeros((), jnp.int32), "raw": new_raw,
                    "valid": new_valid}
            extra = {"refresh_inflight": jnp.asarray(
                self.pipeline.chunks + 1, jnp.int32)}
        state = {**state, "pipeline": pipe}
        return self._finish(params, state, grads, curv, lam, lr, mom,
                            loss, aux, sims, extra_metrics=extra, rows=rows)

    def fast_curv(self, state, lam):
        """The fast path's curvature view + any pipeline progress: drains
        one chunk (and/or flips) when the pipeline is on, otherwise the
        plain double-buffer activation. Returns ``(state, curv, extra)``
        where ``extra`` feeds ``_finish``'s metrics (``refresh_inflight``
        in pipeline mode, empty otherwise). Every fast-step builder goes
        through here so the drain cannot be skipped by a schedule."""
        if self.pipeline is None:
            return state, self._activate(state["curv"]), {}
        curv, pipe, inflight = self.pipeline.drain(
            state["curv"], state["pipeline"], lam)
        return ({**state, "pipeline": pipe}, curv,
                {"refresh_inflight": inflight})

    def step(self, params, state, batch, flags: dict, lam, lr, mom,
             rng: Optional[jax.Array] = None, rows: Optional[dict] = None):
        """Full step with curvature capture. ``flags`` maps stat_name ->
        bool (traced ok); ``rows`` is the model's ``site_rows(batch)``."""
        loss, aux, grads, raw = self.grads_and_raw(params, batch, rng)
        with jax.named_scope(STAGE_STATS):
            counts = self.counts_fn(batch)
        return self.apply_update(params, state, grads, raw, counts, flags,
                                 lam, lr, mom, loss, aux, rows)

    def step_fast(self, params, state, batch, lam, lr, mom,
                  rows: Optional[dict] = None):
        """No capture, no refresh: backward + stale-preconditioned update
        (plus one pipeline drain chunk when ``refresh_chunks > 1``)."""
        with jax.named_scope(STAGE_FWD_BWD):
            (loss, aux), grads = jax.value_and_grad(
                self.loss_fn, has_aux=True)(params, None, batch)
        state, curv, extra = self.fast_curv(state, lam)
        return self._finish(params, state, grads, curv, lam, lr, mom,
                            loss, aux, {}, extra_metrics=extra, rows=rows)

    # ---- double-buffer plumbing ----

    def _activate(self, curv: dict) -> dict:
        """Double-buffer activation: the buffer staged by the latest refresh
        becomes the active preconditioner for THIS step (``_finish`` then
        persists the swap into the state). Identity when the pipeline is
        off. The refresh path performs its own activation inside
        ``_refresh_family``; this one covers the fast (no-capture) steps.
        With the chunked pipeline on this is also identity — activation is
        then the drain's gated flip (``RefreshPipeline.flip``), never an
        unconditional swap of a half-written ``precond_next``."""
        if not self.cfg.double_buffer or self.pipeline is not None:
            return curv
        return {fam: {**entry, "precond": entry["precond_next"]}
                for fam, entry in curv.items()}

    def upgrade_state(self, state: dict) -> dict:
        """Adapt a loaded optimizer state to this config's buffer layout
        (checkpoint compat across the double-buffer introduction): a
        single-buffer checkpoint entering a ``double_buffer`` run seeds the
        staged buffer from the active one (the first activation is then a
        no-op — the run continues exactly where the old semantics left it);
        a double-buffered checkpoint entering a single-buffer run drops the
        staged copy. Same-layout states pass through unchanged.

        The chunked-pipeline state follows the same rules: a checkpoint
        without it entering a ``refresh_chunks > 1`` run seeds an idle
        pipeline (cursor parked, nothing valid — the next capture starts
        it); a mid-drain checkpoint entering an inline run drops the
        pipeline state, losing only the not-yet-activated refresh (the
        next inline refresh recomputes it). A mid-drain state resuming
        under the SAME chunk count continues bit-identically — the cursor,
        raw store and valid latches are ordinary jnp leaves."""
        state = dict(state)
        curv = {}
        for fam, entry in state["curv"].items():
            entry = dict(entry)
            if self.cfg.double_buffer and "precond_next" not in entry:
                entry["precond_next"] = jax.tree.map(jnp.copy,
                                                     entry["precond"])
            if not self.cfg.double_buffer:
                entry.pop("precond_next", None)
            curv[fam] = entry
        if self.pipeline is not None and "pipeline" not in state:
            state["pipeline"] = self.pipeline.init_state()
        if self.pipeline is None:
            state.pop("pipeline", None)
        return {**state, "curv": curv}


# ---------------------------------------------------------------------------
# path-keyed flatten helpers (params are nested dicts)
# ---------------------------------------------------------------------------

def _flatten_paths(tree, prefix: str = "") -> dict:
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten_paths(v, f"{prefix}{k}/"))
    else:
        out[prefix[:-1]] = tree
    return out


def _unflatten_paths(flat: dict, like) -> dict:
    def rec(node, prefix):
        if isinstance(node, dict):
            return {k: rec(v, f"{prefix}{k}/") for k, v in node.items()}
        return flat[prefix[:-1]]
    return rec(like, "")
