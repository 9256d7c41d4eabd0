"""Stage-4 distribution: shard-local inversion + preconditioner gather.

The paper's negligible-overhead claim (§5.2, Osawa et al. 2018) distributes
the Kronecker-factor inversions layer-wise: after Stage 3's ReduceScatterV
each device holds a disjoint chunk of every factor family's leading (layer)
axis, so it inverts ONLY that chunk and the preconditioners return via one
all-gather — the redundant-inverse FLOPs per device drop ~1/p.

:class:`Stage4Inverter` wraps that contract around
``repro.kernels.dispatch.damped_inverse``:

* **Ownership is the reducer's chunk assignment.** The scatter decision
  (``FactorReducer.scatter_axes``) and the ``psum_scatter(tiled=True)``
  chunk layout are reused verbatim, so inversion ownership is invariant
  across ``dense``/``ring``/``ring_fp8``/``hier``/``fused`` — group index
  ``i`` inverts contiguous chunk ``i`` of the leading dim, always.
* **The gather is a :mod:`repro.comm` collective.**
  ``FactorReducer.gather_stat`` moves sym-packed f32 triangles (never
  quantized — inverse rounding error feeds the update direction directly)
  and its bytes are itemized in the wire ledger via
  ``FactorReducer.gather_bytes_per_stat``.
* **Observability rides ``return_info``.** ``invert(..., return_info=True)``
  returns the gathered per-block ``ns_res``/``ns_converged`` PLUS an
  ``owner`` vector tagging which group index inverted each leading chunk
  (-1 everywhere on the replicated fallback) — the test harness's proof
  that no device inverted outside its shard.

``invert`` opens its own ``shard_map`` (the optimizer calls it at the
GSPMD level, inside the refresh ``lax.cond`` — the factors already LEFT
the Stage-3 manual region scattered, so this region just re-binds the same
layout). Statistics whose leading dim could not scatter fall back to the
replicated inverse, exactly the pre-sharding behaviour.

The same property makes ``invert`` callable from the chunked refresh
pipeline's ``lax.switch`` branches (``refresh_chunks > 1``,
:mod:`repro.core.pipeline`): each drain chunk invokes it for its subset of
full-kind stats from a fast step's GSPMD level, one chunk per step. The
per-call contract is unchanged — ownership, gather axes, and wire bytes
per stat are identical to the inline refresh; the pipeline only changes
WHEN each stat's invert+gather executes, not what it does.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.comm import FactorReducer


def _batch_damp(damp, stat_ndim: int) -> jax.Array:
    """Right-pad ``damp`` with singleton dims until it aligns with the
    stat's batch dims ``stat.shape[:-2]`` (leading-aligned). The optimizer
    hands damp either scalar or leading-(layer-)shaped; a bare
    ``damp[..., None]`` is only correct when the stat carries exactly one
    block axis past the damp's — against a 3-D stat with a per-leading damp
    it would silently broadcast an enlarged batch instead of erroring."""
    d = jnp.asarray(damp, jnp.float32)
    while d.ndim < stat_ndim - 2:
        d = d[..., None]
    return d


def _group_index(axes: tuple, mesh) -> jax.Array:
    """Flat index of this device within the scatter group ``axes`` spans,
    row-major in axis order — the ``psum_scatter(tiled=True)`` chunk owner.
    (Built from per-axis ``axis_index`` so it never relies on tuple
    axis-name support.)"""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


class Stage4Inverter:
    """Shard-local damped inversion over a :class:`FactorReducer` layout.

    Construction is host-side and cheap; :meth:`invert` is the traced entry
    point the optimizer's refresh calls per full-kind factor. One instance
    per (reducer, inversion config) — the step builder attaches it via
    ``SPNGD.set_stage4`` when ``NGDConfig.inverse_sharding`` is on.
    """

    def __init__(self, reducer: FactorReducer, *, method: str = "eigh",
                 backend: str = "auto", ns_iters: int = 40,
                 ns_tol: float = 1e-4):
        self.reducer = reducer
        self.mesh = reducer.mesh
        self.method = method
        self.backend = backend
        self.ns_iters = ns_iters
        self.ns_tol = ns_tol

    # ---- host-side ownership map (what the tests assert against) ----

    def owners(self, dim0: int) -> np.ndarray:
        """Expected chunk owner (group index) per leading index, or -1
        everywhere when ``dim0`` cannot scatter (replicated inversion)."""
        axes = self.reducer.scatter_axes(dim0)
        p = self.reducer.group_size(axes) if axes else 1
        if not axes or p <= 1:
            return np.full((dim0,), -1, np.int32)
        return np.repeat(np.arange(p, dtype=np.int32), dim0 // p)

    # ---- traced entry point ----

    def _replicated(self, stat, damp, return_info):
        from repro.kernels import dispatch
        out = dispatch.damped_inverse(
            stat, _batch_damp(damp, stat.ndim), method=self.method,
            backend=self.backend,
            ns_iters=self.ns_iters, ns_tol=self.ns_tol,
            return_info=return_info)
        if not return_info:
            return out
        inv, info = out
        info = dict(info)
        info["owner"] = jnp.full(stat.shape[:1], -1, jnp.int32)
        return inv, info

    def invert(self, stat: jax.Array, damp: jax.Array, *, fam: str,
               key: str, return_info: bool = False):
        """Damped inverse of a full-kind blocked factor ``stat``
        ((lead..., nb, b, b)): each device inverts its reducer-owned chunk
        of the leading dim, then the preconditioner all-gathers
        (``FactorReducer.gather_stat``). Numerically identical to the
        replicated inverse — sharding only partitions the block batch."""
        from jax.sharding import PartitionSpec as P

        from repro.kernels import dispatch
        from repro.obs.tracing import STAGE_INVERSE

        axes = self.reducer.scatter_axes(stat.shape[0]) \
            if stat.ndim >= 3 else ()
        if not axes or self.reducer.group_size(axes) <= 1:
            with jax.named_scope(f"{STAGE_INVERSE}[replicated:{fam}.{key}]"):
                return self._replicated(stat, damp, return_info)

        reducer, mesh = self.reducer, self.mesh
        method, backend = self.method, self.backend
        ns_iters, ns_tol = self.ns_iters, self.ns_tol
        # damp (pi-corrected sqrt-damping) has the factor's leading shape
        # when the family carries a layer axis; scalar damp stays replicated
        damp = jnp.asarray(damp, jnp.float32)
        damp_sharded = damp.ndim >= 1 and damp.shape[0] == stat.shape[0]
        stat_spec = P(axes, *(None,) * (stat.ndim - 1))
        damp_spec = (P(axes, *(None,) * (damp.ndim - 1))
                     if damp_sharded else P())

        def local(s, d):
            inv, info = dispatch.damped_inverse(
                s, _batch_damp(d, s.ndim), method=method, backend=backend,
                ns_iters=ns_iters, ns_tol=ns_tol, return_info=True)
            inv = reducer.gather_stat(fam, key, inv, axes)
            if not return_info:
                return inv
            gi = _group_index(axes, mesh)
            an = axes if len(axes) > 1 else axes[0]
            gathered = {
                k: jax.lax.all_gather(v, an, axis=0, tiled=True)
                for k, v in info.items()}
            gathered["owner"] = jax.lax.all_gather(
                jnp.full((s.shape[0],), gi, jnp.int32), an, axis=0,
                tiled=True)
            return inv, gathered

        out_specs = (P(), {k: P() for k in ("ns_res", "ns_converged",
                                            "owner")}) \
            if return_info else P()
        sm = jax.shard_map(local, mesh=mesh,
                           in_specs=(stat_spec, damp_spec),
                           out_specs=out_specs, axis_names=set(axes),
                           check_vma=False)
        with jax.named_scope(f"{STAGE_INVERSE}[sharded:{fam}.{key}]"):
            return sm(stat, damp)
