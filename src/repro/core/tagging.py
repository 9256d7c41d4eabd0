"""Layer tagging: curvature capture fused into the regular backward pass.

The paper's "practical" pillar (§4.1) is that the *empirical* Fisher can be
estimated during the ordinary forward/backward pass, with no extra
Monte-Carlo backward. We realize that in JAX with a *dummy-cotangent* trick:

Every tagged site (dense matmul, conv-as-im2col matmul, grouped/MoE matmul,
scale-bias, embedding) is a ``jax.custom_vjp`` whose primal takes extra
all-zero "statistics accumulator" arguments. The forward ignores them; the
backward returns, as their cotangents, the *raw factor sums*

    d(a_acc) = sum_t a_t a_t^T     (blocked, f32)
    d(g_acc) = sum_t gy_t gy_t^T   (blocked, f32; gy = dL/ds, un-normalized)

so ``jax.grad`` over (params, fstats) yields the gradients *and* the factor
statistics in one backward pass. Under ``lax.scan`` over layers the dummies
ride along as per-layer ``xs`` and their cotangents stack to (L, ...) —
giving the uniform "factor family" arrays of DESIGN.md §2 for free.

Normalization (tokens vs samples, mean-loss scaling) is deliberately NOT done
here — sites return raw sums; ``core/fisher.py`` normalizes with global
counts (which under pjit are the *global* batch, under shard_map the local
one plus a psum).

When a site's stats argument is ``None`` the plain op runs (zero overhead) —
this is the "no refresh this step" fast path of Algorithm 1.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import numpy as np
import jax.numpy as jnp

from repro.core import kfac
from repro.obs.tracing import STAGE_STATS


# ---------------------------------------------------------------------------
# Factor spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FactorSpec:
    """Static description of what curvature a site collects.

    ``a_max``/``g_max`` override ``max_dim`` per side — used to align factor
    blocks to tensor-parallel shard boundaries so block construction never
    crosses shards (zero cross-shard factor communication; DESIGN.md §4).

    ``backend`` selects the factor-construction kernel for this site
    ("ref" | "pallas" | "auto"; :mod:`repro.kernels.dispatch`).

    ``wire_fmt`` ("" | "e4m3" | "e5m2") switches FULL-kind factor capture to
    the fused wire format: the site's accumulator (and its cotangent) become
    ``{"payload": fp8 (lead..., nb, t), "scale": f32 (lead..., nb)}`` dicts
    emitted by ``factor_sum_wire`` — sym-packed + per-block-quantized inside
    the SYRK epilogue, so the raw f32 sum never round-trips HBM before the
    Stage-3 collective (the "fused" comm strategy consumes these directly).
    Diag / unit-wise stats are unaffected.
    """
    a_kind: str = "full"        # "full" | "diag" | "none"
    g_kind: str = "full"        # "full" | "diag" | "none"
    max_dim: int = 2048         # block-diagonal factor cap (DESIGN.md §4)
    a_max: int = 0              # 0 -> max_dim
    g_max: int = 0
    backend: str = "auto"       # kernel backend for this site's factor sums
    wire_fmt: str = ""          # "" (dense f32) | "e4m3" | "e5m2"
    wire_scale_mode: str = "fp32"  # per-block scale mode for wire capture

    @property
    def a_dim(self) -> int:
        return self.a_max or self.max_dim

    @property
    def g_dim(self) -> int:
        return self.g_max or self.max_dim

    def a_shape(self, d_in: int) -> Optional[tuple[int, ...]]:
        if self.a_kind == "full":
            nb = kfac.num_blocks(d_in, self.a_dim)
            b = kfac.block_size(d_in, self.a_dim)
            return (nb, b, b)
        if self.a_kind == "diag":
            return (d_in,)
        return None

    def g_shape(self, d_out: int) -> Optional[tuple[int, ...]]:
        if self.g_kind == "full":
            nb = kfac.num_blocks(d_out, self.g_dim)
            b = kfac.block_size(d_out, self.g_dim)
            return (nb, b, b)
        if self.g_kind == "diag":
            return (d_out,)
        return None


def _wire_zeros(spec: FactorSpec, shape: tuple[int, ...],
                lead: tuple[int, ...]) -> dict:
    """Zero wire-format accumulator for one full-kind factor of dense shape
    ``(nb, b, b)``: fp8 payload rows + per-block f32 scales."""
    from repro import quant
    if spec.wire_fmt not in quant.FORMATS:
        raise ValueError(f"unknown wire_fmt {spec.wire_fmt!r}; expected "
                         f"{sorted(quant.FORMATS)}")
    nb, b = shape[0], shape[-1]
    t = b * (b + 1) // 2
    return {"payload": jnp.zeros(lead + (nb, t),
                                 quant.FORMATS[spec.wire_fmt]),
            "scale": jnp.zeros(lead + (nb,), jnp.float32)}


def make_stats(spec: FactorSpec, d_in: int, d_out: int,
               lead: tuple[int, ...] = ()) -> dict:
    """Zero stats-accumulator pytree for one site ("fstats" leaf)."""
    out = {}
    sa = spec.a_shape(d_in)
    sg = spec.g_shape(d_out)
    if sa is not None:
        out["a"] = (_wire_zeros(spec, sa, lead)
                    if spec.wire_fmt and spec.a_kind == "full"
                    else jnp.zeros(lead + sa, jnp.float32))
    if sg is not None:
        out["g"] = (_wire_zeros(spec, sg, lead)
                    if spec.wire_fmt and spec.g_kind == "full"
                    else jnp.zeros(lead + sg, jnp.float32))
    return out


def _acc_shape(acc):
    """Residual-friendly shape of one accumulator: a plain tuple, or a
    {"payload", "scale"} dict of tuples for wire-format capture."""
    if isinstance(acc, dict):
        return {k: v.shape for k, v in acc.items()}
    return acc.shape


@jax.named_scope(STAGE_STATS)
def _stat_sum(x2d: jax.Array, kind: str, max_dim: int,
              want_shape, backend: str = "auto",
              spec: Optional[FactorSpec] = None):
    """Raw factor sum for a token matrix (n, d), matching the dummy's shape
    (which may include leading group axes already consumed by the caller).
    A dict ``want_shape`` requests wire-format capture: the fused
    ``factor_sum_wire`` op returns the sym-packed fp8 payload + per-block
    scales as the cotangent (kind is necessarily "full")."""
    if isinstance(want_shape, dict):
        payload, scale = kfac.factor_sum_wire(
            x2d, max_dim, fmt=spec.wire_fmt,
            scale_mode=spec.wire_scale_mode, backend=backend)
        return {"payload": payload.reshape(want_shape["payload"]),
                "scale": scale.reshape(want_shape["scale"])}
    if kind == "full":
        return kfac.factor_sum(x2d, max_dim,
                               backend=backend).reshape(want_shape)
    if kind == "diag":
        return kfac.diag_factor_sum(x2d).reshape(want_shape)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Dense site: y = x @ w      x: (..., d_in), w: (d_in, d_out)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dense_site(spec: FactorSpec, x, w, a_acc, g_acc):
    return jnp.matmul(x, w)


def _dense_site_fwd(spec, x, w, a_acc, g_acc):
    y = jnp.matmul(x, w)
    return y, (x, w, _acc_shape(a_acc), _acc_shape(g_acc))


def _dense_site_bwd(spec, res, gy):
    x, w, a_shape, g_shape = res
    d_in, d_out = w.shape
    x2d = x.reshape(-1, d_in)
    g2d = gy.reshape(-1, d_out)
    dw = jnp.matmul(x2d.T, g2d.astype(x2d.dtype)).astype(w.dtype)
    dx = jnp.matmul(gy, w.T).astype(x.dtype)
    da = (_stat_sum(x2d, spec.a_kind, spec.a_dim, a_shape, spec.backend,
                    spec)
          if a_shape else jnp.zeros(a_shape))
    dg = (_stat_sum(g2d, spec.g_kind, spec.g_dim, g_shape, spec.backend,
                    spec)
          if g_shape else jnp.zeros(g_shape))
    return dx, dw, da, dg


_dense_site.defvjp(_dense_site_fwd, _dense_site_bwd)


def dense_site(x: jax.Array, w: jax.Array, stats: Optional[dict],
               spec: FactorSpec = FactorSpec()) -> jax.Array:
    """Tagged dense matmul. ``stats`` is the zero-accumulator dict from
    :func:`make_stats` (or None for the untagged fast path)."""
    if stats is None:
        return jnp.matmul(x, w)
    zero = jnp.zeros((), jnp.float32)
    return _dense_site(spec, x, w, stats.get("a", zero), stats.get("g", zero))


# ---------------------------------------------------------------------------
# Grouped dense site (MoE experts): y[e] = x[e] @ w[e]
#   x: (E, n, d_in), w: (E, d_in, d_out) -> per-expert factors (E, nb, b, b)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped_site(spec: FactorSpec, x, w, a_acc, g_acc):
    return jnp.einsum("end,edf->enf", x, w)


def _grouped_site_fwd(spec, x, w, a_acc, g_acc):
    return jnp.einsum("end,edf->enf", x, w), (x, w, _acc_shape(a_acc),
                                              _acc_shape(g_acc))


def _grouped_site_bwd(spec, res, gy):
    x, w, a_shape, g_shape = res
    dw = jnp.einsum("end,enf->edf", x, gy.astype(x.dtype)).astype(w.dtype)
    dx = jnp.einsum("enf,edf->end", gy, w).astype(x.dtype)
    # factor sums keep the expert axis: (E, n, d) -> (E, nb, b, b)
    da = (_stat_sum(x, spec.a_kind, spec.a_dim, a_shape, spec.backend, spec)
          if a_shape else None)
    dg = (_stat_sum(gy, spec.g_kind, spec.g_dim, g_shape, spec.backend, spec)
          if g_shape else None)
    if da is None:
        da = jnp.zeros(a_shape)
    if dg is None:
        dg = jnp.zeros(g_shape)
    return dx, dw, da, dg


_grouped_site.defvjp(_grouped_site_fwd, _grouped_site_bwd)


def grouped_dense_site(x: jax.Array, w: jax.Array, stats: Optional[dict],
                       spec: FactorSpec = FactorSpec()) -> jax.Array:
    if stats is None:
        return jnp.einsum("end,edf->enf", x, w)
    zero = jnp.zeros((), jnp.float32)
    return _grouped_site(spec, x, w, stats.get("a", zero), stats.get("g", zero))


# ---------------------------------------------------------------------------
# Bias site: y = x + b  (diagonal Fisher for b; paper treats biases unit-wise)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _bias_site(x, b, acc):
    return x + b


def _bias_site_fwd(x, b, acc):
    return x + b, (b.shape,)


def _bias_site_bwd(res, gy):
    (b_shape,) = res
    g2d = gy.reshape(-1, b_shape[-1]).astype(jnp.float32)
    db = g2d.sum(0).astype(jnp.float32)
    with jax.named_scope(STAGE_STATS):
        dacc = jnp.sum(g2d * g2d, axis=0)
    return gy, db, dacc


_bias_site.defvjp(_bias_site_fwd, _bias_site_bwd)


def bias_site(x: jax.Array, b: jax.Array, stats: Optional[dict]) -> jax.Array:
    if stats is None:
        return x + b
    return _bias_site(x, b, stats["d"])


def make_bias_stats(d: int, lead: tuple[int, ...] = ()) -> dict:
    return {"d": jnp.zeros(lead + (d,), jnp.float32)}


# ---------------------------------------------------------------------------
# Scale-bias site (BatchNorm / RMSNorm affine): y = xhat * gamma (+ beta)
# Unit-wise 2x2 Fisher (Eq. 15-16). ``spatial`` counts trailing token axes
# *within one sample* to sum over before the outer product (conv: H, W).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scale_bias_site(spatial: int, has_beta: bool, xhat, gamma, beta, acc):
    y = xhat * gamma
    return y + beta if has_beta else y


def _scale_bias_site_fwd(spatial, has_beta, xhat, gamma, beta, acc):
    y = xhat * gamma
    if has_beta:
        y = y + beta
    return y, (xhat, gamma, acc.shape)


def _scale_bias_site_bwd(spatial, has_beta, res, gy):
    xhat, gamma, acc_shape = res
    c = xhat.shape[-1]
    gf = gy.astype(jnp.float32)
    xf = xhat.astype(jnp.float32)
    u = gf * xf                                   # per-position dL/dgamma
    # per-sample grads: sum the ``spatial`` axes right before the channel axis
    if spatial:
        ax = tuple(range(-1 - spatial, -1))
        us = u.sum(ax)
        vs = gf.sum(ax)
    else:
        us, vs = u, gf
    us2 = us.reshape(-1, c)
    vs2 = vs.reshape(-1, c)
    dgamma = us2.sum(0)
    dbeta = vs2.sum(0)
    with jax.named_scope(STAGE_STATS):
        if len(acc_shape) >= 2 and acc_shape[-1] == 2 * c:
            # FULL BN Fisher (2C x 2C) — the paper's expensive baseline
            # (Fig. 5 "fullBN"): outer products of the concatenated
            # per-sample grads.
            z = jnp.concatenate([us2, vs2], axis=-1)  # (n, 2C)
            dacc = (z.T @ z).reshape(acc_shape)
        else:
            # unit-wise stats (C, 3): [sum u^2, sum u v, sum v^2]
            # (Eq. 15-16)
            dacc = jnp.stack([jnp.sum(us2 * us2, 0),
                              jnp.sum(us2 * vs2, 0),
                              jnp.sum(vs2 * vs2, 0)],
                             axis=-1).reshape(acc_shape)
    dx = (gf * gamma).astype(xhat.dtype)
    if not has_beta:
        dbeta = jnp.zeros_like(dbeta)
    return dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype), dacc


_scale_bias_site.defvjp(_scale_bias_site_fwd, _scale_bias_site_bwd)


def scale_bias_site(xhat: jax.Array, gamma: jax.Array,
                    beta: Optional[jax.Array], stats: Optional[dict],
                    spatial: int = 0) -> jax.Array:
    if stats is None:
        y = xhat * gamma
        return y + beta if beta is not None else y
    has_beta = beta is not None
    b = beta if has_beta else jnp.zeros_like(gamma)
    acc = stats["uwf"] if "uwf" in stats else stats["uw"]
    return _scale_bias_site(spatial, has_beta, xhat, gamma, b, acc)


def make_scale_bias_stats(c: int, lead: tuple[int, ...] = (),
                          full: bool = False) -> dict:
    if full:
        return {"uwf": jnp.zeros(lead + (2 * c, 2 * c), jnp.float32)}
    return {"uw": jnp.zeros(lead + (c, 3), jnp.float32)}


# ---------------------------------------------------------------------------
# Embedding site: y = table[ids]
#   A factor = diag(token counts); G factor = blocked gy^T gy over tokens.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _embed_site(spec: FactorSpec, ids, table, a_acc, g_acc):
    return jnp.take(table, ids, axis=0)


def _embed_site_fwd(spec, ids, table, a_acc, g_acc):
    return jnp.take(table, ids, axis=0), (ids, table.shape, _acc_shape(a_acc),
                                          _acc_shape(g_acc))


def _embed_site_bwd(spec, res, gy):
    ids, tshape, a_shape, g_shape = res
    v, d = tshape
    flat_ids = ids.reshape(-1)
    g2d = gy.reshape(-1, d)
    dtable = jnp.zeros(tshape, gy.dtype).at[flat_ids].add(g2d)
    with jax.named_scope(STAGE_STATS):
        da = (jnp.zeros(a_shape, jnp.float32).at[flat_ids].add(1.0)
              if a_shape else jnp.zeros(a_shape))
    dg = (_stat_sum(g2d, spec.g_kind, spec.g_dim, g_shape, spec.backend,
                    spec)
          if g_shape else jnp.zeros(g_shape))
    dids = np.zeros(ids.shape, dtype=jax.dtypes.float0)  # int input: no tangent
    return dids, dtable, da, dg


_embed_site.defvjp(_embed_site_fwd, _embed_site_bwd)


def embed_site(ids: jax.Array, table: jax.Array, stats: Optional[dict],
               spec: FactorSpec = FactorSpec(a_kind="diag")) -> jax.Array:
    if stats is None:
        return jnp.take(table, ids, axis=0)
    zero = jnp.zeros((), jnp.float32)
    return _embed_site(spec, ids, table, stats.get("a", zero), stats.get("g", zero))


def make_embed_stats(vocab: int, d: int, spec: FactorSpec,
                     lead: tuple[int, ...] = ()) -> dict:
    out = {"a": jnp.zeros(lead + (vocab,), jnp.float32)}
    sg = spec.g_shape(d)
    if sg is not None:
        out["g"] = jnp.zeros(lead + sg, jnp.float32)
    return out


# ---------------------------------------------------------------------------
# Conv site = im2col patches + dense_site (paper Eq. 10-11): the Kronecker
# factors of a conv layer are exactly the dense factors of its im2col matmul.
# ---------------------------------------------------------------------------

def conv_site(x: jax.Array, w: jax.Array, stats: Optional[dict],
              stride: int = 1, padding: str = "SAME",
              spec: FactorSpec = FactorSpec()) -> jax.Array:
    """2D conv, NHWC, w: (kh, kw, cin, cout), via im2col + tagged matmul."""
    kh, kw, cin, cout = w.shape
    if stats is None and (kh, kw) == (1, 1) and stride == 1:
        return jnp.einsum("bhwc,cd->bhwd", x, w[0, 0])
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # conv_general_dilated_patches returns channels ordered (cin, kh, kw) in
    # the feature dim; reorder w to match: (cin, kh, kw, cout).
    w2d = jnp.transpose(w, (2, 0, 1, 3)).reshape(cin * kh * kw, cout)
    return dense_site(patches, w2d, stats, spec)
