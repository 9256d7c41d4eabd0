"""Kronecker-factored curvature math (paper §3.3, §4).

Conventions
-----------
* Weights are stored ``(d_in, d_out)``; a dense site computes ``y = x @ w``.
* Tokens-as-samples empirical Fisher: with ``n`` the number of tokens that
  flowed through a site, the Kronecker factors are

      A = (1/n) sum_t a_t a_t^T          (input second moment)
      G = (1/n) sum_t ghat_t ghat_t^T    (output log-likelihood grad 2nd moment)

  where ``ghat = n * dL/ds`` undoes the mean-loss scaling, so
  ``G_raw = sum_t (dL/ds)(dL/ds)^T`` relates as ``G = n * G_raw``.
* The natural-gradient update for the site is ``U = A^-1 @ dW @ G^-1``
  (``F = G (x) A`` for vec in our layout; Eq. 6/12 of the paper).
* Large dimensions are split into diagonal blocks of at most ``max_dim``
  ("block-diagonal factor capping", DESIGN.md §4) and every factor array
  carries a leading block axis ``(nb, b, b)`` — possibly with further leading
  layer / expert axes. All ops here broadcast over leading axes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Block partitioning
# ---------------------------------------------------------------------------

def num_blocks(d: int, max_dim: int) -> int:
    """Number of diagonal blocks a dimension of size ``d`` is split into."""
    return max(1, -(-d // max_dim))


def block_size(d: int, max_dim: int) -> int:
    """Uniform (padded) block size used for a dimension of size ``d``."""
    nb = num_blocks(d, max_dim)
    return -(-d // nb)


def padded_dim(d: int, max_dim: int) -> int:
    return num_blocks(d, max_dim) * block_size(d, max_dim)


def block_reshape(x: jax.Array, d: int, max_dim: int, axis: int = -1) -> jax.Array:
    """Reshape ``axis`` (size d) into (nb, b), zero-padding to nb*b."""
    nb = num_blocks(d, max_dim)
    b = block_size(d, max_dim)
    axis = axis % x.ndim
    pad = nb * b - d
    if pad:
        cfg = [(0, 0)] * x.ndim
        cfg[axis] = (0, pad)
        x = jnp.pad(x, cfg)
    new_shape = x.shape[:axis] + (nb, b) + x.shape[axis + 1:]
    return x.reshape(new_shape)


def block_unreshape(x: jax.Array, d: int, axis: int = -2) -> jax.Array:
    """Inverse of :func:`block_reshape`: merge (nb, b) at ``axis`` back to d."""
    axis = axis % x.ndim
    nb, b = x.shape[axis], x.shape[axis + 1]
    merged = x.reshape(x.shape[:axis] + (nb * b,) + x.shape[axis + 2:])
    if nb * b != d:
        merged = jax.lax.slice_in_dim(merged, 0, d, axis=axis)
    return merged


# ---------------------------------------------------------------------------
# Factor statistics from token matrices
# ---------------------------------------------------------------------------

def factor_sum(x: jax.Array, max_dim: int, *,
               backend: Optional[str] = None) -> jax.Array:
    """Blocked ``sum_t x_t x_t^T`` for a token matrix ``x`` of shape
    (..., n, d). Returns (..., nb, b, b) in f32.

    Inputs stay in their storage dtype (bf16 on TPU) with f32 accumulation —
    the paper's mixed-precision Tensor-Core statistics construction (§5.2)
    mapped to the MXU; it also halves any sharding-induced traffic on x.

    ``backend`` selects the implementation ("ref" | "pallas" | "auto", see
    :mod:`repro.kernels.dispatch`)."""
    from repro.kernels import dispatch
    return dispatch.factor_sum(x, max_dim, backend=backend)


def factor_sum_wire(x: jax.Array, max_dim: int, *, fmt: str = "e4m3",
                    scale_mode: str = "fp32",
                    backend: Optional[str] = None):
    """Fused :func:`factor_sum` + wire-format epilogue: returns
    ``(payload fp8 (..., nb, t), scale f32 (..., nb))`` — the sym-packed
    per-block-quantized tile the Stage-3 "fused" strategy puts on the wire
    (see :mod:`repro.kernels.dispatch` ``factor_sum_wire``)."""
    from repro.kernels import dispatch
    return dispatch.factor_sum_wire(x, max_dim, fmt=fmt,
                                    scale_mode=scale_mode, backend=backend)


def diag_factor_sum(x: jax.Array) -> jax.Array:
    """``sum_t x_t^2`` per output coordinate. (..., n, d) -> (..., d)."""
    x = x.astype(jnp.float32)
    return jnp.sum(x * x, axis=-2)


# ---------------------------------------------------------------------------
# Damping + inversion (Eq. 12)
# ---------------------------------------------------------------------------

def _block_trace(f: jax.Array) -> jax.Array:
    """Trace summed over the block axis. f: (..., nb, b, b) -> (...,)."""
    return jnp.trace(f, axis1=-2, axis2=-1).sum(-1)


def pi_correction(a: jax.Array, g: jax.Array, d_a: int, d_g: int,
                  eps: float = 1e-12) -> jax.Array:
    """Martens-Grosse pi: sqrt(mean_eig(A) / mean_eig(G)) via traces.

    ``a``: (..., nbA, bA, bA), ``g``: (..., nbG, bG, bG); returns (...,).
    ``d_a``/``d_g`` are the true (unpadded) dimensions.
    """
    tr_a = _block_trace(a) / d_a
    tr_g = _block_trace(g) / d_g
    return jnp.sqrt(jnp.maximum(tr_a, eps) / jnp.maximum(tr_g, eps))


def damped_inverse(f: jax.Array, damping: jax.Array) -> jax.Array:
    """Inverse of SPD blocked factor ``f + damping*I``.

    f: (..., nb, b, b); damping broadcastable to (...,). Uses eigh for
    robustness (clamps negative eigenvalues that appear from bf16
    accumulation). bf16 inputs solve in f32 (LAPACK has no bf16 eigh);
    outputs are f32 either way."""
    f = f.astype(jnp.float32)
    f = 0.5 * (f + jnp.swapaxes(f, -1, -2))  # re-symmetrize
    vals, vecs = jnp.linalg.eigh(f)
    d = jnp.asarray(damping)[..., None]  # broadcast over the eigenvalue axis
    inv_vals = 1.0 / (jnp.maximum(vals, 0.0) + d)
    return jnp.einsum("...ab,...b,...cb->...ac", vecs, inv_vals, vecs)


def cholesky_inverse(f: jax.Array, damping: jax.Array) -> jax.Array:
    """Cheaper inverse via Cholesky; requires f SPD after damping.
    Solves in f32 like :func:`damped_inverse` (no bf16 LAPACK)."""
    b = f.shape[-1]
    f = f.astype(jnp.float32)
    f = 0.5 * (f + jnp.swapaxes(f, -1, -2))
    d = jnp.asarray(damping)[..., None, None]
    eye = jnp.eye(b, dtype=f.dtype)
    fd = f + d * eye
    chol = jnp.linalg.cholesky(fd)
    return jax.scipy.linalg.cho_solve((chol, True), jnp.broadcast_to(eye, fd.shape))


# Newton-Schulz knobs, defined ONCE here (the algorithm's home): everything
# downstream — dispatch.damped_inverse, NGDConfig.ns_iters/ns_tol — defaults
# to these, so tuning the cap or tolerance is a one-line change.
NS_ITERS = 40   # iteration cap: covers damped condition numbers ~1e4 in f32
NS_TOL = 1e-4   # relative fixed-point residual for early exit / fallback


def newton_schulz_inverse(f: jax.Array, damping: jax.Array, *,
                          iters: int = NS_ITERS,
                          tol: float = NS_TOL) -> tuple[jax.Array, jax.Array]:
    """Matmul-only blocked inverse of ``f + damping*I`` (Newton-Schulz).

    The iteration ``X_{k+1} = X_k (2I - M X_k)`` with the spectral-norm
    upper-bound init ``X_0 = M^T / (||M||_1 ||M||_inf)`` converges
    quadratically for SPD ``M = f + damping*I`` (every eigenvalue of
    ``M X_0`` lies in (0, 1]); this is the pure-jnp reference for the
    Stage-4 Pallas kernel — the inverse built from nothing but GEMMs.

    Per block, iterates freeze once the relative fixed-point residual
    ``||I - M X_k||_F / ||I||_F`` drops to ``tol`` (the early exit); the
    cap ``iters`` bounds the work for blocks that never contract that far.

    f: (..., nb, b, b); damping broadcastable like :func:`damped_inverse`.
    Returns ``(x, res)`` with ``res`` (..., nb) the relative residual of
    the RETURNED iterate — callers use ``res > tol`` as the
    failed-to-contract predicate (ill-conditioned block -> eigh fallback
    in :mod:`repro.kernels.dispatch`).
    """
    b = f.shape[-1]
    f = f.astype(jnp.float32)
    f = 0.5 * (f + jnp.swapaxes(f, -1, -2))
    # damping follows the damped_inverse broadcast convention: (...,) or
    # (..., 1) against the block axis -> expand over (nb,) then the matrix
    d = jnp.broadcast_to(jnp.asarray(damping, jnp.float32), f.shape[:-2])
    eye = jnp.eye(b, dtype=jnp.float32)
    m = f + d[..., None, None] * eye
    # ||M||_1 * ||M||_inf >= ||M||_2^2, so every eigenvalue of M X_0 is in
    # (0, 1] and I - M X_0 is a contraction
    n1 = jnp.max(jnp.sum(jnp.abs(m), axis=-2), axis=-1)
    ninf = jnp.max(jnp.sum(jnp.abs(m), axis=-1), axis=-1)
    x0 = jnp.swapaxes(m, -1, -2) / (n1 * ninf)[..., None, None]
    rnorm = 1.0 / np.sqrt(b)                       # 1 / ||I||_F

    def body(_, x):
        r = eye - jnp.einsum("...ab,...bc->...ac", m, x,
                             preferred_element_type=jnp.float32)
        res = jnp.sqrt(jnp.sum(r * r, axis=(-1, -2))) * rnorm
        step = x + jnp.einsum("...ab,...bc->...ac", x, r,
                              preferred_element_type=jnp.float32)
        return jnp.where((res > tol)[..., None, None], step, x)

    x = jax.lax.fori_loop(0, iters, body, x0)
    # residual of the returned iterate (the in-loop one lags by a step)
    r = eye - jnp.einsum("...ab,...bc->...ac", m, x,
                         preferred_element_type=jnp.float32)
    res = jnp.sqrt(jnp.sum(r * r, axis=(-1, -2))) * rnorm
    return x, res


def damped_factor_inverses(a: jax.Array, g: jax.Array, lam: float,
                           d_a: int, d_g: int, *, method: str = "eigh",
                           backend: Optional[str] = None,
                           ns_iters: int = NS_ITERS,
                           ns_tol: float = NS_TOL) -> tuple[jax.Array, jax.Array]:
    """Compute (A + pi*sqrt(lam) I)^-1 and (G + sqrt(lam)/pi I)^-1 (Eq. 12).

    Routes through :func:`repro.kernels.dispatch.damped_inverse` — the same
    signature the optimizer's Stage-4 recompute uses — so ``method``
    ("eigh" | "cholesky" | "newton_schulz") and ``backend`` select the
    implementation in exactly one place."""
    from repro.kernels import dispatch
    pi = pi_correction(a, g, d_a, d_g)
    sl = jnp.sqrt(jnp.asarray(lam, jnp.float32))
    kw = dict(method=method, backend=backend, ns_iters=ns_iters,
              ns_tol=ns_tol)
    a_inv = dispatch.damped_inverse(a, (pi * sl)[..., None], **kw)
    g_inv = dispatch.damped_inverse(g, (sl / pi)[..., None], **kw)
    return a_inv, g_inv


# ---------------------------------------------------------------------------
# Preconditioning
# ---------------------------------------------------------------------------

def precondition(dw: jax.Array, a_inv: Optional[jax.Array],
                 g_inv: Optional[jax.Array], *,
                 backend: Optional[str] = None) -> jax.Array:
    """Apply ``U = A^-1 @ dW @ G^-1`` with blocked inverses.

    dw: (..., d_in, d_out).
    a_inv: (..., nbA, bA, bA) or (..., d_in) diagonal or None.
    g_inv: (..., nbG, bG, bG) or (..., d_out) diagonal or None.
    ``backend`` routes the blocked applications through
    :mod:`repro.kernels.dispatch` (diagonal sides stay elementwise).
    """
    from repro.kernels import dispatch
    d_in, d_out = dw.shape[-2], dw.shape[-1]
    u = dw.astype(jnp.float32)
    if a_inv is not None:
        if a_inv.ndim == dw.ndim - 1:          # diagonal over d_in
            u = a_inv[..., :, None] * u
        else:
            ba = a_inv.shape[-1]
            ub = block_reshape(u, d_in, ba, axis=-2)   # (..., nbA, bA, d_out)
            ub = dispatch.block_precond_left(a_inv, ub, backend=backend)
            u = block_unreshape(ub, d_in, axis=-3)
    if g_inv is not None:
        if g_inv.ndim == dw.ndim - 1:          # diagonal over d_out
            u = u * g_inv[..., None, :]
        else:
            bg = g_inv.shape[-1]
            ub = block_reshape(u, d_out, bg, axis=-1)  # (..., d_in, nbG, bG)
            ub = dispatch.block_precond_right(ub, g_inv, backend=backend)
            u = block_unreshape(ub, d_out, axis=-2)
    return u.astype(dw.dtype)


# ---------------------------------------------------------------------------
# Unit-wise 2x2 inverse (Eq. 15-17) — used by scale/bias parameters
# ---------------------------------------------------------------------------

def unitwise_solve(stats: jax.Array, g_gamma: jax.Array, g_beta: jax.Array,
                   lam: float) -> tuple[jax.Array, jax.Array]:
    """Solve the per-channel 2x2 damped system (paper Eq. 16-17).

    stats: (..., C, 3) rows [E[gg], E[gb], E[bb]] per channel.
    g_gamma, g_beta: (..., C) gradients. Returns preconditioned grads.
    """
    aa = stats[..., 0] + lam
    ab = stats[..., 1]
    bb = stats[..., 2] + lam
    det = aa * bb - ab * ab
    det = jnp.where(det <= 1e-20, 1e-20, det)
    ug = (bb * g_gamma - ab * g_beta) / det
    ub = (-ab * g_gamma + aa * g_beta) / det
    return ug, ub


def diag_solve(stats: jax.Array, g: jax.Array, lam: float) -> jax.Array:
    """1x1 unit-wise (diagonal Fisher) solve: g / (E[g^2] + lam)."""
    return g / (stats + lam)


# ---------------------------------------------------------------------------
# Symmetry-aware packing (paper §5.2) — upper-triangular communication
# ---------------------------------------------------------------------------

def tril_indices(b: int) -> tuple[np.ndarray, np.ndarray]:
    return np.tril_indices(b)


def _take_bits(x: jax.Array, idx: np.ndarray) -> jax.Array:
    """Static gather along the last axis that moves BITS, not values: float
    payloads gather through their same-width unsigned view. A float gather
    may canonicalize NaN encodings (XLA:CPU widens fp8 before moving it),
    and packed payloads must round-trip bit for bit."""
    idx = jnp.asarray(idx)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.take(x, idx, axis=-1)
    bits = jnp.dtype(f"uint{8 * x.dtype.itemsize}")
    u = jax.lax.bitcast_convert_type(x, bits)
    return jax.lax.bitcast_convert_type(jnp.take(u, idx, axis=-1), x.dtype)


def sym_pack(f: jax.Array) -> jax.Array:
    """Pack symmetric (..., b, b) into (..., b(b+1)/2)."""
    b = f.shape[-1]
    i, j = np.tril_indices(b)
    return _take_bits(f.reshape(f.shape[:-2] + (b * b,)), i * b + j)


def sym_unpack(p: jax.Array, b: int) -> jax.Array:
    """Inverse of :func:`sym_pack`. A static GATHER, not a scatter: entry
    (r, c) reads packed position tri(max(r,c)) + min(r,c) — cheaper to
    lower, and exact for any dtype (incl. fp8 payloads) since only bits
    move."""
    r = np.arange(b)
    hi = np.maximum(r[:, None], r[None, :])
    lo = np.minimum(r[:, None], r[None, :])
    idx = (hi * (hi + 1)) // 2 + lo                      # (b, b) int
    return _take_bits(p, idx.reshape(-1)).reshape(p.shape[:-1] + (b, b))


# ---------------------------------------------------------------------------
# Frobenius similarity (Algorithm 2's predicate)
# ---------------------------------------------------------------------------

def frob_distance(x: jax.Array, y: jax.Array, eps: float = 1e-30) -> jax.Array:
    """||x - y||_F / ||y||_F, computed over ALL axes (a whole factor family
    is compared at once; DESIGN.md §"per-family refresh")."""
    num = jnp.sqrt(jnp.sum((x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2))
    den = jnp.sqrt(jnp.sum(y.astype(jnp.float32) ** 2))
    return num / jnp.maximum(den, eps)
