"""Pallas TPU kernel: blocked Newton-Schulz damped inverse (Stage-4).

SP-NGD recomputes ``(F + lambda I)^-1`` per Kronecker-factor block on every
refresh step. Eigendecomposition / Cholesky are the one Stage-4 workload
that cannot ride the MXU (not matmul-shaped); the Newton-Schulz iteration

    X_{k+1} = X_k (2I - M X_k) = X_k + X_k (I - M X_k)

is nothing BUT matmuls, so this kernel moves the inversion onto the MXU.

Contract per grid instance (one factor block, fully VMEM-resident):

* input is the already-damped, already-symmetrized ``M = F + lambda I``
  (the XLA side owns damping/symmetrization — pure elementwise prep, the
  same division of labour as the ``delta`` rowsum in the attention
  backward);
* the initial iterate is the spectral-norm upper-bound scaling computed
  in-kernel from one pass over ``M``:

      X_0 = M / (||M||_1 ||M||_inf)

  (``M`` symmetric, so ``M^T = M``); ``||M||_1 ||M||_inf >= ||M||_2^2``
  places every eigenvalue of ``M X_0`` in (0, 1], making ``I - M X_0`` a
  contraction for SPD ``M``;
* the iteration runs under a ``fori_loop`` cap of ``iters``; each step
  measures the fixed-point residual ``||I - M X_k||_F / ||I||_F`` and
  freezes the iterate once it reaches ``tol`` (the early exit — further
  trips keep the converged X bit-stable);
* outputs are the final iterate AND its residual, so the dispatch layer
  can detect blocks that failed to contract (ill-conditioned under weak
  damping) and re-solve exactly those via the eigh path.

The whole block stays resident: M, X and the step temporary are
``3 * b^2 * 4`` bytes, which caps the kernel at b = 1024 against the
~16 MB/core VMEM (``ops.NS_KERNEL_MAX_DIM``). Larger blocks run the
TWO-LEVEL tiled variant below (``ns_tiled_residual`` /
``ns_tiled_update``): the operands stay HBM-resident and each matmul of
the iteration walks a ``(bt, bt)`` VMEM tile grid — outer level = the
Newton-Schulz step sequencing (one ``fori_loop`` trip per iteration on
the XLA side, ``ops.ns_inverse_tiled``), inner level = the per-matmul
tile loop inside the kernels — so big blocks no longer fall back to the
jnp reference iteration.

Grid: (g,) for the VMEM-resident kernel (one program per block, no
revisit); (g, nt, nt, nt) for the tiled kernels (output tiles revisited
along the contraction axis, the standard accumulate-in-VMEM pattern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ns_kernel(m_ref, x_ref, res_ref, *, iters: int, tol: float):
    m = m_ref[0].astype(jnp.float32)                 # (bp, bp)
    bp = m.shape[0]
    ri = jax.lax.broadcasted_iota(jnp.int32, (bp, bp), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (bp, bp), 1)
    eye = jnp.where(ri == ci, 1.0, 0.0).astype(jnp.float32)

    am = jnp.abs(m)
    n1 = jnp.max(jnp.sum(am, axis=0))                # max abs column sum
    ninf = jnp.max(jnp.sum(am, axis=1))              # max abs row sum
    # M is symmetric by contract, so M^T / (n1 * ninf) == M * inv_scale
    x = m * (1.0 / (n1 * ninf))
    rnorm = 1.0 / (bp ** 0.5)                        # 1 / ||I||_F, static

    def mm(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def body(_, x):
        r = eye - mm(m, x)
        res = jnp.sqrt(jnp.sum(r * r)) * rnorm
        # early exit: once res <= tol the iterate freezes (any further
        # trips of the capped loop return X unchanged)
        return jnp.where(res > tol, x + mm(x, r), x)

    x = jax.lax.fori_loop(0, iters, body, x)
    # residual of the RETURNED iterate (the in-loop value lags one step);
    # the dispatch layer reads res > tol as "failed to contract"
    r = eye - mm(m, x)
    res_ref[...] = (jnp.sqrt(jnp.sum(r * r)) * rnorm).reshape(1, 1, 1)
    x_ref[...] = x[None]


def _ns_vmem_limit(bp: int) -> int:
    """Scoped-VMEM request for one resident block: the double-buffered
    input and output blocks plus M, X and the step temporary in the body
    (~7 b^2 f32) and headroom. At bp = 1024 that is ~36 MiB: past the
    16 MiB default scope, well inside a v5e core's 128 MiB."""
    return 9 * bp * bp * 4 + (4 << 20)


def ns_inverse_blocks(m: jax.Array, *, iters: int, tol: float,
                      interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """m: (g, bp, bp) f32 symmetric damped blocks ->
    (x (g, bp, bp) f32, res (g, 1, 1) f32).

    The residual block is (1, 1, 1) over a (g, 1, 1) array: its last two
    dims equal the array's, which is what the TPU lowering accepts for a
    block narrower than one (8, 128) tile."""
    g, bp, _ = m.shape
    grid = (g,)
    return pl.pallas_call(
        functools.partial(_ns_kernel, iters=iters, tol=tol),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bp, bp), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, bp, bp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, bp, bp), jnp.float32),
            jax.ShapeDtypeStruct((g, 1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_ns_vmem_limit(bp)),
        interpret=interpret,
    )(m)


# ---------------------------------------------------------------------------
# Two-level tiled variant: blocks past the VMEM cap. M and X stay
# HBM-resident; each Newton-Schulz matmul is its own pallas_call whose
# (g, nt, nt, nt) grid streams (bt, bt) tiles through VMEM — the output
# tile is revisited along the trailing contraction dim k and accumulated
# in place (it stays VMEM-resident across the k sweep because its index
# map ignores k). Step sequencing (freeze-on-converge, the iteration cap)
# lives in ops.ns_inverse_tiled's fori_loop.
# ---------------------------------------------------------------------------

def _mm(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _ns_resid_kernel(m_ref, x_ref, r_ref, ss_ref, *, nt: int, bt: int):
    """One (i, j, k) tile visit of R = I - M @ X, plus the squared
    Frobenius norm of R accumulated into ss (g, 1, 1) across all tiles."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)
    part = _mm(m_ref[0], x_ref[0])

    @pl.when(k == 0)
    def _init():
        # identity tile at global offsets (i*bt, j*bt): nonzero only when
        # the tile straddles the diagonal (i == j)
        ri = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0) + i * bt
        ci = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1) + j * bt
        eye = jnp.where(ri == ci, 1.0, 0.0).astype(jnp.float32)
        r_ref[...] = (eye - part)[None]

    @pl.when(k != 0)
    def _accum():
        r_ref[...] = r_ref[...] - part[None]

    @pl.when(k == nt - 1)
    def _norm():
        r = r_ref[0]
        ss = jnp.sum(r * r)
        first = jnp.logical_and(i == 0, j == 0)

        @pl.when(first)
        def _seed():
            ss_ref[...] = ss.reshape(1, 1, 1)

        @pl.when(jnp.logical_not(first))
        def _add():
            ss_ref[...] = ss_ref[...] + ss



def ns_tiled_residual(m: jax.Array, x: jax.Array, *, bt: int,
                      interpret: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
    """R = I - M @ X over (g, bp, bp) HBM-resident blocks with a (bt, bt)
    VMEM tile loop; also returns ss (g, 1, 1) = ||R||_F^2 per block."""
    g, bp, _ = m.shape
    nt = bp // bt
    return pl.pallas_call(
        functools.partial(_ns_resid_kernel, nt=nt, bt=bt),
        grid=(g, nt, nt, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bt), lambda gi, i, j, k: (gi, i, k)),
            pl.BlockSpec((1, bt, bt), lambda gi, i, j, k: (gi, k, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bt), lambda gi, i, j, k: (gi, i, j)),
            pl.BlockSpec((1, 1, 1), lambda gi, i, j, k: (gi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, bp, bp), jnp.float32),
            jax.ShapeDtypeStruct((g, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(m, x)


def _ns_update_kernel(xij_ref, xik_ref, r_ref, o_ref):
    """One (i, j, k) tile visit of X' = X + X @ R (the same X streamed
    under two index maps: the addend tile (i, j) and the operand tile
    (i, k))."""
    k = pl.program_id(3)
    part = _mm(xik_ref[0], r_ref[0])

    @pl.when(k == 0)
    def _init():
        o_ref[...] = xij_ref[...] + part[None]

    @pl.when(k != 0)
    def _accum():
        o_ref[...] = o_ref[...] + part[None]


def ns_tiled_update(x: jax.Array, r: jax.Array, *, bt: int,
                    interpret: bool = False) -> jax.Array:
    """X' = X + X @ R over (g, bp, bp) HBM-resident blocks."""
    g, bp, _ = x.shape
    nt = bp // bt
    return pl.pallas_call(
        _ns_update_kernel,
        grid=(g, nt, nt, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bt), lambda gi, i, j, k: (gi, i, j)),
            pl.BlockSpec((1, bt, bt), lambda gi, i, j, k: (gi, i, k)),
            pl.BlockSpec((1, bt, bt), lambda gi, i, j, k: (gi, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bt, bt), lambda gi, i, j, k: (gi, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, bp, bp), jnp.float32),
        interpret=interpret,
    )(x, x, r)
