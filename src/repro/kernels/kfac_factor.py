"""Pallas TPU kernel: symmetric rank-k factor construction  A = X^T X.

This is the paper's statistics-construction hot-spot (§5.2 "the first
hotspot is the construction of the statistics A, G") mapped to the TPU:

* MXU-aligned (multiples of 128) VMEM tiles;
* f32 accumulation from bf16 inputs (the paper's mixed-precision Tensor-Core
  factor computation, §5.2);
* symmetry-aware *compute*: only output tiles with i <= j are computed
  (``pl.when`` guard); the wrapper mirrors the strict upper triangle. This
  is the TPU analogue of the paper's symmetry-aware communication — applied
  one level earlier, to the FLOPs themselves (~2x tile savings).

Grid: (d/bm, d/bn, n/bk); the k axis accumulates into the (i, j) output
tile, which Pallas keeps resident in VMEM across the k sweep (output revisit
ordering), so each tile is written to HBM exactly once.

``factor_syrk_wire`` is the fused wire-format variant (Stage-3 "fused"
strategy): the SYRK accumulates into a f32 VMEM scratch block, and the final
k step runs the :mod:`repro.kernels.quant_pack` epilogue in place — block
amax, per-block scale, clip, fp8 cast — so the ONLY HBM writes are the fp8
payload and one f32 scale. The raw f32 factor sum never round-trips HBM
before the collective.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _factor_kernel(x_i_ref, x_j_ref, out_ref, *, n_k: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i <= j)
    def _accum():
        xi = x_i_ref[...].astype(jnp.float32)      # (bk, bm)
        xj = x_j_ref[...].astype(jnp.float32)      # (bk, bn)
        out_ref[...] += jax.lax.dot_general(
            xi, xj, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def factor_syrk(x: jax.Array, *, bm: int = 256, bn: int = 256,
                bk: int = 512, interpret: bool = False) -> jax.Array:
    """x: (n, d) -> lower-triangle-valid (d, d) f32 partial result.

    Tiles with i > j are left zero; use ``ops.kfac_factor`` for the
    mirrored symmetric result.
    """
    n, d = x.shape
    bm = min(bm, d)
    bn = min(bn, d)
    bk = min(bk, n)
    grid = (pl.cdiv(d, bm), pl.cdiv(d, bn), pl.cdiv(n, bk))

    return pl.pallas_call(
        functools.partial(_factor_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, d), jnp.float32),
        interpret=interpret,
    )(x, x)


def _factor_wire_kernel(x_ref, payload_ref, scale_ref, acc_ref, *,
                        n_k: int, fmt_max: float, pow2: bool):
    """SYRK accumulate in VMEM scratch; quantize epilogue on the last k.

    The epilogue is byte-for-byte the :mod:`quant_pack` math (explicit
    reciprocal-multiply scale, pow2 rounding, clip before the fp8 cast) with
    ONE scale for the whole (b, b) block — the same granularity as one
    sym-packed row, so the emitted tile is the PR-5 wire/storage tile.
    """
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xk = x_ref[...].astype(jnp.float32)                  # (bk, b)
    acc_ref[...] += jax.lax.dot_general(
        xk, xk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        f = acc_ref[...]                                 # (b, b) f32
        amax = jnp.max(jnp.abs(f))
        s = amax * (1.0 / fmt_max)
        if pow2:
            s = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(s, 2.0 ** -126))))
        s = jnp.where(amax > 0, s, 1.0)
        # a (1, 1) vector store: Mosaic cannot store a scalar to VMEM
        scale_ref[...] = jnp.broadcast_to(s, (1, 1))
        q = jnp.clip(f / s, -fmt_max, fmt_max)   # e4m3fn overflows to NaN
        payload_ref[...] = q.astype(payload_ref.dtype)


def factor_syrk_wire(x: jax.Array, fp8_dtype, *, fmt_max: float,
                     pow2: bool = False, bk: int = 512,
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: (n, b) -> (payload (b, b) fp8, scale (1, 1) f32).

    Single-block fused SYRK -> wire-format epilogue: the f32 accumulator
    lives only in VMEM scratch across the k sweep; the last grid step
    quantizes it in place. The full (b, b) fp8 block is emitted (symmetric
    by construction); the wrapper's XLA-side ``sym_pack`` gather on the
    1-byte payload produces the packed triangle — pure byte movement, the
    same division of labour as the quant_pack wrappers.
    """
    n, b = x.shape
    bkk = min(bk, n)
    grid = (pl.cdiv(n, bkk),)
    return pl.pallas_call(
        functools.partial(_factor_wire_kernel, n_k=grid[0],
                          fmt_max=fmt_max, pow2=pow2),
        grid=grid,
        in_specs=[pl.BlockSpec((bkk, b), lambda k: (k, 0))],
        out_specs=[
            pl.BlockSpec((b, b), lambda k: (0, 0)),
            pl.BlockSpec((1, 1), lambda k: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, b), fp8_dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, b), jnp.float32)],
        interpret=interpret,
    )(x)
