"""Pallas TPU kernels: sliding-window flash attention (causal, GQA-aware),
forward and fused backward.

Used by the long-context decode configs (long_500k) and Mixtral-style SWA.
Online-softmax over KV tiles; out-of-window tiles are skipped via ``pl.when``
so the compute is O(S * W) not O(S^2) — in the backward kernels too. Scratch
(VMEM) carries the running (max, denom, accumulator) across the KV sweep for
each query tile, and the (dk, dv) accumulators across the (group, Q) sweep
for each KV tile.

Two layouts:

* ``swa_flash`` — q/k/v ``(BH, S, hd)``, heads pre-flattened into the batch
  dim (GQA repeat happens in the caller). Forward only; kept for the plain
  ``swa_attention`` dispatch op.
* ``swa_flash_fwd`` / ``swa_flash_bwd_dq`` / ``swa_flash_bwd_dkdv`` — the
  training path. GQA-grouped: q/do/o ``(BKV, G, S, hd)`` (G = query heads
  per KV head), k/v ``(BKV, S, hd)`` — KV is handed to the kernel
  *unexpanded*, so kernel bandwidth does not inflate by ``h/kv`` and dk/dv
  come out accumulated per KV head. The forward also emits the per-row
  logsumexp ``lse = m + log(sum exp(s - m))`` residual the fused backward
  needs to rebuild the probabilities without a second online-softmax pass.

Grids put the accumulation axis innermost: forward/dq ``(BKV, G, S/bq,
S/bk)``; dk/dv ``(BKV, S/bk, G, S/bq)`` (each KV tile accumulates over every
query-head in its group and every visible Q tile before writing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, d_ref, acc_ref, *,
                bq: int, bk: int, window: int, n_k: int, seq_len: int,
                scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_in_range(qi, kj, bq=bq, bk=bk, window=window))
    def _tile():
        q = q_ref[0].astype(jnp.float32) * scale       # (bq, hd)
        k = k_ref[0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _tile_mask(qi, kj, bq=bq, bk=bk, window=window,
                          seq_len=seq_len)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                            # (bq,)
        m_new = jnp.maximum(m_prev, s.max(-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        d_ref[...] = d_ref[...] * corr + p.sum(-1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        denom = jnp.maximum(d_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)[None]


def _tile_in_range(qi, kj, *, bq: int, bk: int, window: int):
    """Does KV tile kj intersect the visible band of Q tile qi?
    causal: k <= q for some (q, k) in the tile pair; window: k > q - window
    for the tile's largest q."""
    q_lo = qi * bq
    q_hi = q_lo + bq - 1
    k_lo = kj * bk
    k_hi = k_lo + bk - 1
    in_range = (k_lo <= q_hi)
    if window:
        in_range = jnp.logical_and(in_range, k_hi > q_lo - window)
    return in_range


def _tile_mask(qi, kj, *, bq: int, bk: int, window: int, seq_len: int):
    """Per-element (bq, bk) visibility mask for the (qi, kj) tile pair."""
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (k_pos <= q_pos) & (k_pos < seq_len)
    if window:
        mask &= k_pos > (q_pos - window)
    return mask


def swa_flash(q: jax.Array, k: jax.Array, v: jax.Array, *, window: int = 0,
              bq: int = 256, bk: int = 256,
              interpret: bool = False) -> jax.Array:
    """q, k, v: (BH, S, hd) -> (BH, S, hd); causal (+ optional window)."""
    bh, s, hd = q.shape
    bq_ = min(bq, s)
    bk_ = min(bk, s)
    n_k = pl.cdiv(s, bk_)
    grid = (bh, pl.cdiv(s, bq_), n_k)
    scale = hd ** -0.5

    return pl.pallas_call(
        functools.partial(_swa_kernel, bq=bq_, bk=bk_, window=window,
                          n_k=n_k, seq_len=s, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),        # running max
            pltpu.VMEM((bq_,), jnp.float32),        # running denominator
            pltpu.VMEM((bq_, hd), jnp.float32),     # accumulator
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# serving path: single-query flash decode over a (ring-buffer) KV cache
# ---------------------------------------------------------------------------

def _swa_decode_kernel(q_ref, k_ref, ks_ref, v_ref, vs_ref, pos_ref, o_ref,
                       m_ref, d_ref, acc_ref, *,
                       bk: int, window: int, cache_len: int, n_k: int,
                       scale: float):
    """One grid step: q (1, G, hd) resident, sweep KV block j of the cache.

    No S x S tile walk — the grid is (N, C/bk) over KV blocks only; the
    single query row rides along in VMEM for the whole sweep, with the
    online-softmax (m, d, acc) carried in scratch exactly like the training
    forward. fp8 caches dequantize ON READ: k/v arrive as the stored payload
    and ks/vs carry the per-row scales (ones for dense caches), so the f32
    KV never exists in HBM. Ring masking derives each slot's absolute
    position from ``pos`` (slot = position % window) in-kernel; ``window ==
    0`` is the dense full-causal layout (slot s holds position s) and skips
    blocks past ``pos`` entirely.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[pl.program_id(0)]
    # dense mode: blocks whose first slot is past the query position hold
    # nothing visible — skip the compute (the ring mode visits every block:
    # capacity == window means every resident slot is in the band)
    run = (j * bk <= pos) if window == 0 else (j >= 0)

    @pl.when(run)
    def _tile():
        q = q_ref[0].astype(jnp.float32) * scale          # (G, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        k = k * ks_ref[0, 0][:, None]
        v = v * vs_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        sl = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        if window:
            r = pos % window
            base = pos - r
            p = jnp.where(sl <= r, base + sl, base - window + sl)
            valid = (p >= 0) & (p <= pos) & (p > pos - window)
            valid &= sl < window                  # lane padding past C
        else:
            p = sl
            valid = (p <= pos) & (sl < cache_len)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]                               # (G,)
        m_new = jnp.maximum(m_prev, s.max(-1))
        corr = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new[:, None])
        d_ref[...] = d_ref[...] * corr + pr.sum(-1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            pr, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        denom = jnp.maximum(d_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)[None]


def swa_flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                     k_scale: jax.Array, v_scale: jax.Array,
                     pos: jax.Array, *, window: int = 0,
                     cache_len: int = 0, bk: int = 128,
                     interpret: bool = False) -> jax.Array:
    """Single-query GQA flash decode. q (N, G, hd); k/v (N, Cp, hd) cache
    payload (fp8 or dense dtype, Cp = lane-padded capacity); k_scale/v_scale
    (N, 1, Cp) f32 per-row dequant scales (ones for dense) — the unit axis
    makes each (1, bk) scale block span the array's full second-minor dim,
    which the TPU lowering requires of a block one row tall; pos (N,) i32,
    held whole in SMEM and indexed by the grid's sequence coordinate.
    ``window`` > 0 = ring layout of capacity ``window``; 0 = dense cache of
    ``cache_len`` valid slots. Returns (N, G, hd) f32."""
    n, g, hd = q.shape
    cp = k.shape[1]
    bk_ = min(bk, cp)
    n_k = pl.cdiv(cp, bk_)
    grid = (n, n_k)
    scale = hd ** -0.5

    kv_spec = pl.BlockSpec((1, bk_, hd), lambda b, j: (b, j, 0))
    sc_spec = pl.BlockSpec((1, 1, bk_), lambda b, j: (b, 0, j))
    return pl.pallas_call(
        functools.partial(_swa_decode_kernel, bk=bk_, window=window,
                          cache_len=cache_len, n_k=n_k, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda b, j: (b, 0, 0)),
            kv_spec, sc_spec, kv_spec, sc_spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, g, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),          # running max
            pltpu.VMEM((g,), jnp.float32),          # running denominator
            pltpu.VMEM((g, hd), jnp.float32),       # accumulator
        ],
        interpret=interpret,
    )(q, k, k_scale, v, v_scale, pos)


# ---------------------------------------------------------------------------
# training path: GQA-grouped forward with logsumexp residual + fused backward
# ---------------------------------------------------------------------------

def _swa_fwd_res_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                        m_ref, d_ref, acc_ref, *,
                        bq: int, bk: int, window: int, n_k: int,
                        seq_len: int, scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_in_range(qi, kj, bq=bq, bk=bk, window=window))
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * scale    # (bq, hd)
        k = k_ref[0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _tile_mask(qi, kj, bq=bq, bk=bk, window=window,
                          seq_len=seq_len)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        d_ref[...] = d_ref[...] * corr + p.sum(-1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        denom = jnp.maximum(d_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom[:, None]
                      ).astype(o_ref.dtype)[None, None]
        lse_ref[...] = (m_ref[...] + jnp.log(denom))[None, None]


def swa_flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window: int = 0, bq: int = 256, bk: int = 256,
                  interpret: bool = False):
    """GQA forward with residuals. q: (BKV, G, S, hd); k, v: (BKV, S, hd).
    Returns (out (BKV, G, S, hd), lse (BKV, G, S) f32)."""
    bkv, g, s, hd = q.shape
    bq_ = min(bq, s)
    bk_ = min(bk, s)
    n_k = pl.cdiv(s, bk_)
    grid = (bkv, g, pl.cdiv(s, bq_), n_k)
    scale = hd ** -0.5

    return pl.pallas_call(
        functools.partial(_swa_fwd_res_kernel, bq=bq_, bk=bk_, window=window,
                          n_k=n_k, seq_len=s, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq_, hd), lambda b, g, i, j: (b, g, i, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, g, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, g, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq_, hd), lambda b, g, i, j: (b, g, i, 0)),
            pl.BlockSpec((1, 1, bq_), lambda b, g, i, j: (b, g, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, g, s, hd), q.dtype),
            jax.ShapeDtypeStruct((bkv, g, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),        # running max
            pltpu.VMEM((bq_,), jnp.float32),        # running denominator
            pltpu.VMEM((bq_, hd), jnp.float32),     # accumulator
        ],
        interpret=interpret,
    )(q, k, v)


def _bwd_tile_ds(q, k, v, do, delta, lse, qi, kj, *,
                 bq: int, bk: int, window: int, seq_len: int):
    """Shared dq/dkdv tile math: rebuild p from the lse residual, return
    (p, ds). Masked-out entries have s = NEG_INF so p (and hence ds) vanish
    without re-masking. q must arrive pre-scaled."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = _tile_mask(qi, kj, bq=bq, bk=bk, window=window, seq_len=seq_len)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse[:, None])                   # (bq, bk)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    return p, ds


def _swa_bwd_dq_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
                       dq_ref, acc_ref, *,
                       bq: int, bk: int, window: int, n_k: int,
                       seq_len: int, scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_in_range(qi, kj, bq=bq, bk=bk, window=window))
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        _, ds = _bwd_tile_ds(q, k, v, do, delta_ref[0, 0], lse_ref[0, 0],
                             qi, kj, bq=bq, bk=bk, window=window,
                             seq_len=seq_len)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == n_k - 1)
    def _finalize():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)[None, None]


def swa_flash_bwd_dq(q, k, v, lse, delta, do, *, window: int = 0,
                     bq: int = 256, bk: int = 256,
                     interpret: bool = False) -> jax.Array:
    """dq sweep: for each (group, Q tile), accumulate over visible KV tiles.
    Layouts as in :func:`swa_flash_fwd`; ``delta = rowsum(do * o)`` is
    precomputed by the caller (FlashAttention-2 style) so ``o`` never enters
    the kernel's input stream. Returns dq (BKV, G, S, hd) f32."""
    bkv, g, s, hd = q.shape
    bq_ = min(bq, s)
    bk_ = min(bk, s)
    n_k = pl.cdiv(s, bk_)
    grid = (bkv, g, pl.cdiv(s, bq_), n_k)
    scale = hd ** -0.5

    q_spec = pl.BlockSpec((1, 1, bq_, hd), lambda b, g, i, j: (b, g, i, 0))
    kv_spec = pl.BlockSpec((1, bk_, hd), lambda b, g, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, 1, bq_), lambda b, g, i, j: (b, g, i))
    return pl.pallas_call(
        functools.partial(_swa_bwd_dq_kernel, bq=bq_, bk=bk_, window=window,
                          n_k=n_k, seq_len=s, scale=scale),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, row_spec, row_spec, q_spec],
        out_specs=pl.BlockSpec((1, 1, bq_, hd), lambda b, g, i, j: (b, g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bkv, g, s, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq_, hd), jnp.float32),     # dq accumulator
        ],
        interpret=interpret,
    )(q, k, v, lse, delta, do)


def _swa_bwd_dkdv_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
                         dk_ref, dv_ref, dk_acc, dv_acc, *,
                         bq: int, bk: int, window: int, n_g: int, n_q: int,
                         seq_len: int, scale: float):
    kj = pl.program_id(1)
    gi = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_tile_in_range(qi, kj, bq=bq, bk=bk, window=window))
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        p, ds = _bwd_tile_ds(q, k, v, do, delta_ref[0, 0], lse_ref[0, 0],
                             qi, kj, bq=bq, bk=bk, window=window,
                             seq_len=seq_len)
        # accumulate per KV head: every group head and every visible Q tile
        # lands in the same (bk, hd) accumulators
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # q is pre-scaled, so ds^T @ q already carries the 1/sqrt(hd)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((gi == n_g - 1) & (qi == n_q - 1))
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)[None]
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)[None]


def swa_flash_bwd_dkdv(q, k, v, lse, delta, do, *, window: int = 0,
                       bq: int = 256, bk: int = 256,
                       interpret: bool = False):
    """dk/dv sweep: for each KV tile, accumulate over the query-head group
    AND every visible Q tile (grid (BKV, S/bk, G, S/bq), Q innermost).
    ``delta`` precomputed as in :func:`swa_flash_bwd_dq`. Returns (dk, dv),
    both (BKV, S, hd) f32 — per KV head, unexpanded."""
    bkv, g, s, hd = q.shape
    bq_ = min(bq, s)
    bk_ = min(bk, s)
    n_q = pl.cdiv(s, bq_)
    grid = (bkv, pl.cdiv(s, bk_), g, n_q)
    scale = hd ** -0.5

    q_spec = pl.BlockSpec((1, 1, bq_, hd), lambda b, j, g, i: (b, g, i, 0))
    kv_spec = pl.BlockSpec((1, bk_, hd), lambda b, j, g, i: (b, j, 0))
    row_spec = pl.BlockSpec((1, 1, bq_), lambda b, j, g, i: (b, g, i))
    return pl.pallas_call(
        functools.partial(_swa_bwd_dkdv_kernel, bq=bq_, bk=bk_,
                          window=window, n_g=g, n_q=n_q, seq_len=s,
                          scale=scale),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, row_spec, row_spec, q_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, s, hd), jnp.float32),
            jax.ShapeDtypeStruct((bkv, s, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk_, hd), jnp.float32),     # dk accumulator
            pltpu.VMEM((bk_, hd), jnp.float32),     # dv accumulator
        ],
        interpret=interpret,
    )(q, k, v, lse, delta, do)
