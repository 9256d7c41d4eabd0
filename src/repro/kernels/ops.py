"""Jitted public wrappers around the Pallas kernels.

Every wrapper takes ``interpret=None``, which :func:`interpret_mode`
resolves: the kernels compile to Mosaic on a TPU and run under the Pallas
interpreter on the CPU backend (tests). Passing ``interpret=False``
explicitly compiles for the TPU whatever the default backend is.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import kfac_factor as _factor
from repro.kernels import kfac_precond as _precond
from repro.kernels import newton_schulz as _ns
from repro.kernels import quant_pack as _quant
from repro.kernels import swa_attention as _swa


def interpret_mode() -> bool:
    """The one place interpret mode is decided: only the CPU backend runs
    the kernels in the interpreter. A TPU compiles them, and any other
    backend is an error rather than a quiet interpreter run."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas TPU kernels cannot run on the "
                           f"{backend!r} backend")
    return backend == "cpu"


def _interpret(interpret: bool | None) -> bool:
    return interpret_mode() if interpret is None else interpret


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def kfac_factor(x: jax.Array, *, bm: int = 256, bn: int = 256, bk: int = 512,
                interpret: bool | None = None) -> jax.Array:
    """Symmetric factor A = X^T X (f32). The kernel fills only tiles with
    tile_i <= tile_j (symmetry-aware compute, DESIGN.md §6); this wrapper
    mirrors the strict-upper tiles and keeps diagonal tiles as computed."""
    if bm != bn:
        raise ValueError(f"kfac_factor needs square tiling (diagonal tiles "
                         f"are mirrored in place); got bm={bm}, bn={bn}")
    interpret = _interpret(interpret)
    n, d = x.shape
    bt = min(bm, d)
    bkk = min(bk, n)
    dp = -(-d // bt) * bt
    np_ = -(-n // bkk) * bkk
    if dp != d or np_ != n:
        x = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    m = _factor.factor_syrk(x, bm=bt, bn=bt, bk=bkk, interpret=interpret)
    tr = jnp.arange(dp) // bt
    upper = jnp.where(tr[:, None] < tr[None, :], m, 0.0)
    diag = jnp.where(tr[:, None] == tr[None, :], m, 0.0)
    return (upper + upper.T + diag)[:d, :d]


# largest factor block the fused wire kernel keeps VMEM-resident: the f32
# scratch accumulator costs b^2 * 4 bytes plus the fp8 payload block and one
# (bk, b) input tile; 1024 -> ~5.7 MB against the ~16 MB/core budget.
# Dispatch routes bigger blocks to the ref path (XLA SYRK + quantize_rows).
FACTOR_WIRE_MAX_DIM = 1024


@functools.partial(jax.jit, static_argnames=("fmt", "scale_mode", "bk",
                                             "interpret"))
def kfac_factor_wire(x: jax.Array, *, fmt: str = "e4m3",
                     scale_mode: str = "fp32", bk: int = 512,
                     interpret: bool | None = None):
    """Fused factor construction + wire-format epilogue for ONE block:
    x (n, b) -> (payload (t,) fp8 sym-packed, scale () f32).

    The f32 factor sum exists only in the kernel's VMEM scratch; HBM
    receives the fp8 block + scale, and the sym-pack below is a static
    tril gather on 1-byte data (same row order as ``kfac.sym_pack``, so
    the emitted tile IS the PR-5 wire/storage tile)."""
    from repro.quant import quant as _q
    interpret = _interpret(interpret)
    n, b = x.shape
    if b > FACTOR_WIRE_MAX_DIM:
        raise ValueError(f"kfac_factor_wire holds the whole block in VMEM; "
                         f"b={b} exceeds FACTOR_WIRE_MAX_DIM="
                         f"{FACTOR_WIRE_MAX_DIM} (route to the ref path)")
    bp = -(-b // 128) * 128          # lane alignment; zeros are amax-neutral
    bkk = min(bk, n)
    npad = -(-n // bkk) * bkk
    if bp != b or npad != n:
        x = jnp.pad(x, ((0, npad - n), (0, bp - b)))
    payload, scale = _factor.factor_syrk_wire(
        x, _q.FORMATS[fmt], fmt_max=_q.FMT_MAX[fmt],
        pow2=(scale_mode == "pow2"), bk=bkk, interpret=interpret)
    i, j = np.tril_indices(b)
    return payload[:b, :b][i, j], scale[0, 0]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def kfac_block_precond(binv: jax.Array, w: jax.Array, *, bm: int = 256,
                       bn: int = 256, bk: int = 256,
                       interpret: bool | None = None) -> jax.Array:
    """Blocked preconditioner application U[k] = Binv[k] @ W[k]."""
    interpret = _interpret(interpret)
    nb, b, _ = binv.shape
    m = w.shape[-1]
    bm_, bn_, bk_ = min(bm, b), min(bn, m), min(bk, b)
    # pad b to a multiple of BOTH tile sizes (their lcm): padding to
    # max(bm_, bk_) misaligns the grid when bm_ != bk_ and the smaller tile
    # doesn't divide the larger (the last tile then reads past the array)
    tile = math.lcm(bm_, bk_)
    bp = -(-b // tile) * tile
    mp = -(-m // bn_) * bn_
    if bp != b or mp != m:
        binv = jnp.pad(binv, ((0, 0), (0, bp - b), (0, bp - b)))
        w = jnp.pad(w, ((0, 0), (0, bp - b), (0, mp - m)))
    out = _precond.block_precond(binv, w, bm=bm_, bn=bn_, bk=bk_,
                                 interpret=interpret)
    return out[:, :b, :m]


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk",
                                             "interpret"))
def swa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window: int = 0, bq: int = 256, bk: int = 256,
                  interpret: bool | None = None) -> jax.Array:
    """Causal sliding-window flash attention; (BH, S, hd) layout."""
    interpret = _interpret(interpret)
    bh, s, hd = q.shape
    bq_, bk_ = min(bq, s), min(bk, s)
    bt = math.lcm(bq_, bk_)          # same grid-alignment rule as above
    sp = -(-s // bt) * bt
    if sp != s:
        pad = ((0, 0), (0, sp - s), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    out = _swa.swa_flash(q, k, v, window=window, bq=bq_, bk=bk_,
                         interpret=interpret)
    return out[:, :s, :]


def _pad_seq(s: int, bq: int, bk: int) -> int:
    """Padded sequence length: a multiple of BOTH tile sizes (their lcm)."""
    tile = math.lcm(bq, bk)
    return -(-s // tile) * tile


# largest factor block the Newton-Schulz kernel keeps VMEM-resident: one
# block costs ~3 * b^2 * 4 bytes (M, X, step temporary); 1024 -> ~12.6 MB
# against the ~16 MB/core budget. Dispatch routes bigger blocks to the
# two-level tiled variant (ns_inverse_tiled) below, which keeps the
# operands HBM-resident and streams (bt, bt) VMEM tiles per matmul.
NS_KERNEL_MAX_DIM = 1024


@functools.partial(jax.jit, static_argnames=("iters", "tol", "interpret"))
def ns_inverse(m: jax.Array, *, iters: int, tol: float,
               interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Blocked Newton-Schulz inverse of already-damped symmetric blocks.

    m: (g, b, b) f32 (``M = F + lambda I``, symmetrized by the caller) ->
    (x (g, b, b) f32 ~= M^-1, res (g,) f32 relative fixed-point residual
    ``||I - M x||_F / ||I||_F`` of the returned iterate).

    Blocks pad to the 128-lane boundary as ``[[M, 0], [0, dpad*I]]`` with
    ``dpad = ||M||_inf`` per block — an eigenvalue the iteration already
    has to cover (lambda_max <= ||M||_inf), so padding never slows the
    contraction the way a fixed pad value (e.g. 1) would for tiny- or
    huge-scaled factors. The padded rows/cols are sliced off below, and
    the kernel's residual (normalized by the PADDED ||I||_F) is rescaled
    back to the caller's b so the fallback decision matches the unpadded
    reference iteration instead of being sqrt(bp/b) looser.
    """
    interpret = _interpret(interpret)
    if m.shape[-1] > NS_KERNEL_MAX_DIM:
        raise ValueError(f"ns_inverse holds whole blocks in VMEM; "
                         f"b={m.shape[-1]} exceeds NS_KERNEL_MAX_DIM="
                         f"{NS_KERNEL_MAX_DIM} (route to the ref iteration)")
    g, b, _ = m.shape
    bp = -(-b // 128) * 128
    if bp != b:
        dpad = jnp.maximum(jnp.max(jnp.sum(jnp.abs(m), axis=-1), axis=-1),
                           jnp.float32(1e-30))           # (g,): ||M||_inf
        m = jnp.pad(m, ((0, 0), (0, bp - b), (0, bp - b)))
        pad_diag = jnp.where(jnp.arange(bp) >= b, 1.0, 0.0)
        m = m + dpad[:, None, None] * jnp.diag(pad_diag)
    # the kernel normalizes by the PADDED 1/||I_bp||_F: hand it the
    # equivalently-rescaled freeze threshold and scale the residual back,
    # so both the early exit and the fallback decision match the unpadded
    # reference iteration exactly (the padded identity's own residual
    # rides along, erring toward the eigh fallback)
    scale = math.sqrt(bp / b)
    x, res = _ns.ns_inverse_blocks(m, iters=iters, tol=tol / scale,
                                   interpret=interpret)
    return x[:, :b, :b], res[:, 0, 0] * scale


def _ns_tile(bp: int) -> int:
    """Largest MXU-aligned tile that divides the padded block dim (so the
    tile grid needs no edge masking); bp is always a multiple of 128."""
    for bt in (512, 384, 256, 128):
        if bp % bt == 0:
            return bt
    return 128


@functools.partial(jax.jit, static_argnames=("iters", "tol", "interpret"))
def ns_inverse_tiled(m: jax.Array, *, iters: int, tol: float,
                     interpret: bool | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Two-level tiled Newton-Schulz inverse for blocks past
    :data:`NS_KERNEL_MAX_DIM` — same contract as :func:`ns_inverse`
    (already-damped symmetric (g, b, b) blocks in, (inverse, per-block
    residual) out) with no VMEM cap on b.

    Level 1 (here): the iteration's step sequencing — a ``fori_loop``
    whose body calls one residual kernel (``R = I - M X`` + ||R||_F^2)
    and one update kernel (``X' = X + X R``) per trip, freezing converged
    blocks exactly like the resident kernel does. Level 2 (the kernels):
    each matmul walks a (bt, bt) VMEM tile grid over the HBM-resident
    operands. Padding/rescale rules are identical to :func:`ns_inverse`
    (``dpad = ||M||_inf`` identity padding, residual rescaled to the
    unpadded ||I_b||_F), except blocks pad to the tile size so the grid
    needs no edge masking.
    """
    interpret = _interpret(interpret)
    g, b, _ = m.shape
    bt = _ns_tile(-(-b // 128) * 128)
    bp = -(-b // bt) * bt
    if bp != b:
        dpad = jnp.maximum(jnp.max(jnp.sum(jnp.abs(m), axis=-1), axis=-1),
                           jnp.float32(1e-30))           # (g,): ||M||_inf
        m = jnp.pad(m, ((0, 0), (0, bp - b), (0, bp - b)))
        pad_diag = jnp.where(jnp.arange(bp) >= b, 1.0, 0.0)
        m = m + dpad[:, None, None] * jnp.diag(pad_diag)
    scale = math.sqrt(bp / b)
    tol_p = tol / scale
    rnorm = 1.0 / math.sqrt(bp)
    am = jnp.abs(m)
    n1 = jnp.max(jnp.sum(am, axis=-2), axis=-1)          # (g,)
    ninf = jnp.max(jnp.sum(am, axis=-1), axis=-1)
    x0 = m * (1.0 / (n1 * ninf))[:, None, None]

    def resid(x):
        r, ss = _ns.ns_tiled_residual(m, x, bt=bt, interpret=interpret)
        return r, jnp.sqrt(ss[:, 0, 0]) * rnorm

    def body(_, x):
        r, res = resid(x)
        xn = _ns.ns_tiled_update(x, r, bt=bt, interpret=interpret)
        return jnp.where((res > tol_p)[:, None, None], xn, x)

    x = jax.lax.fori_loop(0, iters, body, x0)
    _, res = resid(x)                # residual of the RETURNED iterate
    return x[:, :b, :b], res * scale


# VMEM budget for one quantization tile, in ELEMENTS of the packed row
# axis: a tile touches ~5 bytes/element (f32 in + fp8 out), so 2^21
# elements ≈ 10.5 MB — one whole row of the largest factor block the
# framework produces (max_dim=2048 -> t = b(b+1)/2 ≈ 2.1M) still fits the
# ~16 MB/core VMEM with bg=1, and smaller rows batch up to bg per tile.
_QUANT_TILE_ELEMS = 1 << 21


def _rows_per_tile(bg: int, g: int, t: int) -> int:
    return max(1, min(bg, g, _QUANT_TILE_ELEMS // max(t, 1)))


@functools.partial(jax.jit, static_argnames=("fmt", "scale_mode", "bg",
                                             "interpret"))
def fp8_quant_rows(x: jax.Array, *, fmt: str = "e4m3",
                   scale_mode: str = "fp32", bg: int = 8,
                   interpret: bool | None = None):
    """Per-row fp8 quantization: (..., t) -> (payload fp8 (..., t),
    scale f32 (...,)). Rows are whole quantization tiles (one scale each);
    for sym-packed factors a row is one block's packed lower triangle."""
    from repro.quant import quant as _q
    interpret = _interpret(interpret)
    lead, t = x.shape[:-1], x.shape[-1]
    flat = x.reshape((-1, t))
    g = flat.shape[0]
    bg_ = _rows_per_tile(bg, g, t)
    gp = -(-g // bg_) * bg_
    tp = -(-t // 128) * 128          # lane alignment; zeros are amax-neutral
    if gp != g or tp != t:
        flat = jnp.pad(flat, ((0, gp - g), (0, tp - t)))
    payload, scale = _quant.quant_rows(
        flat, _q.FORMATS[fmt], fmt_max=_q.FMT_MAX[fmt],
        pow2=(scale_mode == "pow2"), bg=bg_, interpret=interpret)
    return (payload[:g, :t].reshape(lead + (t,)),
            scale[:g, 0].reshape(lead))


@functools.partial(jax.jit, static_argnames=("bg", "interpret"))
def fp8_dequant_rows(payload: jax.Array, scale: jax.Array, *, bg: int = 8,
                     interpret: bool | None = None) -> jax.Array:
    """Inverse of :func:`fp8_quant_rows`: fp8 payload + per-row scale -> f32."""
    interpret = _interpret(interpret)
    lead, t = payload.shape[:-1], payload.shape[-1]
    flat = payload.reshape((-1, t))
    g = flat.shape[0]
    bg_ = _rows_per_tile(bg, g, t)
    gp = -(-g // bg_) * bg_
    tp = -(-t // 128) * 128
    if gp != g or tp != t:
        flat = jnp.pad(flat, ((0, gp - g), (0, tp - t)))
    s = jnp.pad(scale.reshape((-1, 1)).astype(jnp.float32),
                ((0, gp - g), (0, 0)))
    out = _quant.dequant_rows(flat, s, bg=bg_, interpret=interpret)
    return out[:g, :t].reshape(lead + (t,))


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def swa_decode(q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array,
               *, window: int = 0, k_scale: jax.Array | None = None,
               v_scale: jax.Array | None = None, bk: int = 128,
               interpret: bool | None = None) -> jax.Array:
    """Single-query flash decode over a KV cache (serving hot path).

    q (N, G, hd) — one query token per sequence in the GQA kernel layout
    (N = B * KV heads, G query heads per KV head); k/v (N, C, hd) cache
    payload (f32/bf16 dense or fp8 with ``k_scale``/``v_scale`` (N, C) f32
    per-row dequant scales); pos (N,) i32 absolute query positions.
    ``window > 0`` means C == window and the cache is a RING buffer (token
    at position p lives in slot p % window); ``window == 0`` attends the
    dense cache full-causally. Returns (N, G, hd) f32."""
    interpret = _interpret(interpret)
    n, g, hd = q.shape
    c = k.shape[1]
    if window and c != window:
        raise ValueError(f"ring decode needs k.shape[1] == window; got "
                         f"{c} vs {window}")
    if k_scale is None:
        k_scale = jnp.ones((n, c), jnp.float32)
    if v_scale is None:
        v_scale = jnp.ones((n, c), jnp.float32)
    bk_ = min(bk, -(-c // 128) * 128)
    cp = -(-c // bk_) * bk_
    if cp != c:
        # zero-fill padding: masked off in-kernel via slot < C
        k = jnp.pad(k, ((0, 0), (0, cp - c), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, cp - c), (0, 0)))
        k_scale = jnp.pad(k_scale, ((0, 0), (0, cp - c)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, cp - c)))
    # k/v enter the kernel in their STORED dtype (fp8 payloads included) —
    # the dequant (cast + scale multiply) happens on read in VMEM, so the
    # f32 cache never exists in HBM
    return _swa.swa_flash_decode(
        q, k, v, k_scale.astype(jnp.float32).reshape(n, 1, cp),
        v_scale.astype(jnp.float32).reshape(n, 1, cp),
        pos.astype(jnp.int32).reshape(n), window=window, cache_len=c,
        bk=bk_, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk",
                                             "interpret"))
def swa_attention_fwd_res(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          window: int = 0, bq: int = 256, bk: int = 256,
                          interpret: bool | None = None):
    """Residual-saving training forward, GQA layout: q (BKV, G, S, hd),
    k/v (BKV, S, hd) — KV unexpanded, one kernel batch entry per KV head.
    Returns (out (BKV, G, S, hd), lse (BKV, G, S) f32)."""
    interpret = _interpret(interpret)
    bkv, g, s, hd = q.shape
    bq_, bk_ = min(bq, s), min(bk, s)
    sp = _pad_seq(s, bq_, bk_)
    if sp != s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
        pad = ((0, 0), (0, sp - s), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    out, lse = _swa.swa_flash_fwd(q, k, v, window=window, bq=bq_, bk=bk_,
                                  interpret=interpret)
    return out[:, :, :s], lse[:, :, :s]


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk",
                                             "interpret"))
def swa_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                      o: jax.Array, lse: jax.Array, do: jax.Array, *,
                      window: int = 0, bq: int = 256, bk: int = 256,
                      interpret: bool | None = None):
    """Fused backward from the saved (o, lse) residuals — no forward
    recompute. Layouts as in :func:`swa_attention_fwd_res`; returns
    (dq (BKV, G, S, hd), dk (BKV, S, hd), dv (BKV, S, hd)), all f32 with
    dk/dv accumulated per KV head across the query-head group."""
    interpret = _interpret(interpret)
    bkv, g, s, hd = q.shape
    bq_, bk_ = min(bq, s), min(bk, s)
    # D_i = rowsum(do * o) once on the XLA side (FlashAttention-2 style):
    # o then never enters the kernels' input streams, and the dk/dv sweep
    # doesn't re-derive it per visited tile
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    sp = _pad_seq(s, bq_, bk_)
    if sp != s:
        qpad = ((0, 0), (0, 0), (0, sp - s), (0, 0))
        kpad = ((0, 0), (0, sp - s), (0, 0))
        # NOTE the in-kernel k_pos < seq_len mask is vacuous here (the
        # kernels see the padded length): padded KEY columns are hidden
        # from real query rows by the causal mask alone (their positions
        # are > every real q_pos). Padded QUERY rows do see real keys with
        # p = exp(0 - 0) = 1, but contribute nothing because the zero-
        # padded do/delta force ds = 0 and p^T @ do = 0 — the zero padding
        # is load-bearing. The garbage dq rows are sliced off below.
        q, do = jnp.pad(q, qpad), jnp.pad(do, qpad)
        k, v = jnp.pad(k, kpad), jnp.pad(v, kpad)
        rpad = ((0, 0), (0, 0), (0, sp - s))
        lse, delta = jnp.pad(lse, rpad), jnp.pad(delta, rpad)
    dq = _swa.swa_flash_bwd_dq(q, k, v, lse, delta, do, window=window,
                               bq=bq_, bk=bk_, interpret=interpret)
    dk, dv = _swa.swa_flash_bwd_dkdv(q, k, v, lse, delta, do, window=window,
                                     bq=bq_, bk=bk_, interpret=interpret)
    return dq[:, :, :s], dk[:, :s], dv[:, :s]
