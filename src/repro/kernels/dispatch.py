"""Kernel backend dispatch: one routing layer between the SP-NGD hot paths
and their implementations.

The paper's overhead argument (§5.2) rests on two hot spots — statistics
construction ``A = X^T X`` and preconditioning ``A^-1 dW G^-1`` — running at
hardware speed. This module owns the decision of *which* implementation runs:

* ``"ref"``    — the pure-``jnp`` einsum path (seed behaviour, bit-for-bit).
* ``"pallas"`` — the MXU-aligned Pallas kernels in this package. On CPU the
  kernels execute with ``interpret=True`` (numerics-exact emulation); on TPU
  they compile to real Mosaic kernels.
* ``"auto"``   — resolve per op and per shape: Pallas on TPU when the dims
  that predict the kernel's win are at least :data:`MIN_PALLAS_DIM`, ref
  everywhere else. Each op passes its own relevant dims to :func:`resolve`
  (matmul-shaped ops gate on their contraction dims — tiny dims cannot fill
  an MXU tile and lose to plain XLA; attention gates on sequence length
  only, being bandwidth- not MXU-bound). On CPU auto always resolves to
  ref, so it is semantics-preserving for tests. Nor does auto pick a kernel
  where the traced region spans several devices under the compiler's
  partitioning (a ``jit`` over a multi-device mesh, a ``shard_map`` that
  leaves a mesh axis automatic): Mosaic kernels are not partitioned
  automatically, so there only ref can lower.

Every public op here accepts the *blocked* factor layout used by the rest of
the framework — arrays of shape ``(lead..., nb, b, b)`` with arbitrary
leading layer/expert axes — and shims it down to the rank-2/rank-3 layouts
the kernels accept (``vmap`` for the SYRK kernel, a leading-axis collapse for
the block preconditioner, which treats its leading dim as an independent
grid axis anyway). f32 accumulation semantics are identical across backends:
inputs may be bf16, accumulation and outputs are f32.

Adding a new kernel
-------------------
Register an implementation for an existing op (or a new op name) with
:func:`register`::

    from repro.kernels import dispatch
    dispatch.register("factor_sum", "pallas", my_faster_impl)

An op resolved to a backend with no registered implementation is an error,
never a silent fall back to ``"ref"``; ``ref`` implementations are
mandatory. Where an op's Pallas kernel does not cover a case (a direct
factorization, a block past a kernel's VMEM cap), the op's resolution says
``"ref"`` itself, so what :func:`resolutions` reports is what ran.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

BACKENDS = ("ref", "pallas", "auto")

# auto: smallest contraction dim worth handing to the MXU kernels. One MXU
# tile is 128x128; below that the kernel's padding outweighs its win.
MIN_PALLAS_DIM = 128

_TABLE: dict[str, dict[str, Callable]] = {}

# (op, backend) -> name of the implementation traced for it, recorded by
# _call; read through resolutions()
_RAN: dict[tuple[str, str], str] = {}


def register(op: str, backend: str, fn: Callable) -> None:
    """Register ``fn`` as the ``backend`` implementation of ``op``."""
    _TABLE.setdefault(op, {})[backend] = fn


def lookup(op: str, backend: str) -> Callable:
    impls = _TABLE.get(op)
    if impls is None:
        raise KeyError(f"unregistered kernel op {op!r}; registered ops: "
                       f"{sorted(_TABLE)}")
    if backend not in impls:
        raise KeyError(f"kernel op {op!r} has no {backend!r} implementation; "
                       f"registered: {sorted(impls)}")
    return impls[backend]


def resolutions() -> dict[str, dict[str, str]]:
    """Every op traced so far in this process: ``{op: {backend: impl}}``,
    the backend its resolution picked and the implementation it called."""
    out: dict[str, dict[str, str]] = {}
    for (op, which), name in sorted(_RAN.items()):
        out.setdefault(op, {})[which] = name
    return out


def _call(op: str, which: str, *args, **kwargs):
    """Invoke the resolved implementation under a stable trace-viewer scope
    (``repro.kernels.<op>[<backend>]``, :func:`repro.obs.tracing
    .kernel_scope`) so a ref-vs-pallas A/B of the same op lines up by name
    in a captured profile. Kept as a separate step from :func:`lookup` so
    tests that spy on lookup still observe every dispatch."""
    from repro.obs.tracing import kernel_scope
    fn = lookup(op, which)
    _RAN[(op, which)] = fn.__name__
    with kernel_scope(op, which):
        return fn(*args, **kwargs)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel_placeable() -> bool:
    """Whether a compiled Mosaic kernel can lower in the region being
    traced: one the compiler does not partition. That is a region with no
    ambient mesh (a single-device ``jit``), a ``shard_map`` manual over every
    mesh axis, or a ``jit`` over a one-device mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return True
    if mesh.manual_axes:
        return set(mesh.manual_axes) == set(mesh.axis_names)
    return mesh.size == 1


def resolve(backend: str | None, *dims: int) -> str:
    """Map a config knob to a concrete backend for one op instance.

    ``dims`` are the shape quantities that must be MXU-worthy for the Pallas
    path to pay off under ``"auto"`` (contraction dims, sequence length...).
    """
    backend = backend or "auto"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
    if backend != "auto":
        return backend
    if not dims:
        # all(()) is True: a dims-less call would resolve to "pallas" on TPU
        # unconditionally, sidestepping the MXU-worthiness gate
        raise ValueError('resolve("auto") needs at least one shape dim '
                         "(the quantities that predict the Pallas win)")
    if (_on_tpu() and all(d >= MIN_PALLAS_DIM for d in dims)
            and _kernel_placeable()):
        return "pallas"
    return "ref"


# ---------------------------------------------------------------------------
# factor_sum: blocked A = sum_t x_t x_t^T     (..., n, d) -> (..., nb, b, b)
# ---------------------------------------------------------------------------

def _factor_sum_ref(x: jax.Array, max_dim: int) -> jax.Array:
    from repro.core import kfac
    d = x.shape[-1]
    xb = kfac.block_reshape(x, d, max_dim, axis=-1)
    return jnp.einsum("...nka,...nkb->...kab", xb, xb,
                      preferred_element_type=jnp.float32)


def _factor_sum_pallas(x: jax.Array, max_dim: int) -> jax.Array:
    from repro.core import kfac
    from repro.kernels import ops
    d = x.shape[-1]
    xb = kfac.block_reshape(x, d, max_dim, axis=-1)   # (..., n, nb, b)
    xb = jnp.moveaxis(xb, -2, -3)                     # (..., nb, n, b)
    lead = xb.shape[:-2]
    n, b = xb.shape[-2:]
    flat = xb.reshape((-1, n, b))
    out = jax.vmap(lambda m: ops.kfac_factor(m))(flat)
    return out.reshape(lead + (b, b))


def factor_sum(x: jax.Array, max_dim: int, *,
               backend: str | None = None) -> jax.Array:
    """Blocked raw factor sum; the §5.2 statistics-construction hot spot."""
    from repro.core import kfac
    b = kfac.block_size(x.shape[-1], max_dim)
    which = resolve(backend, b, x.shape[-2])
    return _call("factor_sum", which, x, max_dim)


# ---------------------------------------------------------------------------
# factor_sum_wire: fused factor sum + wire-format epilogue
#   (..., n, d) -> (payload fp8 (..., nb, t=b(b+1)/2), scale f32 (..., nb))
# The Stage-3 "fused" strategy's capture op: the pallas path emits the fp8
# wire tile straight out of the SYRK kernel's VMEM accumulator (the raw f32
# factor sum never reaches HBM); the ref path is the unfused composition
# factor_sum -> sym_pack -> quantize_rows, numerically equivalent up to f32
# accumulation order.
# ---------------------------------------------------------------------------

def _factor_sum_wire_ref(x, max_dim: int, fmt: str, scale_mode: str):
    from repro.core import kfac
    from repro.quant import quant
    f = _factor_sum_ref(x, max_dim)
    return quant.quantize_rows(kfac.sym_pack(f), fmt, scale_mode)


def _factor_sum_wire_pallas(x, max_dim: int, fmt: str, scale_mode: str):
    from repro.core import kfac
    from repro.kernels import ops
    d = x.shape[-1]
    b = kfac.block_size(d, max_dim)
    xb = kfac.block_reshape(x, d, max_dim, axis=-1)   # (..., n, nb, b)
    xb = jnp.moveaxis(xb, -2, -3)                     # (..., nb, n, b)
    lead = xb.shape[:-2]
    n = xb.shape[-2]
    flat = xb.reshape((-1, n, b))
    payload, scale = jax.vmap(
        lambda m: ops.kfac_factor_wire(m, fmt=fmt, scale_mode=scale_mode)
    )(flat)
    t = b * (b + 1) // 2
    return payload.reshape(lead + (t,)), scale.reshape(lead)


def factor_sum_wire(x: jax.Array, max_dim: int, *, fmt: str = "e4m3",
                    scale_mode: str = "fp32",
                    backend: str | None = None):
    """Fused statistics construction: blocked factor sum emitted directly
    in the sym-packed fp8 wire format (payload, per-block scale)."""
    from repro.core import kfac
    from repro.kernels import ops
    b = kfac.block_size(x.shape[-1], max_dim)
    which = resolve(backend, b, x.shape[-2])
    if b > ops.FACTOR_WIRE_MAX_DIM:
        # the fused kernel keeps the whole block in VMEM: bigger blocks
        # take the unfused XLA composition
        which = "ref"
    return _call("factor_sum_wire", which, x, max_dim, fmt, scale_mode)


# ---------------------------------------------------------------------------
# block_precond_left:  U[k] = Binv[k] @ W[k]
#   binv (..., nb, b, b), w (..., nb, b, m) -> (..., nb, b, m) f32
# ---------------------------------------------------------------------------

def _precond_left_ref(binv: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("...kab,...kbo->...kao", binv, w)


def _collapse_lead(binv, w):
    """Fold leading layer/expert axes into the kernel's block-grid axis —
    every (b x b) @ (b x m) product is independent, so (lead..., nb) can be
    flattened into one batch dim the kernel iterates as grid dim 0."""
    lead = binv.shape[:-2]
    b = binv.shape[-1]
    m = w.shape[-1]
    return binv.reshape((-1, b, b)), w.reshape((-1, b, m)), lead


def _precond_left_pallas(binv: jax.Array, w: jax.Array) -> jax.Array:
    from repro.kernels import ops
    bf, wf, lead = _collapse_lead(binv, w)
    out = ops.kfac_block_precond(bf, wf)
    return out.reshape(lead + out.shape[-2:])


def block_precond_left(binv: jax.Array, w: jax.Array, *,
                       backend: str | None = None) -> jax.Array:
    """Apply blocked inverse from the left (the ``A^-1 dW`` half)."""
    which = resolve(backend, binv.shape[-1], w.shape[-1])
    return _call("block_precond_left", which, binv, w)


# ---------------------------------------------------------------------------
# block_precond_right:  U[k] = W[k] @ Binv[k]
#   w (..., m, nb, b), binv (..., nb, b, b) -> (..., m, nb, b) f32
# ---------------------------------------------------------------------------

def _precond_right_ref(w: jax.Array, binv: jax.Array) -> jax.Array:
    return jnp.einsum("...iko,...kop->...ikp", w, binv)


def _precond_right_pallas(w: jax.Array, binv: jax.Array) -> jax.Array:
    # W @ Binv == (Binv^T @ W^T)^T per block: reuse the left kernel.
    wt = jnp.swapaxes(jnp.moveaxis(w, -3, -2), -1, -2)   # (..., nb, b, m)
    out = _precond_left_pallas(jnp.swapaxes(binv, -1, -2), wt)
    return jnp.moveaxis(jnp.swapaxes(out, -1, -2), -2, -3)


def block_precond_right(w: jax.Array, binv: jax.Array, *,
                        backend: str | None = None) -> jax.Array:
    """Apply blocked inverse from the right (the ``dW G^-1`` half)."""
    which = resolve(backend, binv.shape[-1], w.shape[-3])
    return _call("block_precond_right", which, w, binv)


# ---------------------------------------------------------------------------
# damped_inverse: (F + damping I)^-1 per block — the Stage-4 inversion.
#
# method "eigh" / "cholesky" are direct factorizations: not matmul-shaped,
# so they have no kernel and their resolution is always ref. method
# "newton_schulz" is matmul-only: ref = the jnp blocked iteration
# (kfac.newton_schulz_inverse), pallas = the VMEM-resident kernel
# (kernels/newton_schulz.py) — both share one failure contract: any block
# whose relative residual ||I - M X||_F / ||I||_F is still above ns_tol
# after ns_iters capped iterations is re-solved with the eigh path (and the
# event logged), so an ill-conditioned block can never silently ship a
# wrong inverse. Impl signature: fn(f, damping, method, ns_iters, ns_tol)
# -> (inv, res) with res (...,) per-block residual (zeros for the direct
# methods).
# ---------------------------------------------------------------------------

# the canonical iteration cap / residual tolerance live next to the
# algorithm (kfac is import-safe here: its own dispatch imports are lazy)
from repro.core.kfac import NS_ITERS, NS_TOL  # noqa: E402


def _ns_eigh_fallback(f, damping, x, res, ns_tol):
    """Replace blocks the iteration cannot be trusted on with the eigh
    inverse. Two triggers, both folded into the returned residual:

    * res > ns_tol — the capped iteration failed to contract;
    * min diag(X) <= 0 — an SPD inverse must have a strictly positive
      diagonal, so a non-positive entry means the damped factor was
      INDEFINITE (bf16-accumulation noise can push small eigenvalues
      negative). Newton-Schulz then converges to the true inverse of the
      indefinite matrix, but the framework's contract is eigh's clamped
      semantics (negative eigenvalues -> 0 before damping); those blocks
      must re-solve. Their residual is forced to +inf so callers reading
      ``ns_converged`` see them as fallbacks.

    The cond keeps the eigh work off the hot path when every block is
    trusted. Returns (x, res)."""
    from repro.core import kfac
    diag = jnp.diagonal(x, axis1=-2, axis2=-1)
    res = jnp.where(jnp.min(diag, axis=-1) > 0, res, jnp.inf)
    bad = res > ns_tol

    def fb(x):
        jax.debug.print("damped_inverse[newton_schulz]: {n} block(s) failed "
                        "to contract below tol={t} (or lost SPD); re-solved "
                        "via eigh", n=jnp.sum(bad), t=ns_tol)
        return jnp.where(bad[..., None, None],
                         kfac.damped_inverse(f, damping), x)

    return jax.lax.cond(jnp.any(bad), fb, lambda x: x, x), res


def _damped_inverse_ref(f, damping, method: str, ns_iters: int,
                        ns_tol: float):
    from repro.core import kfac
    if method == "newton_schulz":
        x, res = kfac.newton_schulz_inverse(f, damping, iters=ns_iters,
                                            tol=ns_tol)
        return _ns_eigh_fallback(f, damping, x, res, ns_tol)
    if method not in ("eigh", "cholesky"):
        raise ValueError(f"unknown inverse method {method!r}; expected "
                         "'eigh' | 'cholesky' | 'newton_schulz'")
    inv = kfac.damped_inverse if method == "eigh" else kfac.cholesky_inverse
    return inv(f, damping), jnp.zeros(f.shape[:-2], jnp.float32)


def _damped_inverse_pallas(f, damping, method: str, ns_iters: int,
                           ns_tol: float):
    from repro.kernels import ops
    b = f.shape[-1]
    f32 = f.astype(jnp.float32)
    m = 0.5 * (f32 + jnp.swapaxes(f32, -1, -2))
    d = jnp.broadcast_to(jnp.asarray(damping, jnp.float32), f.shape[:-2])
    m = m + d[..., None, None] * jnp.eye(b, dtype=jnp.float32)
    lead = m.shape[:-2]
    # over-VMEM blocks run the two-level tiled kernel (HBM-resident
    # operands, VMEM tile loop per matmul) instead of degrading to ref
    kern = (ops.ns_inverse_tiled if b > ops.NS_KERNEL_MAX_DIM
            else ops.ns_inverse)
    x, res = kern(m.reshape((-1, b, b)), iters=ns_iters, tol=ns_tol)
    x = x.reshape(lead + (b, b))
    res = res.reshape(lead)
    return _ns_eigh_fallback(f, damping, x, res, ns_tol)


def damped_inverse(f: jax.Array, damping, *, method: str = "eigh",
                   ns_iters: int = NS_ITERS, ns_tol: float = NS_TOL,
                   backend: str | None = None, return_info: bool = False):
    """Stage-4 blocked damped inverse. With ``return_info=True`` also
    returns ``{"ns_res", "ns_converged"}`` per block — the test harness's
    (and any monitoring hook's) view of which blocks took the eigh
    fallback; for the direct methods the residual is identically zero."""
    which = resolve(backend, f.shape[-1])
    if method != "newton_schulz":
        which = "ref"               # direct factorizations have no kernel
    inv, res = _call("damped_inverse", which, f, damping, method,
                      ns_iters, ns_tol)
    if return_info:
        return inv, {"ns_res": res, "ns_converged": res <= ns_tol}
    return inv


# ---------------------------------------------------------------------------
# fp8_pack / fp8_unpack: symmetric blocked factor <-> sym-packed fp8 payload
#   f (..., b, b) -> (payload fp8 (..., t=b(b+1)/2), scale f32 (...,))
# One scale per block: the quantization tile IS the §5.2 communication tile,
# so the packed payload doubles as history storage and reduce-scatter message.
# ---------------------------------------------------------------------------

def _fp8_pack_ref(f, fmt: str, scale_mode: str):
    from repro.core import kfac
    from repro.quant import quant
    return quant.quantize_rows(kfac.sym_pack(f.astype(jnp.float32)),
                               fmt, scale_mode)


def _fp8_pack_pallas(f, fmt: str, scale_mode: str):
    # the tril gather is pure byte movement and stays on the XLA side (same
    # split as delta in ops.swa_attention_bwd); the kernel owns the numeric
    # pass (amax reduce + scale + clip + cast, one VMEM-resident sweep)
    from repro.core import kfac
    from repro.kernels import ops
    return ops.fp8_quant_rows(kfac.sym_pack(f.astype(jnp.float32)),
                              fmt=fmt, scale_mode=scale_mode)


def fp8_pack(f: jax.Array, *, fmt: str = "e4m3", scale_mode: str = "fp32",
             backend: str | None = None):
    """Quantize + sym-pack a symmetric blocked factor; §4.3 history and
    §5.2 payload compression on top of triangular packing."""
    which = resolve(backend, f.shape[-1])
    return _call("fp8_pack", which, f, fmt, scale_mode)


def _fp8_unpack_ref(payload, scale, b: int):
    from repro.core import kfac
    from repro.quant import quant
    return kfac.sym_unpack(quant.dequantize_rows(payload, scale), b)


def _fp8_unpack_pallas(payload, scale, b: int):
    from repro.core import kfac
    from repro.kernels import ops
    return kfac.sym_unpack(ops.fp8_dequant_rows(payload, scale), b)


def fp8_unpack(payload: jax.Array, scale: jax.Array, b: int, *,
               backend: str | None = None) -> jax.Array:
    """Dequantize-on-read: packed fp8 payload -> dense symmetric f32
    (..., b, b) blocks."""
    which = resolve(backend, b)
    return _call("fp8_unpack", which, payload, scale, b)


# ---------------------------------------------------------------------------
# ring_hop_pack / ring_hop_unpack: per-hop fp8 wire codec for the Stage-3
# ring reduce-scatter (repro.comm). Unlike fp8_pack/fp8_unpack these take
# rows that are ALREADY sym-packed (the hop payload is a chunk of packed
# triangles): (..., t) f32 <-> (payload fp8 (..., t), scale f32 (...,)),
# one scale per row — the quantization tile stays the §5.2 block tile, so
# the wire format matches the fp8 storage format bit for bit.
# ---------------------------------------------------------------------------

def _ring_hop_pack_ref(rows, fmt: str, scale_mode: str):
    from repro.quant import quant
    return quant.quantize_rows(rows, fmt, scale_mode)


def _ring_hop_pack_pallas(rows, fmt: str, scale_mode: str):
    from repro.kernels import ops
    return ops.fp8_quant_rows(rows, fmt=fmt, scale_mode=scale_mode)


def ring_hop_pack(rows: jax.Array, *, fmt: str = "e4m3",
                  scale_mode: str = "fp32", backend: str | None = None):
    """Quantize one ring hop's partial-sum rows to the fp8 wire format."""
    which = resolve(backend, rows.shape[-1])
    return _call("ring_hop_pack", which, rows, fmt, scale_mode)


def _ring_hop_unpack_ref(payload, scale):
    from repro.quant import quant
    return quant.dequantize_rows(payload, scale)


def _ring_hop_unpack_pallas(payload, scale):
    from repro.kernels import ops
    return ops.fp8_dequant_rows(payload, scale)


def ring_hop_unpack(payload: jax.Array, scale: jax.Array, *,
                    backend: str | None = None) -> jax.Array:
    """Dequantize a received hop payload back to the f32 accumulator."""
    which = resolve(backend, payload.shape[-1])
    return _call("ring_hop_unpack", which, payload, scale)


# ---------------------------------------------------------------------------
# swa_attention: causal sliding-window attention, (BH, S, hd) layout
# ---------------------------------------------------------------------------

def _swa_ref(q, k, v, window: int):
    from repro.kernels import ref
    return ref.swa_attention_ref(q, k, v, window=window)


def _swa_pallas(q, k, v, window: int):
    from repro.kernels import ops
    return ops.swa_attention(q, k, v, window=window)


def swa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window: int = 0, backend: str | None = None) -> jax.Array:
    # auto gates on seq only: flash attention's win is avoiding the (S, S)
    # score materialization (bandwidth-bound), not MXU tile fill, and the
    # standard head dims (64) would never pass the generic contraction-dim
    # threshold
    which = resolve(backend, q.shape[-2])
    return _call("swa_attention", which, q, k, v, window)


# ---------------------------------------------------------------------------
# swa_decode: single-query flash decode over a KV cache (the serving hot
# path). q (N, G, hd) in the GQA kernel layout (N = B * KV heads, G query
# heads per KV head, same grouping as the training ops); k/v (N, C, hd) are
# the CACHE contents — ``window > 0`` means C == window and the cache is a
# ring buffer (token at position p lives in slot p % window), ``window ==
# 0`` means a dense cache attended full-causally. pos (N,) i32 holds each
# sequence's absolute query position (== tokens already cached; the query's
# own k/v must be written before the call). k_scale/v_scale (N, C) f32 are
# optional per-row dequant scales for fp8 payloads — the pallas path
# dequantizes ON READ in VMEM, so the f32 cache never exists in HBM.
# ---------------------------------------------------------------------------

def _swa_decode_ref(q, k, v, pos, window: int, k_scale, v_scale):
    from repro.kernels import ref
    return ref.swa_decode_ref(q, k, v, pos, window=window,
                              k_scale=k_scale, v_scale=v_scale)


def _swa_decode_pallas(q, k, v, pos, window: int, k_scale, v_scale):
    from repro.kernels import ops
    return ops.swa_decode(q, k, v, pos, window=window,
                          k_scale=k_scale, v_scale=v_scale)


def swa_decode(q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array, *,
               window: int = 0, k_scale: jax.Array | None = None,
               v_scale: jax.Array | None = None,
               backend: str | None = None) -> jax.Array:
    """Single-query decode attention; returns (N, G, hd) f32."""
    # auto gates on cache capacity (the swept dim) — like swa_attention the
    # win is bandwidth, not MXU fill, and hd=64 would never pass the gate
    which = resolve(backend, k.shape[-2])
    return _call("swa_decode", which, q, k, v, pos, window, k_scale, v_scale)


# ---------------------------------------------------------------------------
# swa_attention_fwd_res / swa_attention_bwd: the training path.
#
# GQA layout contract: q / o / do are (BKV, G, S, hd) — query heads grouped
# by the KV head they attend through (head h = c*G + r maps to KV head c,
# matching models.attention._repeat_kv) — and k / v / dk / dv are
# (BKV, S, hd), i.e. KV is handed to the kernels UNEXPANDED. The forward
# also returns the per-row logsumexp residual lse (BKV, G, S) f32; the
# backward consumes (o, lse) instead of recomputing attention, and dk/dv
# come back accumulated per KV head across the whole query-head group.
# ---------------------------------------------------------------------------

def _swa_fwd_res_ref(q, k, v, window: int):
    from repro.kernels import ref
    return ref.swa_attention_fwd_res_ref(q, k, v, window=window)


def _swa_fwd_res_pallas(q, k, v, window: int):
    from repro.kernels import ops
    return ops.swa_attention_fwd_res(q, k, v, window=window)


def _swa_bwd_ref(q, k, v, o, lse, do, window: int):
    # the ref backward IS the recompute path: jax.vjp of the ref forward
    # (o / lse are unused), so "pallas" still degrades gracefully op-by-op
    from repro.kernels import ref

    def fwd(q, k, v):
        return ref.swa_attention_fwd_res_ref(q, k, v, window=window)[0]

    _, vjp = jax.vjp(fwd, q, k, v)
    dq, dk, dv = vjp(do.astype(o.dtype))
    return (dq.astype(jnp.float32), dk.astype(jnp.float32),
            dv.astype(jnp.float32))


def _swa_bwd_pallas(q, k, v, o, lse, do, window: int):
    from repro.kernels import ops
    return ops.swa_attention_bwd(q, k, v, o, lse, do, window=window)


def swa_attention_fwd_res(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          window: int = 0, backend: str | None = None):
    """Training forward: returns (out, lse) in the GQA layout above."""
    which = resolve(backend, q.shape[-2])
    return _call("swa_attention_fwd_res", which, q, k, v, window)


def swa_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                      o: jax.Array, lse: jax.Array, do: jax.Array, *,
                      window: int = 0, backend: str | None = None):
    """Fused backward from residuals: returns (dq, dk, dv), all f32."""
    which = resolve(backend, q.shape[-2])
    return _call("swa_attention_bwd", which, q, k, v, o, lse, do, window)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

register("factor_sum", "ref", _factor_sum_ref)
register("factor_sum", "pallas", _factor_sum_pallas)
register("factor_sum_wire", "ref", _factor_sum_wire_ref)
register("factor_sum_wire", "pallas", _factor_sum_wire_pallas)
register("block_precond_left", "ref", _precond_left_ref)
register("block_precond_left", "pallas", _precond_left_pallas)
register("block_precond_right", "ref", _precond_right_ref)
register("block_precond_right", "pallas", _precond_right_pallas)
register("damped_inverse", "ref", _damped_inverse_ref)
register("damped_inverse", "pallas", _damped_inverse_pallas)
register("fp8_pack", "ref", _fp8_pack_ref)
register("fp8_pack", "pallas", _fp8_pack_pallas)
register("fp8_unpack", "ref", _fp8_unpack_ref)
register("fp8_unpack", "pallas", _fp8_unpack_pallas)
register("ring_hop_pack", "ref", _ring_hop_pack_ref)
register("ring_hop_pack", "pallas", _ring_hop_pack_pallas)
register("ring_hop_unpack", "ref", _ring_hop_unpack_ref)
register("ring_hop_unpack", "pallas", _ring_hop_unpack_pallas)
register("swa_attention", "ref", _swa_ref)
register("swa_attention", "pallas", _swa_pallas)
register("swa_decode", "ref", _swa_decode_ref)
register("swa_decode", "pallas", _swa_decode_pallas)
register("swa_attention_fwd_res", "ref", _swa_fwd_res_ref)
register("swa_attention_fwd_res", "pallas", _swa_fwd_res_pallas)
register("swa_attention_bwd", "ref", _swa_bwd_ref)
register("swa_attention_bwd", "pallas", _swa_bwd_pallas)
