"""Plain K-FAC algebra for the references: blocked Kronecker factors,
their damped inverses and the preconditioned update, in float32 at the
highest matmul precision. It follows the paper (Eq. 6, 10-12, 15-17) and
shares no code with the program under test.

A factor of dimension ``d`` is kept as diagonal blocks of at most
``max_dim``: ``nb = ceil(d / max_dim)`` blocks of ``b = ceil(d / nb)``,
with ``d`` zero-padded to ``nb * b``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _quantize(x, dt):
    if dt.itemsize == 1:
        top = float(jnp.finfo(dt).max)
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * (top / amax)).astype(dt).astype(F32) * (amax / top)
    return x.astype(dt).astype(F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _lower(x, dt_name: str):
    return _quantize(x, jnp.dtype(dt_name))


def _lower_fwd(x, dt_name):
    return _lower(x, dt_name), None


def _lower_bwd(dt_name, _, g):
    return (_quantize(g, jnp.dtype(dt_name)),)


_lower.defvjp(_lower_fwd, _lower_bwd)


def cast(x, op_dtype):
    """Matmul operands at ``op_dtype`` (None: float32 as is), in the
    forward pass and, for their cotangents, in the backward. An 8-bit float
    is scaled per tensor to its largest finite value first, as fp8 training
    does, so only its precision is lost, not its range."""
    x = x.astype(F32)
    return x if op_dtype is None else _lower(x, jnp.dtype(op_dtype).name)


def dot(spec: str, a, b, op_dtype=None):
    return jnp.einsum(spec, cast(a, op_dtype), cast(b, op_dtype),
                      precision=HI, preferred_element_type=F32)


def blocks(d: int, max_dim: int) -> tuple[int, int]:
    nb = max(1, -(-d // max_dim))
    return nb, -(-d // nb)


def split(x, d: int, max_dim: int, axis: int):
    """Axis ``axis`` of size ``d`` -> (nb, b), zero-padded."""
    nb, b = blocks(d, max_dim)
    axis %= x.ndim
    if nb * b != d:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, nb * b - d)
        x = jnp.pad(x, pad)
    return x.reshape(x.shape[:axis] + (nb, b) + x.shape[axis + 1:])


def merge(x, d: int, axis: int):
    axis %= x.ndim
    nb, b = x.shape[axis], x.shape[axis + 1]
    x = x.reshape(x.shape[:axis] + (nb * b,) + x.shape[axis + 2:])
    return jax.lax.slice_in_dim(x, 0, d, axis=axis)


def gram(x2d, max_dim: int, op_dtype=None):
    """Blocked sum over rows of x x^T: (n, d) -> (nb, b, b)."""
    xb = split(x2d, x2d.shape[-1], max_dim, axis=-1)     # (n, nb, b)
    return dot("nib,nic->ibc", xb, xb, op_dtype)


def trace_mean(f, d: int, full: bool):
    """Mean eigenvalue: trace over every block (or the sum of a diagonal
    factor) over the true dimension ``d``."""
    tr = jnp.trace(f, axis1=-2, axis2=-1).sum(-1) if full else f.sum(-1)
    return tr / d


def pi_split(a, a_full: bool, d_a: int, g, g_full: bool, d_g: int):
    """Martens-Grosse pi = sqrt(mean eig A / mean eig G)."""
    ea = jnp.maximum(trace_mean(a, d_a, a_full), 1e-12)
    eg = jnp.maximum(trace_mean(g, d_g, g_full), 1e-12)
    return jnp.sqrt(ea / eg)


def inverse(f, damp, full: bool):
    """(F + damp I)^-1 of a blocked symmetric factor, or elementwise
    1 / (max(F, 0) + damp) of a diagonal one. ``damp`` broadcasts over the
    factor's leading axes."""
    if not full:
        return 1.0 / (jnp.maximum(f, 0.0) + damp[..., None])
    b = f.shape[-1]
    m = 0.5 * (f + jnp.swapaxes(f, -1, -2)) + \
        damp[..., None, None, None] * jnp.eye(b, dtype=F32)
    chol = jnp.linalg.cholesky(m)
    eye = jnp.broadcast_to(jnp.eye(b, dtype=F32), m.shape)
    y = jax.lax.linalg.triangular_solve(chol, eye, left_side=True,
                                        lower=True)
    return jnp.einsum("...ji,...jk->...ik", y, y, precision=HI)


def left(a_inv, dw, full: bool, max_dim: int, op_dtype=None):
    """A^-1 dW over dW's second-last axis."""
    if a_inv is None:
        return dw
    if not full:
        return a_inv[..., :, None] * dw
    d = dw.shape[-2]
    ub = split(dw, d, max_dim, axis=-2)                  # (..., nb, b, m)
    return merge(dot("...ibc,...icm->...ibm", a_inv, ub, op_dtype), d, -3)


def right(dw, g_inv, full: bool, max_dim: int, op_dtype=None):
    """dW G^-1 over dW's last axis."""
    if g_inv is None:
        return dw
    if not full:
        return dw * g_inv[..., None, :]
    d = dw.shape[-1]
    ub = split(dw, d, max_dim, axis=-1)                  # (..., m, nb, b)
    return merge(dot("...mib,...ibc->...mic", ub, g_inv, op_dtype), d, -2)


def identity(d: int, max_dim: int, full: bool, lead: tuple = ()):
    """The preconditioner before the first refresh activates."""
    if not full:
        return jnp.ones(lead + (d,), F32)
    nb, b = blocks(d, max_dim)
    return jnp.broadcast_to(jnp.eye(b, dtype=F32), lead + (nb, b, b))


def momentum(p, v, u, lr: float, mom: float):
    """Heavy-ball step in the parameters' storage dtype."""
    v = (mom * v.astype(F32) - lr * u.astype(p.dtype).astype(F32)
         ).astype(v.dtype)
    return (p.astype(F32) + v.astype(F32)).astype(p.dtype), v


def schedule(interval: int, chunks: int, steps: int):
    """Per step: (captures statistics, activates the refresh captured at
    that earlier step or None). Every statistic refreshes together at
    steps 1, 1 + interval, ...; a capture's inverses serve from ``chunks +
    1`` steps after it (they are computed over the ``chunks`` steps in
    between)."""
    out = []
    for t in range(1, steps + 1):
        capture = (t - 1) % interval == 0
        c = t - chunks - 1
        activates = c if c >= 1 and (c - 1) % interval == 0 else None
        out.append((capture, activates))
    return out


# ---------------------------------------------------------------------------
# parameter trees: nested dicts addressed by '/'-joined paths
# ---------------------------------------------------------------------------

def get(tree, path: str):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def paths(tree, prefix: str = ""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from paths(v, f"{prefix}{k}/")
    else:
        yield prefix[:-1], tree


def unflat(flat: dict, like):
    """Rebuild ``like``'s nesting from a {path: leaf} dict."""
    def rec(node, prefix):
        if isinstance(node, dict):
            return {k: rec(v, f"{prefix}{k}/") for k, v in node.items()}
        return flat[prefix[:-1]]
    return rec(like, "")


@jax.jit
def _norms(tree):
    return {path: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for path, x in paths(tree)}


def leaf_norms(tree) -> dict:
    """{path: float32 norm} of every leaf."""
    return {k: float(v) for k, v in _norms(tree).items()}
