"""Plain float32 reference of SP-NGD training a basic-block ResNet.

A CIFAR ResNet as the configuration states it: a 3x3 stem convolution,
stages of ``blocks_per_stage`` basic blocks (3x3 conv, BatchNorm, ReLU, 3x3
conv, BatchNorm, plus the identity or a 1x1 strided projection without a
BatchNorm, ReLU), the first block of every stage after the first striding
by 2, global average pooling and a dense head without a bias; mean
cross-entropy.
BatchNorm normalizes with the batch's own statistics (epsilon 1e-5);
convolutions pad as "SAME".

SP-NGD (paper §3-4): a convolution's Kronecker factors are those of its
im2col matmul (Eq. 10-11), A over the patches' features in (channel,
kernel row, kernel column) order, normalized by the number of output
positions, G over the output channels, normalized per sample; BatchNorm's
scale and shift take the unit-wise 2x2 Fisher of their per-sample
gradients (Eq. 15-17). Factors larger than ``kfac_max_dim`` are kept as
diagonal blocks. Damping, inversion, schedule and the heavy-ball step are
as in :mod:`chipbench.reference.kfac_plain`.

Every number is float32 and every matmul and convolution runs at the
highest precision; with ``op_dtype`` set (the control) they take their
operands at that dtype.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from chipbench.reference.kfac_plain import (F32, HI, cast, dot, get,
                                            gram, identity, inverse,
                                            leaf_norms, left, momentum,
                                            pi_split, right, schedule,
                                            unflat)


# images per block of a convolution's patch Gram
ROW_BLOCK = 128


def _layout(cfg):
    """Conv sites (name, path, kernel, stride, cin, cout, input size) and
    BN sites (name, gamma path, beta path, channels, size)."""
    convs, bns = [], []
    hw, c_in, w0 = cfg["image_size"], cfg["in_channels"], cfg["widths"][0]
    convs.append(("stem", "stem/w", 3, 1, c_in, w0, hw))
    bns.append(("stem_bn", "stem/gamma", "stem/beta", w0, hw))
    c_in = w0
    for si, w in enumerate(cfg["widths"]):
        for bi in range(cfg["blocks_per_stage"]):
            nm = f"s{si}b{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            out = -(-hw // stride)
            convs.append((f"{nm}_w1", f"{nm}/w1", 3, stride, c_in, w, hw))
            bns.append((f"{nm}_bn1", f"{nm}/g1", f"{nm}/b1", w, out))
            convs.append((f"{nm}_w2", f"{nm}/w2", 3, 1, w, w, out))
            bns.append((f"{nm}_bn2", f"{nm}/g2", f"{nm}/b2", w, out))
            if stride != 1 or c_in != w:
                convs.append((f"{nm}_wskip", f"{nm}/wskip", 1, stride, c_in,
                              w, hw))
            c_in, hw = w, out
    return convs, bns


def _same_pad(size: int, k: int, stride: int) -> tuple[int, int]:
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return total // 2, total - total // 2


def _conv(x, w, stride, op):
    k = w.shape[0]
    pads = [_same_pad(x.shape[1], k, stride),
            _same_pad(x.shape[2], k, stride)]
    return jax.lax.conv_general_dilated(
        cast(x, op), cast(w, op), (stride, stride), pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
        preferred_element_type=F32)


def _forward(params, eps, batch, cfg, op):
    """Loss; ``eps`` are zeros added at every site's output, whose
    gradients are the output cotangents. Also returns each conv's input,
    each BN's normalized input and the head's input."""
    conv_in, xhat = {}, {}

    def conv(name, path, stride, x):
        conv_in[name] = x
        return _conv(x, get(params, path), stride, op) + eps[name]

    def bn(name, gpath, bpath, x):
        mu = x.mean((0, 1, 2), keepdims=True)
        var = jnp.square(x - mu).mean((0, 1, 2), keepdims=True)
        xh = (x - mu) * jax.lax.rsqrt(var + 1e-5)
        xhat[name] = xh
        return xh * get(params, gpath) + get(params, bpath) + eps[name]

    h = jax.nn.relu(bn("stem_bn", "stem/gamma", "stem/beta",
                       conv("stem", "stem/w", 1, batch["images"])))
    c_in = cfg["widths"][0]
    for si, w in enumerate(cfg["widths"]):
        for bi in range(cfg["blocks_per_stage"]):
            nm = f"s{si}b{bi}"
            stride = 2 if (bi == 0 and si > 0) else 1
            y = conv(f"{nm}_w1", f"{nm}/w1", stride, h)
            y = jax.nn.relu(bn(f"{nm}_bn1", f"{nm}/g1", f"{nm}/b1", y))
            y = conv(f"{nm}_w2", f"{nm}/w2", 1, y)
            y = bn(f"{nm}_bn2", f"{nm}/g2", f"{nm}/b2", y)
            if stride != 1 or c_in != w:
                h = conv(f"{nm}_wskip", f"{nm}/wskip", stride, h)
            h = jax.nn.relu(h + y)
            c_in = w
    pooled = h.mean((1, 2))
    logits = dot("bc,ck->bk", pooled, params["head"]["w"], op) + eps["head"]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch["labels"][:, None], -1)[:, 0]
    return nll.mean(), (conv_in, xhat, pooled)


def _zeros_eps(cfg, b):
    convs, bns = _layout(cfg)
    eps = {}
    for name, _, _, stride, _, cout, hw in convs:
        out = -(-hw // stride)
        eps[name] = jnp.zeros((b, out, out, cout), F32)
    for name, _, _, c, hw in bns:
        eps[name] = jnp.zeros((b, hw, hw, c), F32)
    eps["head"] = jnp.zeros((b, cfg["num_classes"]), F32)
    return eps


def _patch_gram(x, k: int, stride: int, max_dim: int, op):
    """Blocked A = sum over output positions of patch patch^T, the patch's
    features in (channel, kernel row, kernel column) order. The patches
    are made and summed :data:`ROW_BLOCK` images at a time, so that a
    whole batch's patches (2.4 GB for one 64-channel site at 1024 images)
    never sit in memory beside the backward pass's outputs."""
    pads = [_same_pad(x.shape[1], k, stride),
            _same_pad(x.shape[2], k, stride)]

    def block(xb):
        patches = jax.lax.conv_general_dilated_patches(
            xb, (k, k), (stride, stride), pads,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
        return gram(patches.reshape(-1, patches.shape[-1]), max_dim, op)

    rows = math.gcd(x.shape[0], ROW_BLOCK)
    xs = x.reshape(x.shape[0] // rows, rows, *x.shape[1:])
    return jax.lax.map(block, xs).sum(0)


def _backward(params, batch, cfg, op):
    """Loss, weight gradients (f32), output cotangents and site inputs."""
    eps = _zeros_eps(cfg, batch["images"].shape[0])
    (loss, aux), (grads, gy) = jax.value_and_grad(
        _forward, argnums=(0, 1), has_aux=True)(params, eps, batch, cfg, op)
    return loss, grads, gy, aux


def _statistics(batch, gy, aux, cfg, op):
    """Normalized factors: conv A by output positions, G by samples
    (the per-sample gradient is b times the mean loss's); BN unit-wise
    stats of the per-sample (gamma, beta) gradients."""
    md = cfg["optimizer"]["kfac_max_dim"]
    conv_in, xhat, pooled = aux
    convs, bns = _layout(cfg)
    b = batch["images"].shape[0]
    st = {}
    for name, _, k, stride, _, cout, hw in convs:
        n_pos = b * (-(-hw // stride)) ** 2
        st[name] = {"a": _patch_gram(conv_in[name], k, stride, md, op)
                    / n_pos,
                    "g": gram(gy[name].reshape(-1, cout), md, op) * b}
    for name, _, _, c, _ in bns:
        us = jnp.sum(gy[name] * xhat[name], (1, 2))          # (b, c)
        vs = jnp.sum(gy[name], (1, 2))
        st[name] = {"uw": jnp.stack([jnp.sum(us * us, 0),
                                     jnp.sum(us * vs, 0),
                                     jnp.sum(vs * vs, 0)], -1) * b}
    st["head"] = {"a": gram(pooled, md, op) / b,
                  "g": gram(gy["head"], md, op) * b}
    return st


def _dims(cfg):
    convs, _ = _layout(cfg)
    dims = {name: (k * k * cin, cout)
            for name, _, k, _, cin, cout, _ in convs}
    dims["head"] = (cfg["widths"][-1], cfg["num_classes"])
    return dims


def _inverses(st, cfg):
    lam = cfg["optimizer"]["damping"]
    sl = jnp.sqrt(jnp.asarray(lam, F32))
    dims = _dims(cfg)
    out = {}
    for site, s in st.items():
        if "a" not in s:
            out[site] = dict(s)
            continue
        d_a, d_g = dims[site]
        pi = pi_split(s["a"], True, d_a, s["g"], True, d_g)
        out[site] = {"a": inverse(s["a"], pi * sl, True),
                     "g": inverse(s["g"], sl / pi, True)}
    return out


def _initial(cfg):
    md = cfg["optimizer"]["kfac_max_dim"]
    _, bns = _layout(cfg)
    pc = {site: {"a": identity(d_a, md, True), "g": identity(d_g, md, True)}
          for site, (d_a, d_g) in _dims(cfg).items()}
    for name, _, _, c, _ in bns:
        pc[name] = {"uw": jnp.zeros((c, 3), F32)}
    return pc


def _update(params, vel, grads, pc, cfg, op):
    """Preconditioned heavy-ball step; per-leaf gradient norms."""
    o = cfg["optimizer"]
    md, lam, lr, mom = (o["kfac_max_dim"], o["damping"], o["lr"],
                        o["momentum"])
    convs, bns = _layout(cfg)
    ups = {}
    for name, path, k, _, cin, cout, _ in convs:
        dw = get(grads, path)                                # (k,k,cin,cout)
        d2 = jnp.transpose(dw, (2, 0, 1, 3)).reshape(cin * k * k, cout)
        u = right(left(pc[name]["a"], d2, True, md, op), pc[name]["g"],
                  True, md, op)
        ups[path] = jnp.transpose(u.reshape(cin, k, k, cout), (1, 2, 0, 3))
    ups["head/w"] = right(left(pc["head"]["a"], grads["head"]["w"], True,
                               md, op), pc["head"]["g"], True, md, op)
    for name, gpath, bpath, _, _ in bns:
        s = pc[name]["uw"]
        gg, gb = get(grads, gpath), get(grads, bpath)
        aa, ab, bb = s[..., 0] + lam, s[..., 1], s[..., 2] + lam
        det = jnp.maximum(aa * bb - ab * ab, 1e-20)
        ups[gpath] = (bb * gg - ab * gb) / det
        ups[bpath] = (-ab * gg + aa * gb) / det
    new_p, new_v = {}, {}
    for path, u in ups.items():
        new_p[path], new_v[path] = momentum(get(params, path),
                                            get(vel, path), u, lr, mom)
    gnorm = {path: jnp.sqrt(jnp.sum(jnp.square(get(grads, path))))
             for path in ups}
    return unflat(new_p, params), unflat(new_v, vel), gnorm


@functools.lru_cache(maxsize=4)
def _programs(config_json: str, op):
    cfg = json.loads(config_json)
    return (
        jax.jit(lambda p, b: _backward(p, b, cfg, op)),
        jax.jit(lambda b, gy, aux: _statistics(b, gy, aux, cfg, op)),
        jax.jit(lambda st: _inverses(st, cfg)),
        jax.jit(lambda p, v, g, pc: _update(p, v, g, pc, cfg, op),
                donate_argnums=(0, 1)),
    )


def run(config: dict, traffic: dict, params, batches: list, steps: int,
        op_dtype=None, fault=None) -> dict:
    """Train ``steps`` steps from ``params`` on ``batches`` (one each);
    see :func:`chipbench.reference.decoder_lm.run` for what it returns.
    ``fault="half_batch"`` trains on the first half of each batch."""
    if traffic["accum"] != 1:
        raise NotImplementedError("the conv reference takes accum = 1")
    op = None if op_dtype is None else jnp.dtype(op_dtype).name
    backward, statistics, inverses, update = _programs(
        json.dumps(config, sort_keys=True), op)
    o = config["optimizer"]
    vel = jax.tree.map(jnp.zeros_like, params)
    pc, pending = _initial(config), {}
    out = {"loss": []}
    for t, (capture, activates) in enumerate(
            schedule(traffic["interval"], o["refresh_chunks"], steps), 1):
        batch = batches[t - 1]
        if fault == "half_batch":
            half = batch["images"].shape[0] // 2
            batch = jax.tree.map(lambda x: x[:half], batch)
        if activates is not None:
            pc = inverses(pending.pop(activates))
        loss, grads, gy, aux = backward(params, batch)
        if capture:
            pending[t] = statistics(batch, gy, aux)
        params, vel, gnorm = update(params, vel, grads, pc)
        del grads, gy, aux
        out["loss"].append(float(loss))
        if t == 1:
            out["grad1"] = {k: v / o["lr"]
                            for k, v in leaf_norms(vel).items()}
            out["raw_grad1"] = {k: float(v) for k, v in gnorm.items()}
    out["params"] = params
    return out
