"""Plain float32 reference of SP-NGD training a Qwen-style decoder LM.

The model follows the published Qwen1.5 (Qwen2) architecture: token
embedding, pre-norm blocks of RMSNorm, multi-head attention with biased
q/k/v projections and rotary embeddings, residual, RMSNorm, SwiGLU MLP,
residual; a final RMSNorm and an untied output head; mean next-token
cross-entropy. Departures, each shared with the program so that the two
compute one function: rotary pairs are the interleaved dims (2i, 2i + 1)
(Hugging Face pairs i with i + hd/2; the two are one model under a fixed
permutation of each head's q and k columns), and RMSNorm's epsilon is
1e-6.

The optimizer is the paper's SP-NGD: per site the Kronecker factors
A = E[a a^T] (input side) and G = E[g g^T] (output side, g the gradient of
the per-sample log-likelihood), kept as diagonal blocks of at most
``kfac_max_dim``; the embedding's A and the head's G are diagonal (token
frequencies and squared logit gradients); biases and norm scales take the
unit-wise diagonal Fisher. Factors are damped with the pi split of
sqrt(damping), inverted, and applied as A^-1 dW G^-1, then a heavy-ball
step. Every statistic refreshes every ``interval`` steps and a refresh's
inverses serve from ``refresh_chunks + 1`` steps after its capture, as the
configuration states.

Weights and velocity are stored in the configuration's dtype; every other
number is float32 and every matmul runs at the highest precision. With
``op_dtype`` set (the control), every matmul takes its operands at that
dtype instead.

Gradients are formed from each site's input and output cotangent (dW =
sum_t a_t g_t^T), so the weights are never differentiated in float32 as a
whole; and a step's batch is taken :data:`ROW_BLOCK` sequences at a time,
the blocks' gradients and factor sums added up before the update. The
mean is over the whole batch: that is the data-parallel step's
mathematics (the mean over every chip's sequences, factors over all of
them), computed on one chip. Both keep a 4-layer stage at published
widths inside one chip's memory once the program's state is freed.
"""

from __future__ import annotations

import functools
import json
import types

import jax
import jax.numpy as jnp

from chipbench.reference.kfac_plain import (F32, dot, get, gram,
                                            identity, inverse, leaf_norms,
                                            left, momentum, pi_split, right,
                                            schedule, unflat)

# (site, weight path, bias path or None, input activation, output
# cotangent); q/k/v share their input
BLOCK_DENSE = [
    ("wq", "attn/wq", "attn/bq", "h1", "q"),
    ("wk", "attn/wk", "attn/bk", "h1", "k"),
    ("wv", "attn/wv", "attn/bv", "h1", "v"),
    ("wo", "attn/wo", None, "o", "o"),
    ("up", "mlp/up", None, "h2", "up"),
    ("gate", "mlp/gate", None, "h2", "gate"),
    ("down", "mlp/down", None, "a", "down"),
]
BLOCK_NORMS = [("ln1", "ln1/gamma", "xh1"), ("ln2", "ln2/gamma", "xh2")]
# the head's logits are formed this many chunks of tokens per sequence at
# a time
HEAD_CHUNKS = 2
# sequences whose forward and backward run at a time
ROW_BLOCK = 2


def _rms(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)


def _rope(x, theta: float):
    """x (B, S, H, hd): rotate dims (2i, 2i+1) by pos * theta^(-2i/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _trunk(params, eps, tok, cfg, op):
    """Embedding and blocks to the final norm's output; ``eps`` are zeros
    added at every site's output, so their cotangents are the sites'
    output cotangents. Also returns the activations the statistics and
    gradients need."""
    b, s = tok.shape
    h, hd = cfg["num_attention_heads"], cfg["hidden_size"] // \
        cfg["num_attention_heads"]
    x = params["embed"]["table"][tok].astype(F32) + eps["embed"]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, xs):
        p, e = xs
        xh1 = _rms(x)
        h1 = xh1 * p["ln1"]["gamma"].astype(F32) + e["ln1"]
        q = dot("bsd,de->bse", h1, p["attn"]["wq"], op) + \
            p["attn"]["bq"].astype(F32) + e["q"]
        k = dot("bsd,de->bse", h1, p["attn"]["wk"], op) + \
            p["attn"]["bk"].astype(F32) + e["k"]
        v = dot("bsd,de->bse", h1, p["attn"]["wv"], op) + \
            p["attn"]["bv"].astype(F32) + e["v"]
        q = _rope(q.reshape(b, s, h, hd), cfg["rope_theta"])
        k = _rope(k.reshape(b, s, -1, hd), cfg["rope_theta"])
        v = v.reshape(b, s, -1, hd)
        rep = h // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        sc = dot("bqhd,bkhd->bhqk", q, k, op) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = dot("bhqk,bkhd->bqhd", pr, v, op).reshape(b, s, h * hd)
        x = x + dot("bsd,de->bse", o, p["attn"]["wo"], op) + e["o"]
        xh2 = _rms(x)
        h2 = xh2 * p["ln2"]["gamma"].astype(F32) + e["ln2"]
        up = dot("bsd,df->bsf", h2, p["mlp"]["up"], op) + e["up"]
        gate = dot("bsd,df->bsf", h2, p["mlp"]["gate"], op) + e["gate"]
        a = jax.nn.silu(gate) * up
        x = x + dot("bsf,fd->bsd", a, p["mlp"]["down"], op) + e["down"]
        return x, {"xh1": xh1, "h1": h1, "o": o, "xh2": xh2, "h2": h2,
                   "a": a}

    x, acts = jax.lax.scan(layer, x, (params["blocks"], eps["blocks"]))
    xhf = _rms(x)
    hf = xhf * params["final_norm"]["gamma"].astype(F32) + eps["final"]
    return hf, (acts, xhf)


def _head_chunks(hf, labels, n_chunks: int):
    n = labels.size
    return (hf.reshape(n_chunks, n // n_chunks, hf.shape[-1]),
            labels.reshape(n_chunks, n // n_chunks))


def _head(hf, labels, w, op, n_chunks: int, n: int):
    """Cross-entropy of the head's logits summed over these tokens and
    divided by the batch's ``n``, taken over tokens in chunks so a
    whole-vocabulary logit matrix never exists at once. Returns the loss,
    dL/dhf, and each logit's squared cotangent summed over tokens (the
    head's diagonal G before normalization)."""
    hc, yc = _head_chunks(hf, labels, n_chunks)

    def body(carry, xs):
        nll, gsq = carry
        h, y = xs
        logits = dot("cd,dv->cv", h, w, op)
        lse = jax.nn.logsumexp(logits, axis=-1)
        nll = nll + jnp.sum(lse - jnp.take_along_axis(
            logits, y[:, None], -1)[:, 0])
        g = jnp.exp(logits - lse[:, None])
        g = g.at[jnp.arange(y.size), y].add(-1.0) / n     # dL/dlogits
        return (nll, gsq + jnp.sum(g * g, 0)), dot("cv,dv->cd", g, w, op)

    (nll, gsq), d_h = jax.lax.scan(
        body, (jnp.zeros((), F32), jnp.zeros((w.shape[-1],), F32)),
        (hc, yc))
    return nll / n, d_h.reshape(hf.shape), gsq


def _head_grad(hf, labels, w, op, n_chunks: int, n: int):
    """dL/dW of the head, summed over the same token chunks."""
    hc, yc = _head_chunks(hf, labels, n_chunks)

    def body(acc, xs):
        h, y = xs
        logits = dot("cd,dv->cv", h, w, op)
        g = jax.nn.softmax(logits, axis=-1)
        g = g.at[jnp.arange(y.size), y].add(-1.0) / n
        return acc + dot("cd,cv->dv", h, g, op), None

    acc, _ = jax.lax.scan(body, jnp.zeros(w.shape, F32), (hc, yc))
    return acc


def _zeros_eps(cfg, b, s):
    d, ff, L = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_hidden_layers"])
    kvd = cfg["num_key_value_heads"] * d // cfg["num_attention_heads"]
    z = lambda *shape: jnp.zeros(shape, F32)
    blk = {"ln1": z(L, b, s, d), "q": z(L, b, s, d), "k": z(L, b, s, kvd),
           "v": z(L, b, s, kvd), "o": z(L, b, s, d), "ln2": z(L, b, s, d),
           "up": z(L, b, s, ff), "gate": z(L, b, s, ff),
           "down": z(L, b, s, d)}
    return {"embed": z(b, s, d), "blocks": blk, "final": z(b, s, d)}


def _backward(params, batch, n, cfg, op):
    """This block's share of the loss (its tokens' sum over the batch's
    ``n``), every site's input (f32) and output cotangent, and the head's
    squared logit cotangents."""
    b, s = batch["tokens"].shape
    eps = _zeros_eps(cfg, b, s)
    hf, vjp, (acts, xhf) = jax.vjp(
        lambda e: _trunk(params, e, batch["tokens"], cfg, op), eps,
        has_aux=True)
    loss, d_hf, head_gsq = _head(hf.reshape(b * s, -1),
                                 batch["labels"].reshape(-1),
                                 params["head"]["w"], op, HEAD_CHUNKS * b,
                                 n)
    (gy,) = vjp(d_hf.reshape(hf.shape))
    return loss, acts, xhf, hf, gy, head_gsq


def _flat(x):
    return x.reshape(-1, x.shape[-1])


def _statistics(batch, acts, xhf, hf, gy, head_gsq, n, cfg, op):
    """This block's share of every site's normalized factors (Eq. 10-13,
    15): A over the input side, G over the output side scaled by the
    batch's n (the per-sample gradient is n times the mean loss's)."""
    md, v = cfg["optimizer"]["kfac_max_dim"], cfg["vocab_size"]
    tok = batch["tokens"]
    per_layer = jax.vmap(lambda x: gram(_flat(x), md, op))
    st = {"embed": {"a": jnp.zeros((v,), F32).at[tok.reshape(-1)].add(1.0)
                    / n,
                    "g": gram(_flat(gy["embed"]), md, op) * n},
          "head": {"a": gram(_flat(hf), md, op) / n, "g": head_gsq * n},
          "final_norm": {"uw": jnp.sum(jnp.square(_flat(gy["final"]
                                                        * xhf)), 0) * n}}
    blk = gy["blocks"]
    for site, _, bias, a_name, g_name in BLOCK_DENSE:
        st[site] = {"a": per_layer(acts[a_name]) / n,
                    "g": per_layer(blk[g_name]) * n}
        if bias:
            st[site]["d"] = jnp.sum(jnp.square(blk[g_name]), (1, 2)) * n
    for site, _, xh in BLOCK_NORMS:
        st[site] = {"uw": jnp.sum(jnp.square(blk[site] * acts[xh]),
                                  (1, 2)) * n}
    return st


def _inverses(st, cfg):
    """Damped inverses of a captured refresh (Eq. 12): A + pi sqrt(lam) I
    and G + sqrt(lam)/pi I; diagonal statistics pass through."""
    lam = cfg["optimizer"]["damping"]
    sl = jnp.sqrt(jnp.asarray(lam, F32))
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dims = _dims(cfg)
    out = {}
    for site, s in st.items():
        if "a" not in s:
            out[site] = dict(s)
            continue
        a_full, g_full = _kinds(site)
        d_a, d_g = dims[site]
        pi = pi_split(s["a"], a_full, d_a, s["g"], g_full, d_g)
        out[site] = {"a": inverse(s["a"], pi * sl, a_full),
                     "g": inverse(s["g"], sl / pi, g_full)}
        if "d" in s:
            out[site]["d"] = s["d"]
    return out


def _kinds(site):
    """(A is blocked, G is blocked) of a site."""
    if site == "embed":
        return False, True
    if site == "head":
        return True, False
    return True, True


def _dims(cfg):
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["vocab_size"]
    kvd = cfg["num_key_value_heads"] * d // cfg["num_attention_heads"]
    return {"embed": (v, d), "head": (d, v), "wq": (d, d), "wk": (d, kvd),
            "wv": (d, kvd), "wo": (d, d), "up": (d, ff), "gate": (d, ff),
            "down": (ff, d)}


def _initial(cfg, L):
    """Preconditioner state before the first refresh activates: identity
    factors, zero diagonal statistics."""
    md = cfg["optimizer"]["kfac_max_dim"]
    dims = _dims(cfg)
    pc = {}
    for site, (d_a, d_g) in dims.items():
        a_full, g_full = _kinds(site)
        lead = () if site in ("embed", "head") else (L,)
        pc[site] = {"a": identity(d_a, md, a_full, lead),
                    "g": identity(d_g, md, g_full, lead)}
    for site, _, bias, _, _ in BLOCK_DENSE:
        if bias:
            pc[site]["d"] = jnp.zeros((L, dims[site][1]), F32)
    pc["final_norm"] = {"uw": jnp.zeros((cfg["hidden_size"],), F32)}
    for site, _, _ in BLOCK_NORMS:
        pc[site] = {"uw": jnp.zeros((L, cfg["hidden_size"]), F32)}
    return pc


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x)))


def _update_head(w, v, hf, labels, pc, n, cfg, op):
    """The head's gradient (summed over token chunks), A^-1 dW G^-1 with a
    diagonal G, and its heavy-ball step."""
    o, b = cfg["optimizer"], labels.shape[0]
    dw = _head_grad(_flat(hf), labels.reshape(-1), w, op, HEAD_CHUNKS * b,
                    n)
    u = right(left(pc["a"], dw, True, o["kfac_max_dim"], op), pc["g"],
              False, o["kfac_max_dim"], op)
    w, v = momentum(w, v, u, o["lr"], o["momentum"])
    return w, v, _norm(dw)


def _update_embed(table, v, tokens, gy, pc, cfg, op):
    """The embedding's gradient (rows scattered by token), a diagonal A
    over the vocabulary, a blocked G, and its heavy-ball step."""
    o = cfg["optimizer"]
    dw = jnp.zeros(table.shape, F32).at[tokens.reshape(-1)].add(_flat(gy))
    u = right(left(pc["a"], dw, False, o["kfac_max_dim"], op), pc["g"],
              True, o["kfac_max_dim"], op)
    table, v = momentum(table, v, u, o["lr"], o["momentum"])
    return table, v, _norm(dw)


def _rest_grads(acts, xhf, gy, op):
    """This block's share of the gradients of the blocks and the final
    norm, from sites (dW = sum_t a_t g_t^T)."""
    blk = gy["blocks"]
    grads = {"final_norm/gamma": jnp.sum(_flat(gy["final"] * xhf), 0)}
    layer_dw = jax.vmap(lambda x, g: dot("nd,ne->de", _flat(x), _flat(g),
                                         op))
    for _, path, bias, a_name, g_name in BLOCK_DENSE:
        grads[f"blocks/{path}"] = layer_dw(acts[a_name], blk[g_name])
        if bias:
            grads[f"blocks/{bias}"] = jnp.sum(blk[g_name], (1, 2))
    for site, path, xh in BLOCK_NORMS:
        grads[f"blocks/{path}"] = jnp.sum(blk[site] * acts[xh], (1, 2))
    return grads


def _update_rest(params, vel, grads, pc, cfg, op):
    """The blocks and the final norm: their gradients preconditioned, the
    heavy-ball step; ``params`` and ``vel`` hold the "blocks" and
    "final_norm" subtrees."""
    o = cfg["optimizer"]
    md, lam, lr, mom = (o["kfac_max_dim"], o["damping"], o["lr"],
                        o["momentum"])
    ups = {"final_norm/gamma": grads["final_norm/gamma"]
           / (pc["final_norm"]["uw"] + lam)}
    for site, path, bias, _, _ in BLOCK_DENSE:
        ups[f"blocks/{path}"] = right(
            left(pc[site]["a"], grads[f"blocks/{path}"], True, md, op),
            pc[site]["g"], True, md, op)
        if bias:
            ups[f"blocks/{bias}"] = grads[f"blocks/{bias}"] / \
                (pc[site]["d"] + lam)
    for site, path, _ in BLOCK_NORMS:
        ups[f"blocks/{path}"] = grads[f"blocks/{path}"] / \
            (pc[site]["uw"] + lam)
    new_p, new_v = {}, {}
    for path, u in ups.items():
        new_p[path], new_v[path] = momentum(get(params, path),
                                            get(vel, path), u, lr, mom)
    return (unflat(new_p, params), unflat(new_v, vel),
            {k: _norm(g) for k, g in grads.items()})


_tree_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                    donate_argnums=0)


def _add(acc, x):
    """``acc + x`` leaf by leaf; the first block's share is taken as is."""
    return x if acc is None else _tree_add(acc, x)


@functools.lru_cache(maxsize=4)
def _programs(config_json: str, op):
    """The reference's jitted programs for one configuration; ``n``, the
    batch's token count, is static."""
    cfg = json.loads(config_json)
    return types.SimpleNamespace(
        backward=jax.jit(lambda p, b, n: _backward(p, b, n, cfg, op),
                         static_argnums=2),
        statistics=jax.jit(lambda b, a, x, h, g, s, n: _statistics(
            b, a, x, h, g, s, n, cfg, op), static_argnums=6),
        rest_grads=jax.jit(lambda a, x, g: _rest_grads(a, x, g, op)),
        inverses=jax.jit(lambda st: _inverses(st, cfg)),
        head=jax.jit(lambda w, v, h, y, pc, n: _update_head(
            w, v, h, y, pc, n, cfg, op), donate_argnums=(0, 1),
            static_argnums=5),
        embed=jax.jit(lambda w, v, t, g, pc: _update_embed(
            w, v, t, g, pc, cfg, op), donate_argnums=(0, 1)),
        rest=jax.jit(lambda p, v, g, pc: _update_rest(
            p, v, g, pc, cfg, op), donate_argnums=(0, 1)))


def _rows(batch, start: int, stop: int) -> dict:
    return jax.tree.map(lambda x: x[start:stop], batch)


def _joined(parts: list):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def run(config: dict, traffic: dict, params, batches: list, steps: int,
        op_dtype=None, fault=None) -> dict:
    """Train ``steps`` steps from ``params`` on ``batches`` (one each).
    Returns each step's loss, the first step's gradient norms as the
    optimizer took them (velocity / lr) and before preconditioning, and
    the final parameters. ``fault`` plants a known error: ``"half_batch"``
    trains on the first half of each batch's rows (the mean over them);
    ``"drop_shard"`` leaves the rows of the traffic's last data shard out
    of the gradient and factor sums (the mean still over every row)."""
    if traffic["accum"] != 1:
        raise NotImplementedError("the LM reference takes accum = 1")
    op = None if op_dtype is None else jnp.dtype(op_dtype).name
    f = _programs(json.dumps(config, sort_keys=True), op)
    o = config["optimizer"]
    L = config["num_hidden_layers"]
    params = dict(params)
    vel = jax.tree.map(jnp.zeros_like, params)
    pc, pending = _initial(config, L), {}
    out = {"loss": []}
    for t, (capture, activates) in enumerate(
            schedule(traffic["interval"], o["refresh_chunks"], steps), 1):
        batch = batches[t - 1]
        rows = batch["tokens"].shape[0]
        if fault == "half_batch":
            rows //= 2
            batch = _rows(batch, 0, rows)
        n = batch["tokens"].size
        if fault == "drop_shard":
            rows -= rows // traffic["mesh"]["data"]
        if activates is not None:
            pc = f.inverses(pending.pop(activates))
        loss, stats, grads, hf, gy_embed, blocks = 0.0, None, None, [], [], []
        for i in range(0, rows, ROW_BLOCK):
            block = _rows(batch, i, min(i + ROW_BLOCK, rows))
            part, acts, xhf, h, gy, head_gsq = f.backward(params, block, n)
            loss = loss + part
            if capture:
                stats = _add(stats, f.statistics(block, acts, xhf, h, gy,
                                                 head_gsq, n))
            grads = _add(grads, f.rest_grads(acts, xhf, gy))
            hf.append(h)
            gy_embed.append(gy["embed"])
            blocks.append(block)
            del acts, xhf, h, gy, head_gsq
        if capture:
            pending[t] = stats
        kept = jax.tree.map(lambda *x: _joined(list(x)), *blocks)
        gnorm = {}
        w, v, gnorm["head/w"] = f.head(params["head"]["w"], vel["head"]["w"],
                                       _joined(hf), kept["labels"],
                                       pc["head"], n)
        params["head"], vel["head"] = {"w": w}, {"w": v}
        w, v, gnorm["embed/table"] = f.embed(
            params["embed"]["table"], vel["embed"]["table"],
            kept["tokens"], _joined(gy_embed), pc["embed"])
        params["embed"], vel["embed"] = {"table": w}, {"table": v}
        rest = ("blocks", "final_norm")
        p, v, g = f.rest({k: params[k] for k in rest},
                         {k: vel[k] for k in rest}, grads, pc)
        params.update(p)
        vel.update(v)
        gnorm.update(g)
        del grads, hf, gy_embed, blocks, kept
        out["loss"].append(float(loss))
        if t == 1:
            out["grad1"] = {k: v / o["lr"]
                            for k, v in leaf_norms(vel).items()}
            out["raw_grad1"] = {k: float(v) for k, v in gnorm.items()}
    out["params"] = params
    return out
