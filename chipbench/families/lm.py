"""The system under test for decoder-LM configurations.

repro's ``DecoderLM`` trained by ``SPNGD`` through the jitted pair
``make_train_step`` / ``make_fast_step`` (both donate params and optimizer
state), with an ``IntervalController`` pinned to the traffic's refresh
interval. The configuration file uses the Hugging Face key names of the
model's published ``config.json``; :func:`arch_config` maps them onto the
program's ``ArchConfig``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.work import Work

ITEM = "tokens"


def arch_config(config: dict):
    from repro.configs.base import ArchConfig
    opt = config["optimizer"]
    d, h = config["hidden_size"], config["num_attention_heads"]
    return ArchConfig(
        name=config["name"], arch_type="dense",
        n_layers=config["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        head_dim=d // h, act=config["hidden_act"], gated_mlp=True,
        qkv_bias=config["qkv_bias"], rope_theta=config["rope_theta"],
        norm="rmsnorm", backend=opt["backend"],
        kfac_max_dim=opt["kfac_max_dim"], head_g_kind=opt["head_g_kind"],
        dtype=jnp.dtype(config["torch_dtype"]), remat=True,
        source=config["source"])


def build_model(config: dict):
    from repro.models.transformer import DecoderLM
    return DecoderLM(arch_config(config))


def rows_per_step(traffic: dict) -> int:
    return traffic["seqs"] * traffic["accum"]


def items_per_step(traffic: dict) -> int:
    return rows_per_step(traffic) * traffic["seq_len"]


def leaf_init(path: str, shape: tuple):
    """How the benchmark draws a weight leaf from the seed: ("normal",
    std), ("ones",) or ("zeros",)."""
    name = path.rsplit("/", 1)[-1]
    if name == "gamma":
        return ("ones",)
    if path == "embed/table":
        return ("normal", 0.02)
    if name in ("bq", "bk", "bv"):
        return ("normal", 0.02)
    return ("normal", (2.0 / shape[-2]) ** 0.5)        # He normal, (d_in, d_out)


def make_batches(key, traffic: dict, config: dict, n: int) -> list:
    """``n`` distinct batches of uniformly random token ids."""
    rows, s = rows_per_step(traffic), traffic["seq_len"]
    ids = jax.random.randint(key, (n, rows, s + 1), 0, config["vocab_size"],
                             jnp.int32)
    return [{"tokens": ids[i, :, :-1], "labels": ids[i, :, 1:]}
            for i in range(n)]


# ---------------------------------------------------------------------------
# work counted from the configuration (chipbench/work.py)
# ---------------------------------------------------------------------------

def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // h
    return {"d": d, "h": h, "hd": hd, "kvd": cfg["num_key_value_heads"] * hd,
            "ff": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"]}


def dense_sites(cfg: dict) -> list[tuple[str, int, int, int, bool, bool]]:
    """(site, count, d_in, d_out, A blocked, G blocked) of every factored
    matmul-shaped site: the blocks' projections (count = layers), the
    embedding (diagonal A) and the head (diagonal G)."""
    m = dims(cfg)
    L, d, kvd, ff, v = m["L"], m["d"], m["kvd"], m["ff"], m["v"]
    head_full = cfg["optimizer"]["head_g_kind"] == "full"
    return [("wq", L, d, d, True, True), ("wk", L, d, kvd, True, True),
            ("wv", L, d, kvd, True, True), ("wo", L, d, d, True, True),
            ("up", L, d, ff, True, True), ("gate", L, d, ff, True, True),
            ("down", L, ff, d, True, True),
            ("embed", 1, v, d, False, True),
            ("head", 1, d, v, True, head_full)]


def attention_fwd_flops_per_token(cfg: dict, seq: int) -> float:
    """QK^T and PV of one causal layer, averaged over a sequence's
    positions ((seq + 1) / 2 keys each)."""
    m = dims(cfg)
    return 2 * 2 * m["h"] * m["hd"] * (seq + 1) / 2


def model_flops_per_item(cfg: dict, traffic: dict) -> float:
    """Forward and backward FLOPs of one trained token."""
    m = dims(cfg)
    d, kvd, ff = m["d"], m["kvd"], m["ff"]
    per_layer = d * d + 2 * d * kvd + d * d + 3 * d * ff
    matmul = m["L"] * per_layer + d * m["v"]
    return 3 * (2 * matmul + m["L"] * attention_fwd_flops_per_token(
        cfg, traffic["seq_len"]))


def attention_work(cfg: dict, traffic: dict) -> Work:
    """Both attention kernels of one step: forward and backward, every
    layer, every sequence of the step, bf16 operands."""
    m = dims(cfg)
    rows, seq = rows_per_step(traffic), traffic["seq_len"]
    fwd = attention_fwd_flops_per_token(cfg, seq) * rows * seq * m["L"]
    el = rows * seq * m["L"]
    q, kv = m["h"] * m["hd"], m["kvd"]
    lse = 4 * rows * seq * m["h"] * m["L"]
    fwd_b = 2 * el * (q + 2 * kv + q) + lse           # q k v in, o out
    bwd_b = 2 * el * (3 * q + 2 * kv + 2 * kv + q) + lse  # q o do k v, dq dk dv
    return Work(3 * fwd, fwd_b + bwd_b)
