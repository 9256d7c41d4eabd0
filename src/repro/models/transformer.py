"""Config-driven decoder-only LM covering all assigned architecture families:
dense GQA (llama/qwen/nemotron/musicgen/llava backbones), MoE (mixtral,
qwen2-moe), hybrid attention+SSM (hymba), and RWKV-6.

Layers are homogeneous and stacked: parameters and K-FAC factor-statistics
arrays carry a leading (L,) axis and the forward is a ``lax.scan`` over
layers — this is what turns the paper's ragged ReduceScatterV into uniform
factor-family collectives (DESIGN.md §2).

Model surface used by the rest of the framework:
  init(key) -> params
  loss(params, fstats, batch) -> (loss, aux)        # train step objective
  forward(params, batch, fstats) -> (logits, aux)   # prefill
  init_cache(batch, max_len) / decode_step(params, cache, tokens)
  site_infos() / fstats()                           # SP-NGD wiring
  site_counts(batch) / site_rows(batch)
  input_specs(shape) -> ShapeDtypeStruct batch      # dry-run stand-ins
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, InputShape
from repro.core import tagging
from repro.core.fisher import SiteInfo
from repro.core.tagging import FactorSpec
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import he_normal, rmsnorm, layernorm, apply_rope
from repro.models.mlp import mlp, init_mlp


class DecoderLM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        # Optional residual-stream sharding constraint between layers
        # (Megatron-style sequence parallelism; set by the launch layer).
        self.act_hook = None
        # Optional MoE dispatch-buffer sharding constraint (launch layer).
        self.moe_hook = None
        self.spec = FactorSpec(max_dim=cfg.kfac_max_dim, backend=cfg.backend,
                               wire_fmt=cfg.factor_wire)
        self.head_spec = FactorSpec(g_kind=cfg.head_g_kind,
                                    max_dim=cfg.kfac_max_dim,
                                    backend=cfg.backend,
                                    wire_fmt=cfg.factor_wire)
        self.embed_spec = FactorSpec(a_kind="diag", g_kind="full",
                                     max_dim=cfg.kfac_max_dim,
                                     backend=cfg.backend,
                                     wire_fmt=cfg.factor_wire)
        self.specs = self._block_site_specs()

    def _tp_spec(self, d_in: int, d_out: int, *, a_tp: bool = False,
                 g_tp: bool = False) -> FactorSpec:
        """Factor spec with blocks aligned to TP shard boundaries
        (cfg.tp_shards > 0): the side whose activation is model-sharded gets
        block size = dim/tp so factor construction never crosses shards."""
        cfg = self.cfg
        tp = cfg.tp_shards

        def aligned(dim: int) -> int:
            """Largest block size that divides the shard width (dim/tp) and
            fits under kfac_max_dim — blocks must never cross shards."""
            if dim % tp or dim // tp < cfg.min_block:
                return 0
            b = dim // tp
            while b > cfg.kfac_max_dim:
                for k in (2, 3, 5, 7):
                    if b % k == 0:
                        b //= k
                        break
                else:
                    return 0            # no usable divisor
            return b if b >= cfg.min_block else 0

        a_max = aligned(d_in) if (tp and a_tp) else 0
        g_max = aligned(d_out) if (tp and g_tp) else 0
        return FactorSpec(max_dim=cfg.kfac_max_dim, a_max=a_max, g_max=g_max,
                          backend=cfg.backend, wire_fmt=cfg.factor_wire)

    def _spec_sub(self, prefix: str) -> dict:
        return {k[len(prefix):]: v for k, v in self.specs.items()
                if k.startswith(prefix)}

    def _block_site_specs(self) -> dict:
        """Per-site FactorSpec for block-level sites (module-local names).
        Column-parallel matmuls have model-sharded OUTPUTS (g side);
        row-parallel matmuls have model-sharded INPUTS (a side)."""
        cfg = self.cfg
        d, h, kv, hd, ff = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                            cfg.hd, cfg.d_ff)
        s: dict = {}
        if cfg.block_type in ("dense", "moe", "hymba"):
            s["attn_wq"] = self._tp_spec(d, h * hd, g_tp=True)
            s["attn_wk"] = self._tp_spec(d, kv * hd, g_tp=True)
            s["attn_wv"] = self._tp_spec(d, kv * hd, g_tp=True)
            s["attn_wo"] = self._tp_spec(h * hd, d, a_tp=True)
        if cfg.block_type in ("dense", "hymba"):
            s["mlp_up"] = self._tp_spec(d, ff, g_tp=True)
            s["mlp_gate"] = s["mlp_up"]
            s["mlp_down"] = self._tp_spec(ff, d, a_tp=True)
        if cfg.block_type == "moe":
            s["moe_router"] = self.spec
            s["moe_we_up"] = self._tp_spec(d, ff, g_tp=True)
            s["moe_we_gate"] = s["moe_we_up"]
            s["moe_we_down"] = self._tp_spec(ff, d, a_tp=True)
            sf = cfg.n_shared_experts * ff
            s["moe_sh_up"] = self._tp_spec(d, sf, g_tp=True)
            s["moe_sh_gate"] = s["moe_sh_up"]
            s["moe_sh_down"] = self._tp_spec(sf, d, a_tp=True)
        if cfg.block_type == "hymba":
            di = cfg.ssm_expand * d
            dt_rank = max(1, d // 16)
            s["ssm_in_proj"] = self._tp_spec(d, 2 * di, g_tp=True)
            s["ssm_xdb"] = self._tp_spec(di, dt_rank + 2 * cfg.ssm_state,
                                         a_tp=True)
            s["ssm_dt_proj"] = self._tp_spec(dt_rank, di, g_tp=True)
            s["ssm_out_proj"] = self._tp_spec(di, d, a_tp=True)
        if cfg.block_type == "rwkv":
            for nm in ("tm_wr", "tm_wk", "tm_wv", "tm_wg"):
                s[nm] = self._tp_spec(d, d, g_tp=True)
            s["tm_wo"] = self._tp_spec(d, d, a_tp=True)
            s["tm_w_lora_a"] = self.spec
            s["tm_w_lora_b"] = self.spec
            s["cm_wk"] = self._tp_spec(d, cfg.d_ff, g_tp=True)
            s["cm_wv"] = self._tp_spec(cfg.d_ff, d, a_tp=True)
            s["cm_wr"] = self._tp_spec(d, d, g_tp=True)
        return s

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        ke, kb, kn, kh, kp = jax.random.split(key, 5)
        params = {
            "embed": {"table": (jax.random.normal(ke, (cfg.vocab, cfg.d_model))
                                * 0.02).astype(cfg.dtype)},
            "final_norm": {"gamma": jnp.ones((cfg.d_model,), jnp.float32)},
            "head": {"w": he_normal(kh, (cfg.d_model, cfg.vocab), cfg.dtype)},
        }
        if cfg.frontend == "vision":
            params["proj"] = {"w": he_normal(kp, (cfg.frontend_dim, cfg.d_model),
                                             cfg.dtype)}
        keys = jax.random.split(kb, cfg.n_layers)
        per_layer = [self._init_block(k) for k in keys]
        params["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
        return params

    def _init_block(self, key: jax.Array) -> dict:
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        p: dict = {"ln1": {"gamma": jnp.ones((cfg.d_model,), jnp.float32)},
                   "ln2": {"gamma": jnp.ones((cfg.d_model,), jnp.float32)}}
        if cfg.norm == "layernorm":
            p["ln1"]["beta"] = jnp.zeros((cfg.d_model,), jnp.float32)
            p["ln2"]["beta"] = jnp.zeros((cfg.d_model,), jnp.float32)
        if cfg.block_type in ("dense", "moe", "hymba"):
            p["attn"] = self._init_attn(ks[0])
        if cfg.block_type in ("dense", "hymba"):
            p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp,
                                cfg.dtype)
        if cfg.block_type == "moe":
            p["moe"] = moe_lib.init_moe(ks[2], cfg.d_model, cfg.d_ff,
                                        cfg.n_experts, cfg.n_shared_experts,
                                        cfg.dtype)
        if cfg.block_type == "hymba":
            p["ssm"] = ssm_lib.init_ssm(ks[3], cfg.d_model, cfg.ssm_state,
                                        cfg.dtype, expand=cfg.ssm_expand)
        if cfg.block_type == "rwkv":
            p.pop("ln1"); p.pop("ln2")
            p["ln1"] = {"gamma": jnp.ones((cfg.d_model,), jnp.float32),
                        "beta": jnp.zeros((cfg.d_model,), jnp.float32)}
            p["ln2"] = {"gamma": jnp.ones((cfg.d_model,), jnp.float32),
                        "beta": jnp.zeros((cfg.d_model,), jnp.float32)}
            p["tm"] = rwkv_lib.init_rwkv_tm(ks[4], cfg.d_model, cfg.hd,
                                            cfg.dtype)
            p["cm"] = rwkv_lib.init_rwkv_cm(ks[5], cfg.d_model, cfg.d_ff,
                                            cfg.dtype)
        return p

    def _init_attn(self, key: jax.Array) -> dict:
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        h, kv, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model
        p = {"wq": he_normal(ks[0], (d, h * hd), cfg.dtype),
             "wk": he_normal(ks[1], (d, kv * hd), cfg.dtype),
             "wv": he_normal(ks[2], (d, kv * hd), cfg.dtype),
             "wo": he_normal(ks[3], (h * hd, d), cfg.dtype)}
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((h * hd,), cfg.dtype)
            p["bk"] = jnp.zeros((kv * hd,), cfg.dtype)
            p["bv"] = jnp.zeros((kv * hd,), cfg.dtype)
        return p

    # ------------------------------------------------------------------
    # norms / attention helpers
    # ------------------------------------------------------------------

    def _norm(self, x, p, fs_key, fs):
        stats = fs.get(fs_key) if fs else None
        if "beta" in p:
            return layernorm(x, p["gamma"], p["beta"], stats)
        return rmsnorm(x, p["gamma"], stats)

    def _attn(self, x, p, fs, *, positions, cache_kv=None, cache_len=None,
              window=None, serve=None):
        cfg = self.cfg
        b, s, d = x.shape
        h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        g = lambda n: (fs.get(f"attn_{n}") if fs else None)
        sp = self.specs
        q = tagging.dense_site(x, p["wq"], g("wq"), sp["attn_wq"])
        k = tagging.dense_site(x, p["wk"], g("wk"), sp["attn_wk"])
        v = tagging.dense_site(x, p["wv"], g("wv"), sp["attn_wv"])
        if cfg.qkv_bias:
            q = tagging.bias_site(q, p["bq"], g("bq"))
            k = tagging.bias_site(k, p["bk"], g("bk"))
            v = tagging.bias_site(v, p["bv"], g("bv"))
        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s, kv, hd)
        v = v.reshape(b, s, kv, hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        win = cfg.sliding_window if window is None else window
        if cache_kv is not None and serve is not None:
            win = serve.resolved_window(cfg)
            out, new_cache = self._attn_serve(q, k, v, cache_kv, cache_len,
                                              serve, win)
        elif cache_kv is not None:
            ck, cv = cache_kv["k"], cache_kv["v"]     # (B, M, KV, hd)
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype),
                                                     cache_len, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype),
                                                     cache_len, axis=1)
            m = ck.shape[1]
            if s == 1 and win and win < m:
                # decode-span clamp: a windowed query sees at most `win`
                # keys, so slice that span out of the max_len-padded cache
                # instead of streaming (and masking) all m slots. start is
                # clamped so the slice stays in bounds before the window
                # fills; q_offset/kv_len are re-based into the slice, which
                # keeps the mask identical to the unclamped call.
                start = jnp.clip(cache_len + 1 - win, 0, m - win)
                cks = jax.lax.dynamic_slice_in_dim(ck, start, win, axis=1)
                cvs = jax.lax.dynamic_slice_in_dim(cv, start, win, axis=1)
                out = attn_lib.attention(q, cks, cvs, causal=True, window=win,
                                         q_offset=cache_len - start,
                                         kv_len=cache_len + 1 - start,
                                         backend=cfg.backend)
            else:
                out = attn_lib.attention(q, ck, cv, causal=True, window=win,
                                         q_offset=cache_len,
                                         kv_len=cache_len + s,
                                         backend=cfg.backend)
            new_cache = {"k": ck, "v": cv}
        else:
            # k/v stay at kv heads (unexpanded): the kernel-eligible route
            # keeps them per-KV-head all the way into the Pallas kernels
            # (GQA layout contract, see repro.kernels.dispatch); the chunked
            # ref path expands inside attention()
            out = attn_lib.attention(q, k, v, causal=True, window=win,
                                     backend=cfg.backend)
            new_cache = None
        o = tagging.dense_site(out.reshape(b, s, h * hd), p["wo"], g("wo"),
                               sp["attn_wo"])
        return o, new_cache

    def _attn_serve(self, q, k, v, cache_kv, cache_len, serve, win):
        """Serving cache paths (``repro.serve``): ring buffer sized to the
        window (fp8 or f32 payload) or the dense-f32 ``window=0`` fallback,
        both decoding through the single-query ``swa_decode`` flash op.

        q (B, S, H, hd); k/v (B, S, KV, hd); cache payload (B, C, KV, hd)
        [+ (B, C, KV) scales for fp8]; cache_len (B,) i32 per-sequence
        positions. S > 1 is prefill (full windowed attention over the
        prompt, then pack the last C tokens into their ring slots); S == 1
        is one decode step (write the token's k/v into slot ``pos % C``,
        then flash-decode over the cache). Returns (out, new_cache)."""
        from repro.kernels import dispatch
        from repro.serve import cache as cache_lib
        cfg = self.cfg
        b, s, h, hd = q.shape
        kv = k.shape[2]
        ck, cv = cache_kv["k"], cache_kv["v"]
        cap = ck.shape[1]
        ring = serve.is_ring(cfg)
        fmt = serve.quant_fmt if ring else None
        backend = serve.backend or cfg.backend
        # the kernel's ring contract needs C == window; the dense fallback
        # (full causal) passes window=0 and masks on position <= pos
        kern_win = cap if ring else 0

        if s > 1:
            out = attn_lib.attention(q, k, v, causal=True, window=win,
                                     backend=backend)
            # pack the cache tail: slot s' receives the latest prompt
            # position p <= S-1 with p % C == s' (negative = unwritten)
            idx = cache_lib.prefill_gather_index(s, cap)
            live = jnp.asarray(idx >= 0)[None, :, None, None]
            sel = jnp.asarray(idx.clip(min=0), jnp.int32)
            gk = jnp.where(live, k[:, sel], 0.0)
            gv = jnp.where(live, v[:, sel], 0.0)
            kp, ks = cache_lib.encode_rows(gk, fmt, serve.scale_mode)
            vp, vs = cache_lib.encode_rows(gv, fmt, serve.scale_mode)
            new_cache = {"k": kp.astype(ck.dtype), "v": vp.astype(cv.dtype)}
            if ks is not None:
                new_cache["k_scale"] = ks
                new_cache["v_scale"] = vs
            return out, new_cache

        # decode: write this token, then flash-decode over the cache
        kp, ks = cache_lib.encode_rows(k, fmt, serve.scale_mode)
        vp, vs = cache_lib.encode_rows(v, fmt, serve.scale_mode)
        slot = (cache_len % cap).astype(jnp.int32)
        ck = cache_lib.write_slot(ck, kp.astype(ck.dtype), slot)
        cv = cache_lib.write_slot(cv, vp.astype(cv.dtype), slot)
        new_cache = {"k": ck, "v": cv}
        ksg = vsg = None
        if ks is not None:
            cks = cache_lib.write_slot(cache_kv["k_scale"], ks, slot)
            cvs = cache_lib.write_slot(cache_kv["v_scale"], vs, slot)
            new_cache["k_scale"] = cks
            new_cache["v_scale"] = cvs
            ksg = cks.transpose(0, 2, 1).reshape(b * kv, cap)
            vsg = cvs.transpose(0, 2, 1).reshape(b * kv, cap)
        # GQA kernel layout (query head c*G + r under KV head c, same
        # grouping as models.attention._to_kernel_layout)
        qg = q[:, 0].reshape(b, kv, h // kv, hd).reshape(b * kv, h // kv, hd)
        kg = ck.transpose(0, 2, 1, 3).reshape(b * kv, cap, hd)
        vg = cv.transpose(0, 2, 1, 3).reshape(b * kv, cap, hd)
        pos = jnp.repeat(cache_len.astype(jnp.int32), kv)
        og = dispatch.swa_decode(qg, kg, vg, pos, window=kern_win,
                                 k_scale=ksg, v_scale=vsg, backend=backend)
        out = og.reshape(b, h, hd)[:, None].astype(q.dtype)
        return out, new_cache

    # ------------------------------------------------------------------
    # block (shared by train forward and decode, cache optional)
    # ------------------------------------------------------------------

    def _block(self, x, p, fs, *, positions, cache=None, cache_len=None,
               serve=None):
        """Returns (y, aux_loss, new_cache)."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        new_cache = {}
        if cfg.block_type == "rwkv":
            h1 = self._norm(x, p["ln1"], "ln1", fs)
            tm_kwargs = {}
            if cache is not None:
                tm_kwargs = dict(last_x=cache["tm_x"], wkv_state=cache["wkv"])
            tm_out = rwkv_lib.time_mix(h1, p["tm"],
                                       _sub(fs, "tm_"), head_dim=cfg.hd,
                                       spec=self.spec,
                                       specs=self._spec_sub("tm_"),
                                       chunk=cfg.scan_chunk,
                                       return_state=cache is not None,
                                       **tm_kwargs)
            if cache is not None:
                tm_out, (new_last, new_wkv) = tm_out
                new_cache["tm_x"] = new_last
                new_cache["wkv"] = new_wkv
            x = x + tm_out
            h2 = self._norm(x, p["ln2"], "ln2", fs)
            cm_kwargs = {}
            if cache is not None:
                cm_kwargs = dict(last_x=cache["cm_x"])
            cm_out = rwkv_lib.channel_mix(h2, p["cm"], _sub(fs, "cm_"),
                                          spec=self.spec,
                                          specs=self._spec_sub("cm_"),
                                          return_state=cache is not None,
                                          **cm_kwargs)
            if cache is not None:
                cm_out, new_cm_x = cm_out
                new_cache["cm_x"] = new_cm_x
            x = x + cm_out
            return x, aux, new_cache

        h1 = self._norm(x, p["ln1"], "ln1", fs)
        kv_sub = (_kv_cache_sub(cache) if cache is not None else None)
        if cfg.block_type == "hymba":
            attn_out, kvc = self._attn(h1, p["attn"], fs, positions=positions,
                                       cache_kv=kv_sub, cache_len=cache_len,
                                       serve=serve)
            ssm_kwargs = {}
            if cache is not None:
                ssm_kwargs = dict(init_state=cache["ssm_h"],
                                  conv_cache=cache["conv"])
            ssm_out = ssm_lib.ssm_branch(h1, p["ssm"], _sub(fs, "ssm_"),
                                         state=cfg.ssm_state, spec=self.spec,
                                         specs=self._spec_sub("ssm_"),
                                         chunk=cfg.scan_chunk,
                                         return_state=cache is not None,
                                         **ssm_kwargs)
            if cache is not None:
                ssm_out, (new_h, new_conv) = ssm_out
                new_cache.update(ssm_h=new_h, conv=new_conv, **kvc)
            # parallel heads: average the two branch outputs (Hymba-style)
            x = x + 0.5 * (attn_out + ssm_out)
        else:
            attn_out, kvc = self._attn(h1, p["attn"], fs, positions=positions,
                                       cache_kv=kv_sub, cache_len=cache_len,
                                       serve=serve)
            if cache is not None:
                new_cache.update(kvc)
            x = x + attn_out

        h2 = self._norm(x, p["ln2"], "ln2", fs)
        if cfg.block_type == "moe":
            y, aux = moe_lib.moe_block(
                h2, p["moe"], _sub(fs, "moe_"), n_experts=cfg.n_experts,
                top_k=cfg.top_k, act=cfg.act,
                capacity_factor=cfg.capacity_factor, spec=self.spec,
                specs=self._spec_sub("moe_"), buf_hook=self.moe_hook)
            x = x + y
        else:
            x = x + mlp(h2, p["mlp"], _sub(fs, "mlp_"), act=cfg.act,
                        gated=cfg.gated_mlp, spec=self.spec,
                        specs=self._spec_sub("mlp_"))
        return x, aux, new_cache

    # ------------------------------------------------------------------
    # embedding / frontend
    # ------------------------------------------------------------------

    def _embed_inputs(self, params, batch, fs):
        """Returns (h (B, S_total, d), positions (S_total,), text_start)."""
        cfg = self.cfg
        tok = batch["tokens"]
        h_text = tagging.embed_site(tok, params["embed"]["table"],
                                    fs.get("embed") if fs else None,
                                    self.embed_spec)
        if cfg.frontend == "vision":
            pe = batch["pixel_embeds"].astype(cfg.dtype)  # (B, Tf, fd)
            img = tagging.dense_site(pe, params["proj"]["w"],
                                     fs.get("proj") if fs else None, self.spec)
            h = jnp.concatenate([img, h_text], axis=1)
            n_front = pe.shape[1]
        else:
            h = h_text
            n_front = 0
        positions = jnp.arange(h.shape[1])
        return h, positions, n_front

    # ------------------------------------------------------------------
    # forward / loss
    # ------------------------------------------------------------------

    def forward(self, params, batch, fstats=None):
        cfg = self.cfg
        h, positions, n_front = self._embed_inputs(params, batch, fstats)
        fs_blk = _blk_stats(fstats)

        def body(carry, xs):
            x, aux = carry
            if fs_blk is None:
                p = xs
                fs_l = None
            else:
                p, fs_l = xs
            y, a, _ = self._block(x, p, fs_l, positions=positions)
            if self.act_hook is not None:
                y = self.act_hook(y)
            return (y, aux + a), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        xs = params["blocks"] if fs_blk is None else (params["blocks"], fs_blk)
        (h, aux_loss), _ = jax.lax.scan(body_fn, (h, jnp.zeros((), jnp.float32)),
                                        xs)
        h = self._norm(h, params["final_norm"], "final_norm", fstats)
        logits = tagging.dense_site(h, params["head"]["w"],
                                    fstats.get("head") if fstats else None,
                                    self.head_spec)
        return logits, {"aux_loss": aux_loss / cfg.n_layers,
                        "n_front": n_front}

    def loss(self, params, fstats, batch):
        cfg = self.cfg
        logits, aux = self.forward(params, batch, fstats)
        n_front = aux["n_front"]
        if n_front:
            logits_text = logits[:, n_front:, :]
        else:
            logits_text = logits
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits_text.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("mask")
        if mask is not None:
            denom = jnp.maximum(mask.sum(), 1.0)
            loss = (nll * mask).sum() / denom
        else:
            loss = nll.mean()
        total = loss + cfg.aux_loss_coef * aux["aux_loss"]
        return total, {"logits": logits_text, "nll": loss,
                       "aux_loss": aux["aux_loss"]}

    # ------------------------------------------------------------------
    # serving: cache init / prefill / single-token decode
    # ------------------------------------------------------------------

    def init_cache(self, batch_size: int, max_len: int,
                   dtype=None, *, serve=None) -> dict:
        cfg = self.cfg
        dtype = dtype or cfg.dtype
        L, b = cfg.n_layers, batch_size
        if serve is not None:
            return self._init_serve_cache(b, max_len, serve)
        c: dict = {"len": jnp.zeros((), jnp.int32)}
        if cfg.block_type in ("dense", "moe", "hymba"):
            kvshape = (L, b, max_len, cfg.n_kv_heads, cfg.hd)
            c["k"] = jnp.zeros(kvshape, dtype)
            c["v"] = jnp.zeros(kvshape, dtype)
        if cfg.block_type == "hymba":
            di = cfg.ssm_expand * cfg.d_model
            c["ssm_h"] = jnp.zeros((L, b, di, cfg.ssm_state), jnp.float32)
            c["conv"] = jnp.zeros((L, b, 3, di), dtype)
        if cfg.block_type == "rwkv":
            h = cfg.d_model // cfg.hd
            c["tm_x"] = jnp.zeros((L, b, 1, cfg.d_model), dtype)
            c["cm_x"] = jnp.zeros((L, b, 1, cfg.d_model), dtype)
            c["wkv"] = jnp.zeros((L, b, h, cfg.hd, cfg.hd), jnp.float32)
        return c

    def _init_serve_cache(self, b: int, max_len: int, serve) -> dict:
        """Serving cache (``repro.serve``): ring buffer sized to the window
        (fp8 payload + per-row f32 scales, or f32), or the dense-f32
        fallback when the resolved window is 0 (full causal — nothing is
        evictable, so a ring cannot be smaller than max_len anyway).
        ``len`` is a per-sequence (B,) position vector so the continuous
        batcher can hold sequences at different depths in one cache."""
        from repro.serve import cache as cache_lib
        cfg = self.cfg
        if cfg.block_type not in ("dense", "moe"):
            raise NotImplementedError(
                f"serve caches cover attention-only blocks (dense/moe); "
                f"got block_type={cfg.block_type!r}")
        win = serve.resolved_window(cfg)
        ring = serve.is_ring(cfg)
        if not ring and win:
            raise ValueError(
                "serve kv_cache='dense' supports window == 0 only (a "
                "windowed dense decode belongs to the legacy serve=None "
                "path or the ring cache)")
        cap = cache_lib.ring_capacity(win, max_len) if ring else max_len
        L, kvh, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
        c: dict = {"len": jnp.zeros((b,), jnp.int32)}
        fmt = serve.quant_fmt if ring else None
        if fmt is None:
            c["k"] = jnp.zeros((L, b, cap, kvh, hd), jnp.float32)
            c["v"] = jnp.zeros((L, b, cap, kvh, hd), jnp.float32)
        else:
            from repro.quant import quant
            pdt = quant.FORMATS[fmt]
            c["k"] = jnp.zeros((L, b, cap, kvh, hd), pdt)
            c["v"] = jnp.zeros((L, b, cap, kvh, hd), pdt)
            c["k_scale"] = jnp.zeros((L, b, cap, kvh), jnp.float32)
            c["v_scale"] = jnp.zeros((L, b, cap, kvh), jnp.float32)
        return c

    def decode_step(self, params, cache, tokens: jax.Array, *, serve=None):
        """tokens: (B,) -> (logits (B, V), new_cache). One decode position.

        With ``serve`` (a :class:`repro.serve.ServeConfig`) the cache is the
        serving layout from :meth:`init_cache` — per-sequence ``len`` (B,),
        ring/fp8 payloads — and attention runs the ``swa_decode`` flash op;
        without it, the seed's dense-cache path (scalar ``len``)."""
        cfg = self.cfg
        h = tagging.embed_site(tokens[:, None], params["embed"]["table"],
                               None, self.embed_spec)
        pos = cache["len"]
        if serve is not None:
            positions = pos[:, None]               # (B, 1) per-seq rope
        else:
            positions = pos + jnp.arange(1)

        layer_cache = {k: v for k, v in cache.items() if k != "len"}

        def body(x, xs):
            p, c = xs
            y, _, new_c = self._block(x, p, None, positions=positions,
                                      cache=c, cache_len=pos, serve=serve)
            return y, new_c

        h, new_layer_cache = jax.lax.scan(body, h,
                                          (params["blocks"], layer_cache))
        h = self._norm(h, params["final_norm"], "final_norm", None)
        logits = tagging.dense_site(h, params["head"]["w"], None,
                                    self.head_spec)
        new_cache = dict(new_layer_cache)
        new_cache["len"] = pos + 1
        return logits[:, 0, :], new_cache

    def prefill(self, params, batch, max_len: int, *, serve=None):
        """Forward + cache fill (used by the serving example)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        cache = self.init_cache(b, max_len, serve=serve)
        h, positions, n_front = self._embed_inputs(params, batch, None)

        layer_cache = {k: v for k, v in cache.items() if k != "len"}
        len0 = (jnp.zeros((b,), jnp.int32) if serve is not None
                else jnp.zeros((), jnp.int32))

        def body(x, xs):
            p, c = xs
            y, _, new_c = self._block(x, p, None, positions=positions,
                                      cache=c, cache_len=len0, serve=serve)
            return y, new_c

        h, new_layer_cache = jax.lax.scan(body, h,
                                          (params["blocks"], layer_cache))
        h = self._norm(h, params["final_norm"], "final_norm", None)
        logits = tagging.dense_site(h, params["head"]["w"], None,
                                    self.head_spec)
        cache = dict(new_layer_cache)
        slen = jnp.asarray(h.shape[1], jnp.int32)
        cache["len"] = (jnp.full((b,), slen) if serve is not None else slen)
        return logits, cache

    # ------------------------------------------------------------------
    # SP-NGD wiring: site registry, factor templates, token counts
    # ------------------------------------------------------------------

    def site_infos(self) -> dict[str, SiteInfo]:
        cfg = self.cfg
        L = (cfg.n_layers,)
        d, h, kv, hd, ff, v = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.hd, cfg.d_ff, cfg.vocab)
        infos: dict[str, SiteInfo] = {
            "embed": SiteInfo("embed", "embed/table", v, d, self.embed_spec),
            "head": SiteInfo("dense", "head/w", d, v, self.head_spec),
            "final_norm": SiteInfo("scale_bias", "final_norm/gamma", d, d),
        }
        if cfg.frontend == "vision":
            infos["proj"] = SiteInfo("dense", "proj/w", cfg.frontend_dim, d,
                                     self.spec)

        def blk(name, kind, path, d_in, d_out, spec=None, lead=L, beta=None):
            eff = spec or self.specs.get(name, self.spec)
            infos[f"blk/{name}"] = SiteInfo(kind, f"blocks/{path}", d_in,
                                            d_out, eff,
                                            lead=lead, beta_param=beta)

        norm_beta = ("blocks/ln1/beta" if cfg.norm == "layernorm"
                     or cfg.block_type == "rwkv" else None)
        blk("ln1", "scale_bias", "ln1/gamma", d, d,
            beta="blocks/ln1/beta" if norm_beta else None)
        blk("ln2", "scale_bias", "ln2/gamma", d, d,
            beta="blocks/ln2/beta" if norm_beta else None)

        if cfg.block_type in ("dense", "moe", "hymba"):
            blk("attn_wq", "dense", "attn/wq", d, h * hd)
            blk("attn_wk", "dense", "attn/wk", d, kv * hd)
            blk("attn_wv", "dense", "attn/wv", d, kv * hd)
            blk("attn_wo", "dense", "attn/wo", h * hd, d)
            if cfg.qkv_bias:
                blk("attn_bq", "bias", "attn/bq", 0, h * hd)
                blk("attn_bk", "bias", "attn/bk", 0, kv * hd)
                blk("attn_bv", "bias", "attn/bv", 0, kv * hd)
        if cfg.block_type in ("dense", "hymba"):
            blk("mlp_up", "dense", "mlp/up", d, ff)
            if cfg.gated_mlp:
                blk("mlp_gate", "dense", "mlp/gate", d, ff)
            blk("mlp_down", "dense", "mlp/down", ff, d)
        if cfg.block_type == "moe":
            E = cfg.n_experts
            blk("moe_router", "dense", "moe/router", d, E)
            blk("moe_we_up", "grouped", "moe/we_up", d, ff, lead=L + (E,))
            blk("moe_we_gate", "grouped", "moe/we_gate", d, ff, lead=L + (E,))
            blk("moe_we_down", "grouped", "moe/we_down", ff, d, lead=L + (E,))
            if cfg.n_shared_experts:
                sf = cfg.n_shared_experts * ff
                blk("moe_sh_up", "dense", "moe/sh_up", d, sf)
                blk("moe_sh_gate", "dense", "moe/sh_gate", d, sf)
                blk("moe_sh_down", "dense", "moe/sh_down", sf, d)
        if cfg.block_type == "hymba":
            di = cfg.ssm_expand * d
            dt_rank = max(1, d // 16)
            blk("ssm_in_proj", "dense", "ssm/in_proj", d, 2 * di)
            blk("ssm_xdb", "dense", "ssm/xdb", di, dt_rank + 2 * cfg.ssm_state)
            blk("ssm_dt_proj", "dense", "ssm/dt_proj", dt_rank, di)
            blk("ssm_out_proj", "dense", "ssm/out_proj", di, d)
        if cfg.block_type == "rwkv":
            lora_r = 32
            for nm in ("wr", "wk", "wv", "wg", "wo"):
                blk(f"tm_{nm}", "dense", f"tm/{nm}", d, d)
            blk("tm_w_lora_a", "dense", "tm/w_lora_a", d, lora_r)
            blk("tm_w_lora_b", "dense", "tm/w_lora_b", lora_r, d)
            for nm in ("mu_r", "mu_k", "mu_v", "mu_w", "mu_g"):
                blk(f"tm_{nm}", "scale_bias", f"tm/{nm}", d, d)
            blk("tm_ln_scale", "scale_bias", "tm/ln_scale", d, d)
            blk("cm_wk", "dense", "cm/wk", d, ff)
            blk("cm_wv", "dense", "cm/wv", ff, d)
            blk("cm_wr", "dense", "cm/wr", d, d)
            blk("cm_cm_mu_k", "scale_bias", "cm/mu_k", d, d)
            blk("cm_cm_mu_r", "scale_bias", "cm/mu_r", d, d)
        return infos

    def fstats(self) -> dict:
        """Zero factor-statistic accumulators, flat {family: stats}."""
        out = {}
        for fam, info in self.site_infos().items():
            if info.kind in ("dense", "grouped"):
                out[fam] = tagging.make_stats(info.spec, info.d_in, info.d_out,
                                              lead=info.lead)
            elif info.kind == "embed":
                out[fam] = tagging.make_embed_stats(info.d_in, info.d_out,
                                                    info.spec, lead=info.lead)
            elif info.kind == "bias":
                out[fam] = tagging.make_bias_stats(info.d_out, lead=info.lead)
            elif info.kind == "scale_bias":
                out[fam] = tagging.make_scale_bias_stats(info.d_out,
                                                         lead=info.lead)
        return out

    def site_counts(self, batch) -> dict:
        cfg = self.cfg
        tok = batch["tokens"]
        b = tok.shape[0]
        s_text = tok.shape[1] if tok.ndim > 1 else 1
        n_front = cfg.frontend_tokens if cfg.frontend == "vision" else 0
        n_total = b * (s_text + n_front)
        mask = batch.get("mask")
        n_loss = mask.sum() if mask is not None else jnp.asarray(
            b * s_text, jnp.float32)
        counts = {}
        for fam in self.fstats():
            if fam == "embed":
                counts[fam] = (b * s_text, n_loss)
            elif fam == "proj":
                counts[fam] = (b * n_front, n_loss)
            else:
                counts[fam] = (n_total, n_loss)
        return counts

    def site_rows(self, batch) -> dict:
        """``{family: ids}``: the rows of each embedding family's gradient
        that a step on ``batch`` can make nonzero."""
        return {"embed": batch["tokens"]}

    # ------------------------------------------------------------------
    # dry-run input stand-ins
    # ------------------------------------------------------------------

    def input_specs(self, shape: InputShape) -> dict:
        """ShapeDtypeStruct batch for lowering (no allocation)."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        if shape.kind == "train":
            batch = {"tokens": sds((b, s), i32), "labels": sds((b, s), i32)}
            if cfg.frontend == "vision":
                batch["pixel_embeds"] = sds((b, cfg.frontend_tokens,
                                             cfg.frontend_dim), jnp.bfloat16)
            return batch
        if shape.kind == "prefill":
            batch = {"tokens": sds((b, s), i32)}
            if cfg.frontend == "vision":
                batch["pixel_embeds"] = sds((b, cfg.frontend_tokens,
                                             cfg.frontend_dim), jnp.bfloat16)
            return batch
        # decode: one token against a cache of length s
        cache = jax.eval_shape(lambda: self.init_cache(b, s))
        return {"tokens": sds((b,), i32), "cache": cache}


def _kv_cache_sub(cache: dict) -> dict:
    """KV-cache entries of a layer cache (payloads + optional fp8 scales)."""
    return {k: cache[k] for k in ("k", "v", "k_scale", "v_scale")
            if k in cache}


def _sub(fs: Optional[dict], prefix: str) -> Optional[dict]:
    """Sub-view of a block's stats dict by key prefix."""
    if fs is None:
        return None
    return {k[len(prefix):]: v for k, v in fs.items() if k.startswith(prefix)}


def _blk_stats(fstats: Optional[dict]) -> Optional[dict]:
    """Block families ("blk/<name>") -> scan xs dict {"<name>": stats}."""
    if fstats is None:
        return None
    return {k[4:]: v for k, v in fstats.items() if k.startswith("blk/")}
