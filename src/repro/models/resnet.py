"""Small ResNet (CIFAR-scale) — the paper-faithful substrate.

The paper trains ResNet-50/ImageNet; this scaled-down ResNet exercises the
*exact* technique set at CPU-testable scale: conv-layer K-FAC via im2col
(Eq. 10-11), BatchNorm scale/bias with unit-wise 2x2 Fisher (Eq. 15-17),
running mixup + random erasing (§6.1), polynomial decay + coupled momentum
(§6.2), and weight norm rescaling (§6.3). BatchNorm uses in-batch statistics
(no moving averages) as in the large-batch training literature the paper
builds on.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import tagging
from repro.core.fisher import SiteInfo
from repro.core.tagging import FactorSpec


@dataclasses.dataclass(frozen=True)
class ConvNetConfig:
    n_classes: int = 10
    widths: tuple = (16, 32, 64)
    blocks_per_stage: int = 2
    in_channels: int = 3
    kfac_max_dim: int = 2048
    bn_fisher: str = "unit"      # "unit" (Eq. 15) | "full" (Fig. 5 baseline)


def _batchnorm(x, gamma, beta, stats, eps=1e-5):
    mu = x.mean((0, 1, 2), keepdims=True)
    var = x.var((0, 1, 2), keepdims=True)
    xhat = (x - mu) * jax.lax.rsqrt(var + eps)
    return tagging.scale_bias_site(xhat, gamma, beta, stats, spatial=2)


class ConvNet:
    def __init__(self, cfg: ConvNetConfig = ConvNetConfig()):
        self.cfg = cfg
        self.spec = FactorSpec(max_dim=cfg.kfac_max_dim)

    # ---- init ----

    def init(self, key) -> dict:
        cfg = self.cfg
        from repro.models.layers import he_normal
        params = {}
        k0, key = jax.random.split(key)
        params["stem"] = {
            "w": he_normal(k0, (3, 3, cfg.in_channels, cfg.widths[0]),
                           fan_in=9 * cfg.in_channels),
            "gamma": jnp.ones(cfg.widths[0]), "beta": jnp.zeros(cfg.widths[0])}
        c_in = cfg.widths[0]
        for si, w in enumerate(cfg.widths):
            for bi in range(cfg.blocks_per_stage):
                name = f"s{si}b{bi}"
                k1, k2, k3, key = jax.random.split(key, 4)
                stride = 2 if (bi == 0 and si > 0) else 1
                blk = {
                    "w1": he_normal(k1, (3, 3, c_in, w), fan_in=9 * c_in),
                    "g1": jnp.ones(w), "b1": jnp.zeros(w),
                    "w2": he_normal(k2, (3, 3, w, w), fan_in=9 * w),
                    "g2": jnp.ones(w), "b2": jnp.zeros(w),
                }
                if stride != 1 or c_in != w:
                    blk["wskip"] = he_normal(k3, (1, 1, c_in, w), fan_in=c_in)
                params[name] = blk
                c_in = w
        kh, key = jax.random.split(key)
        params["head"] = {"w": he_normal(kh, (c_in, cfg.n_classes))}
        return params

    # ---- forward ----

    def forward(self, params, x, fstats=None):
        cfg = self.cfg
        g = lambda n: (fstats.get(n) if fstats else None)
        h = tagging.conv_site(x, params["stem"]["w"], g("stem_w"),
                              spec=self.spec)
        h = _batchnorm(h, params["stem"]["gamma"], params["stem"]["beta"],
                       g("stem_bn"))
        h = jax.nn.relu(h)
        c_in = cfg.widths[0]
        for si, w in enumerate(cfg.widths):
            for bi in range(cfg.blocks_per_stage):
                name = f"s{si}b{bi}"
                p = params[name]
                stride = 2 if (bi == 0 and si > 0) else 1
                y = tagging.conv_site(h, p["w1"], g(f"{name}_w1"),
                                      stride=stride, spec=self.spec)
                y = _batchnorm(y, p["g1"], p["b1"], g(f"{name}_bn1"))
                y = jax.nn.relu(y)
                y = tagging.conv_site(y, p["w2"], g(f"{name}_w2"),
                                      spec=self.spec)
                y = _batchnorm(y, p["g2"], p["b2"], g(f"{name}_bn2"))
                if "wskip" in p:
                    h = tagging.conv_site(h, p["wskip"], g(f"{name}_wskip"),
                                          stride=stride, spec=self.spec)
                h = jax.nn.relu(h + y)
                c_in = w
        h = h.mean((1, 2))                          # global average pool
        logits = tagging.dense_site(h, params["head"]["w"], g("head"),
                                    self.spec)
        return logits

    def loss(self, params, fstats, batch):
        logits = self.forward(params, batch["images"], fstats)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        if labels.ndim == 1:                        # hard labels
            nll = -jnp.take_along_axis(logp, labels[:, None], -1).mean()
        else:                                       # soft labels (mixup)
            nll = -(labels * logp).sum(-1).mean()
        return nll, {"logits": logits}

    # ---- SP-NGD wiring ----

    def site_infos(self) -> dict[str, SiteInfo]:
        cfg = self.cfg
        infos = {
            "stem_w": SiteInfo("conv", "stem/w", 9 * cfg.in_channels,
                               cfg.widths[0], self.spec, ksize=3),
            "stem_bn": SiteInfo("scale_bias", "stem/gamma", cfg.widths[0],
                                cfg.widths[0], beta_param="stem/beta"),
            "head": SiteInfo("dense", "head/w", cfg.widths[-1],
                             cfg.n_classes, self.spec),
        }
        c_in = cfg.widths[0]
        for si, w in enumerate(cfg.widths):
            for bi in range(cfg.blocks_per_stage):
                nm = f"s{si}b{bi}"
                infos[f"{nm}_w1"] = SiteInfo("conv", f"{nm}/w1", 9 * c_in, w,
                                             self.spec, ksize=3)
                infos[f"{nm}_bn1"] = SiteInfo("scale_bias", f"{nm}/g1", w, w,
                                              beta_param=f"{nm}/b1")
                infos[f"{nm}_w2"] = SiteInfo("conv", f"{nm}/w2", 9 * w, w,
                                             self.spec, ksize=3)
                infos[f"{nm}_bn2"] = SiteInfo("scale_bias", f"{nm}/g2", w, w,
                                              beta_param=f"{nm}/b2")
                if (bi == 0 and si > 0) or c_in != w:
                    infos[f"{nm}_wskip"] = SiteInfo("conv", f"{nm}/wskip",
                                                    c_in, w, self.spec,
                                                    ksize=1)
                c_in = w
        return infos

    def fstats(self) -> dict:
        full = self.cfg.bn_fisher == "full"
        out = {}
        for fam, info in self.site_infos().items():
            if info.kind in ("dense", "conv"):
                out[fam] = tagging.make_stats(info.spec, info.d_in,
                                              info.d_out, lead=info.lead)
            elif info.kind == "scale_bias":
                out[fam] = tagging.make_scale_bias_stats(info.d_out,
                                                         lead=info.lead,
                                                         full=full)
        return out

    def site_counts(self, batch) -> dict:
        """Conv sites: n_a = B*Ho*Wo (im2col tokens), n_g = B (samples)."""
        b, hh, ww, _ = batch["images"].shape
        counts = {}
        c_in = self.cfg.widths[0]
        # stem at full resolution
        counts["stem_w"] = (b * hh * ww, b)
        counts["stem_bn"] = (b, b)
        res = {0: (hh, ww)}
        h, w_ = hh, ww
        for si, w in enumerate(self.cfg.widths):
            for bi in range(self.cfg.blocks_per_stage):
                nm = f"s{si}b{bi}"
                if bi == 0 and si > 0:
                    h, w_ = -(-h // 2), -(-w_ // 2)
                counts[f"{nm}_w1"] = (b * h * w_, b)
                counts[f"{nm}_bn1"] = (b, b)
                counts[f"{nm}_w2"] = (b * h * w_, b)
                counts[f"{nm}_bn2"] = (b, b)
                counts[f"{nm}_wskip"] = (b * h * w_, b)
        counts["head"] = (b, b)
        return {k: v for k, v in counts.items() if k in self.fstats()}

    def site_rows(self, batch) -> dict:
        """No embedding family: every gradient row is preconditioned."""
        return {}
