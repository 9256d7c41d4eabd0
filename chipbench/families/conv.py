"""The system under test for convolutional configurations.

repro's ``ConvNet`` (basic-block ResNet) trained by ``SPNGD`` through the
jitted pair ``make_train_step`` / ``make_fast_step``: conv K-FAC on im2col
patches (paper Eq. 10-11) and the unit-wise BatchNorm Fisher (Eq. 15-17).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.work import Work, syrk

ITEM = "images"


def build_model(config: dict):
    from repro.models.resnet import ConvNet, ConvNetConfig
    _basic_blocks(config)
    return ConvNet(ConvNetConfig(
        n_classes=config["num_classes"], widths=tuple(config["widths"]),
        blocks_per_stage=config["blocks_per_stage"],
        in_channels=config["in_channels"],
        kfac_max_dim=config["optimizer"]["kfac_max_dim"], bn_fisher="unit"))


def rows_per_step(traffic: dict) -> int:
    return traffic["images"] * traffic["accum"]


def items_per_step(traffic: dict) -> int:
    return rows_per_step(traffic)


def leaf_init(path: str, shape: tuple):
    name = path.rsplit("/", 1)[-1]
    if name in ("gamma", "g1", "g2"):
        return ("ones",)
    if name in ("beta", "b1", "b2"):
        return ("normal", 0.02)
    return ("normal", (2.0 / math.prod(shape[:-1])) ** 0.5)   # He normal


def make_batches(key, traffic: dict, config: dict, n: int) -> list:
    """``n`` distinct batches of standard-normal images and uniform labels."""
    rows, hw, c = (rows_per_step(traffic), config["image_size"],
                   config["in_channels"])
    ki, kl = jax.random.split(key)
    images = jax.random.normal(ki, (n, rows, hw, hw, c), jnp.float32)
    labels = jax.random.randint(kl, (n, rows), 0, config["num_classes"],
                                jnp.int32)
    return [{"images": images[i], "labels": labels[i]} for i in range(n)]


# ---------------------------------------------------------------------------
# work counted from the configuration (chipbench/work.py)
# ---------------------------------------------------------------------------

def _basic_blocks(cfg: dict) -> None:
    if cfg["block"] != "basic":
        raise SystemExit(f"{cfg['name']}: ConvNet has basic blocks only, "
                         f"not {cfg['block']!r}")


def conv_sites(cfg: dict) -> list[tuple[str, int, int, int, int, int]]:
    """(site, kernel, cin, cout, output positions per image, stride) of
    every convolution of a basic-block ResNet."""
    _basic_blocks(cfg)
    hw, c_in = cfg["image_size"], cfg["in_channels"]
    out = [("stem", 3, c_in, cfg["widths"][0], hw * hw, 1)]
    c_in = cfg["widths"][0]
    for si, w in enumerate(cfg["widths"]):
        for bi in range(cfg["blocks_per_stage"]):
            stride = 2 if (bi == 0 and si > 0) else 1
            o = -(-hw // stride)
            out.append((f"s{si}b{bi}_w1", 3, c_in, w, o * o, stride))
            out.append((f"s{si}b{bi}_w2", 3, w, w, o * o, 1))
            if stride != 1 or c_in != w:
                out.append((f"s{si}b{bi}_wskip", 1, c_in, w, o * o, stride))
            c_in, hw = w, o
    return out


def model_flops_per_item(cfg: dict, traffic: dict) -> float:
    """Forward and backward FLOPs of one trained image."""
    fwd = sum(2 * k * k * cin * cout * pos
              for _, k, cin, cout, pos, _ in conv_sites(cfg))
    fwd += 2 * cfg["widths"][-1] * cfg["num_classes"]
    return 3 * fwd


def dense_sites(cfg: dict) -> list[tuple[str, int, int, int, bool, bool]]:
    """Every factored site as a matmul: a conv's im2col patches, the
    head."""
    out = [(name, 1, k * k * cin, cout, True, True)
           for name, k, cin, cout, _, _ in conv_sites(cfg)]
    out.append(("head", 1, cfg["widths"][-1], cfg["num_classes"], True,
                True))
    return out


def factor_sum_work(cfg: dict, traffic: dict) -> Work:
    """Every factor_sum of one capture step: each conv's A over its im2col
    patches and G over its output cotangents, the head's A and G; f32
    inputs."""
    md, images = cfg["optimizer"]["kfac_max_dim"], rows_per_step(traffic)
    w = Work()
    for _, k, cin, cout, pos, _ in conv_sites(cfg):
        n = images * pos
        w = w + syrk(n, k * k * cin, md) + syrk(n, cout, md)
    return w + syrk(images, cfg["widths"][-1], md) + \
        syrk(images, cfg["num_classes"], md)
