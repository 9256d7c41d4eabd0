"""The work counts against hand counts of the two configurations."""

import json
import os

import pytest

from chipbench import harness, work
from chipbench.families import conv, lm

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_lm_model_flops_per_token():
    # 6 x (4 x (4 x 2560^2 + 3 x 2560 x 6912) + 2560 x 151936) matmul
    # weights, plus causal attention: 3 x 4 layers x 2 matmuls x 2 x 20
    # heads x 128 x 1025 / 2 keys on average
    cfg = config("qwen1_5_4b-l4")
    blocks = 4 * (4 * 2560 ** 2 + 3 * 2560 * 6912)
    attn = 3 * 4 * 2 * 2 * 20 * 128 * 1025 / 2
    hand = 6 * (blocks + 2560 * 151936) + attn
    got = lm.model_flops_per_item(cfg, {"seq_len": 1024})
    assert got == pytest.approx(hand, rel=1e-12)
    assert got == pytest.approx(4.30e9, rel=2e-3)


def test_conv_model_flops_per_image():
    # forward: stem 27 x 64 at 32x32; stage 1: 4 convs 576 x 64 at 32x32;
    # stages 2-4: a strided 3x3, a 1x1 projection and three 3x3 at the
    # halved size, each 37.7 + 4.2 + 3 x 75.5 MFLOP
    cfg = config("resnet18-cifar")
    fwd = 2 * 27 * 64 * 1024 + 4 * 2 * 576 * 64 * 1024
    for cin, cout, hw in ((64, 128, 16), (128, 256, 8), (256, 512, 4)):
        pos = hw * hw
        fwd += 2 * pos * (9 * cin * cout + cin * cout + 3 * 9 * cout * cout)
    fwd += 2 * 512 * 10
    got = conv.model_flops_per_item(cfg, {})
    assert got == pytest.approx(3 * fwd)
    assert got == pytest.approx(3.33e9, rel=2e-3)


def test_precond_work_lm():
    # blocks of ceil(d / ceil(d / 2048)): 2560 -> 2 x 1280, 6912 -> 4 x 1728
    cfg = config("qwen1_5_4b-l4")
    b1, b2, V = 1280, 1728, 151936
    qkvo = 4 * (2 * 2 * b1 ** 2 * 2560 + 2 * 2560 * 2 * b1 ** 2)
    upgate = 2 * (2 * 2 * b1 ** 2 * 6912 + 2 * 2560 * 4 * b2 ** 2)
    down = 2 * 4 * b2 ** 2 * 2560 + 2 * 6912 * 2 * b1 ** 2
    embed_head = 2 * (2 * V * 2 * b1 ** 2)
    hand = 4 * (qkvo + upgate + down) + embed_head
    w = work.precond_work(lm.dense_sites(cfg), 2048)
    assert w.flops == pytest.approx(hand)


def test_factor_sum_work_conv():
    # one 1x1 conv, 2 images of 4 positions, cin 3, cout 5: A over 3
    # features and G over 5, n = 8 rows each; a head of 5 -> 10
    cfg = {"name": "tiny", "block": "basic", "image_size": 2,
           "in_channels": 3, "widths": [5], "blocks_per_stage": 0,
           "num_classes": 10, "optimizer": {"kfac_max_dim": 2048}}
    w = conv.factor_sum_work(cfg, {"images": 2, "accum": 1})
    stem = 8 * 27 * 28 + 8 * 5 * 6
    head = 2 * 5 * 6 + 2 * 10 * 11
    assert w.flops == stem + head


def test_roofline_share_takes_the_binding_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share(work.Work(100, 1), 2.0, peaks) == 50.0
    assert work.roofline_share(work.Work(1, 100), 20.0, peaks) == 50.0


def test_conv_counts_refuse_other_blocks():
    cfg = dict(config("resnet18-cifar"), block="bottleneck")
    with pytest.raises(SystemExit, match="basic blocks only"):
        conv.model_flops_per_item(cfg, {})


@pytest.mark.parametrize("chips", [1, 4])
def test_mfu_over_the_peak_of_every_chip(chips):
    """One chip: the value before ``chips`` was read, bit for bit; four:
    the same items over four chips' peak."""
    import types
    from chipbench import readers
    cfg = config("qwen1_5_4b-l4")
    traffic = {"seqs": 2, "accum": 1, "seq_len": 1024}
    ctx = types.SimpleNamespace(
        family=lm, config=cfg, traffic=traffic, items=15 * 2048 * chips,
        window_s=4.418, chips=chips, peaks={"bf16_flops_per_s": 1.97e14})
    one_chip = 100.0 * lm.model_flops_per_item(cfg, traffic) * ctx.items \
        / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
    assert readers.mfu(ctx) == one_chip / chips


@pytest.mark.parametrize("chips", [1, 4])
def test_precond_work_is_one_chips_share(chips):
    import types
    from chipbench import readers
    cfg = config("qwen1_5_4b-l4")
    ctx = types.SimpleNamespace(family=lm, config=cfg, chips=chips)
    whole = work.precond_work(lm.dense_sites(cfg), 2048)
    share = readers.precond_work(ctx)
    assert (share.flops, share.bytes) == (whole.flops / chips,
                                          whole.bytes / chips)


class _Device:
    def __init__(self, id, in_use, reserved):
        self.id = id
        self._stats = {"peak_bytes_in_use": in_use,
                       "peak_bytes_reserved": reserved}

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("chips,peak", [(1, 10 + 3), (4, 12 + 5)])
def test_device_peak_bytes_is_the_fullest_chip(monkeypatch, chips, peak):
    import jax
    devices = [_Device(0, 10, 3), _Device(1, 12, 5), _Device(2, 11, 1),
               _Device(3, 9, 0), _Device(4, 100, 0)]
    monkeypatch.setattr(jax, "devices", lambda: devices)
    assert harness.device_peak_bytes(chips) == peak


@pytest.mark.parametrize("metric,reader", [
    ("spngd.stage3_share.lm", "spngd.stage3_share.py"),
    ("comm.exposed_share.lm", "comm.exposed_share.py"),
    ("step.mfu.lm", "step.mfu.py"), ("step.mfu.conv", "step.mfu.py"),
    ("block_precond_roofline.lm", "block_precond_roofline.lm.py"),
    ("setup_s", "setup_s.py")])
def test_metric_reader_by_name_or_base_name(metric, reader):
    path = harness.reader_path(metric)
    assert os.path.basename(path) == reader and os.path.exists(path)


def test_every_metric_has_a_reader():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(harness.reader_path(m["name"])), m["name"]


def test_reference_patch_gram_in_row_blocks(monkeypatch):
    """The conv reference sums its patch Grams over blocks of images; the
    blocks add up to the Gram of the whole batch."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import convnet
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 6, 6, 3), jnp.float32)
    whole = convnet._patch_gram(x, 3, 2, 16, None)
    monkeypatch.setattr(convnet, "ROW_BLOCK", 2)
    blocked = convnet._patch_gram(x, 3, 2, 16, None)
    assert blocked.shape == whole.shape == (2, 14, 14)
    assert jnp.allclose(blocked, whole, rtol=1e-5, atol=1e-5)
