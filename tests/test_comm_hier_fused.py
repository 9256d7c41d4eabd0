"""PR-6 Stage-3 additions: hierarchical two-level reduce + fused wire path.

  * hier_split topology math + CommConfig devices_per_host validation;
  * hier reduce parity vs dense on a simulated 2-host x 4-device mesh
    (both levels active: intra-host f32 psum_scatter, inter-host fp8 ring);
  * per-level wire-byte ledger: wire_stat_level_bytes hand-check, reducer
    breakdown, IntervalController intra/inter columns + checkpoint codec,
    and the acceptance bound inter-host <= 0.2x dense f32;
  * fused capture: factor_sum_wire ref-vs-pallas bit parity on the scales,
    the lookup spy proving the SYRK call site emits wire-format payloads
    with ZERO separate ring_hop_pack dispatches, and 20-step e2e loss
    parity with dense under both jit and shard_map;
  * the accum>1 + wire-template guard.
"""
import os

import pytest

if "PYTEST_XDIST" not in os.environ and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.comm import (CommConfig, FactorReducer, hier_split,
                        make_comm_config, wire_stat_bytes,
                        wire_stat_level_bytes)
from repro.core.stale import IntervalController, sym_packed_bytes
from repro.kernels import dispatch
from repro.launch.mesh import make_mesh
from repro.quant import encoded_nbytes

needs_devices = pytest.mark.skipif(len(jax.devices()) < 8,
                                   reason="needs 8 virtual devices")


# ---------------------------------------------------------------------------
# topology + accounting (host-side, no devices needed)
# ---------------------------------------------------------------------------

def test_hier_config_and_split():
    # hier defaults to the fp8 wire like ring_fp8
    assert make_comm_config("hier").wire_dtype == "fp8_e4m3"
    assert make_comm_config("fused").wire_dtype == "fp8_e4m3"
    with pytest.raises(ValueError, match="devices_per_host"):
        CommConfig(strategy="hier", wire_dtype="fp8_e4m3", devices_per_host=0)
    cfg4 = make_comm_config("hier", devices_per_host=4)
    assert cfg4.local_devices() == 4
    # D = gcd(devices_per_host, p), H = p / D
    assert hier_split(cfg4, 8) == (4, 2)     # 2 hosts x 4 devices
    assert hier_split(cfg4, 4) == (4, 1)     # one host: pure psum_scatter
    assert hier_split(make_comm_config("hier", devices_per_host=1), 8) \
        == (1, 8)                            # degenerate: pure ring
    assert hier_split(cfg4, 6) == (2, 3)     # non-divisible: gcd grouping
    assert hier_split(cfg4, 1) == (1, 1)


def test_wire_level_bytes_accounting():
    shape = (8, 2, 16, 16)                   # blocked symmetric factor
    dense = 8 * 2 * 16 * 16 * 4
    packed = sym_packed_bytes(shape)         # f32 triangles
    fp8 = encoded_nbytes(shape, symmetric=True)
    cfg = make_comm_config("hier", devices_per_host=4)

    # 2 hosts x 4 devices: full packed f32 intra, fp8/D slice inter
    intra, inter = wire_stat_level_bytes(shape, True, cfg, group_size=8)
    assert (intra, inter) == (packed, fp8 // 4)
    assert wire_stat_bytes(shape, True, cfg, group_size=8) == intra + inter
    # acceptance bound: inter-host level <= 0.2x the dense f32 collective
    assert inter <= 0.2 * dense

    # one host: no inter level; one device per host: no intra level
    assert wire_stat_level_bytes(shape, True, cfg, group_size=4) \
        == (packed, 0)
    cfg1 = make_comm_config("hier", devices_per_host=1)
    assert wire_stat_level_bytes(shape, True, cfg1, group_size=8) \
        == (0, fp8)
    # non-symmetric stats ride both levels as dense f32
    assert wire_stat_level_bytes((8, 6), False, cfg, group_size=8) \
        == (8 * 6 * 4, 8 * 6 * 4 // 4)
    # replication fallback bills its dense psum to the inter column
    assert wire_stat_level_bytes(shape, True, cfg, scattered=False) \
        == (0, dense)
    # flat strategies have no level split at all
    assert wire_stat_level_bytes(shape, True, make_comm_config("ring_fp8"),
                                 group_size=8) == (0, 0)


def test_interval_controller_level_ledger():
    ctrl = IntervalController(
        ["x", "y"], alpha=0.5,
        wire_bytes_per_stat={"x": 130, "y": 260},
        wire_level_bytes_per_stat={"x": (100, 30), "y": (200, 60)})
    ctrl.update(1, {"x": True, "y": False}, {"x": (0.0, 0.0)})
    s = ctrl.summary()["comm"]
    assert s["total_wire_intra_bytes"] == 100    # only the refreshed stat
    assert s["total_wire_inter_bytes"] == 30
    assert s["dense_wire_intra_bytes"] == 300    # refresh-every-step
    assert s["dense_wire_inter_bytes"] == 90
    # round-trips through the checkpoint codec
    ctrl2 = IntervalController.from_state_dict(ctrl.state_dict())
    assert ctrl2.total_wire_inter_bytes == 30
    assert ctrl2.stats["y"].wire_intra_bytes_per_refresh == 200
    # pre-PR-6 checkpoints (no level columns) restore at zero
    old = ctrl.state_dict()
    for k in ("total_wire_intra_bytes", "dense_wire_intra_bytes",
              "total_wire_inter_bytes", "dense_wire_inter_bytes"):
        old.pop(k)
    for st in old["stats"].values():
        st.pop("wire_intra_bytes_per_refresh")
        st.pop("wire_inter_bytes_per_refresh")
    ctrl3 = IntervalController.from_state_dict(old)
    assert ctrl3.total_wire_inter_bytes == 0


# ---------------------------------------------------------------------------
# fused capture kernel: ref vs pallas(interpret) parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scale_mode", ["fp32", "pow2"])
def test_factor_sum_wire_ref_vs_pallas(scale_mode):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(3, 64, 32) * 2, jnp.float32)  # (lead, n, d)
    pay_r, sc_r = dispatch.factor_sum_wire(x, 16, scale_mode=scale_mode,
                                           backend="ref")
    pay_p, sc_p = dispatch.factor_sum_wire(x, 16, scale_mode=scale_mode,
                                           backend="pallas")
    t = 16 * 17 // 2
    assert pay_r.shape == (3, 2, t) and sc_r.shape == (3, 2)
    # The two paths sum the same products in a different order (XLA's
    # einsum vs the kernel's dot over one VMEM tile), so each block's f32
    # amax may differ in its last bit; the scale math on top is the same
    # explicit reciprocal-multiply in both (pinned bit-exact on equal
    # inputs by the fp8_pack parity tests). Scales therefore agree to a
    # few f32 ulps (a 64-term sum's reordering error), and a payload byte
    # may move to the adjacent fp8 code where a scaled value sits on a
    # rounding boundary.
    np.testing.assert_allclose(np.asarray(sc_r), np.asarray(sc_p),
                               rtol=1e-6, atol=0)
    codes_r = np.asarray(pay_r).view(np.uint8).astype(np.int32)
    codes_p = np.asarray(pay_p).view(np.uint8).astype(np.int32)
    assert np.abs(codes_r - codes_p).max() <= 1
    assert (codes_r != codes_p).mean() <= 0.01
    # decode matches the dense factor sum within the e4m3 bound
    from repro import quant
    dense = dispatch.factor_sum(x, 16, backend="ref")
    dec = quant.decode_wire_stat({"payload": pay_r, "scale": sc_r})
    amax = np.abs(np.asarray(dense)).max()
    assert np.abs(np.asarray(dec) - np.asarray(dense)).max() <= 0.05 * amax


# ---------------------------------------------------------------------------
# hier reduce parity on the simulated 2-host x 4-device mesh
# ---------------------------------------------------------------------------

def _template(shapes: dict):
    return {"fam": {k: jax.ShapeDtypeStruct(s, jnp.float32)
                    for k, s in shapes.items()}}


def _reduce_with(mesh, manual_axes, comm, raw_all, template, sym_fn):
    red = FactorReducer(mesh, manual_axes=manual_axes, comm=comm,
                        template=template, sym_fn=sym_fn)

    def body(raw):
        return red.reduce(jax.tree.map(lambda x: x[0], raw))

    in_specs = jax.tree.map(lambda _: P(red.dp), raw_all)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(in_specs,),
                       out_specs=red.out_specs(),
                       axis_names=set(red.dp), check_vma=False)
    return jax.tree.map(np.asarray, jax.jit(fn)(raw_all)), red


@needs_devices
@pytest.mark.parametrize("devices_per_host", [4, 1, 8])
def test_hier_reduce_parity_two_level(devices_per_host):
    """hier vs dense on an 8-device group modelled as 2 hosts x 4 devices
    (plus the degenerate pure-ring and pure-psum_scatter splits)."""
    mesh = make_mesh((4, 2), ("data", "model"))
    shapes = {"a": (8, 2, 16, 16),        # symmetric: fp8 inter-host ring
              "d": (8, 6)}                # non-symmetric: f32 both levels
    template = _template(shapes)
    sym_fn = lambda fam, key: key == "a"  # noqa: E731
    rng = np.random.RandomState(0)
    f = rng.randn(8, 8, 2, 16, 16).astype(np.float32)
    raw_all = {"fam": {"a": jnp.asarray(f + np.swapaxes(f, -1, -2)),
                       "d": jnp.asarray(rng.randn(8, 8, 6), np.float32)}}

    dense_out, _ = _reduce_with(mesh, "all", make_comm_config("dense"),
                                raw_all, template, sym_fn)
    hier_out, red = _reduce_with(
        mesh, "all",
        make_comm_config("hier", devices_per_host=devices_per_host),
        raw_all, template, sym_fn)
    d, h = hier_split(red.comm, 8)
    assert (d, h) == {4: (4, 2), 1: (1, 8), 8: (8, 1)}[devices_per_host]
    assert red.scatter_report()["hier_topology"] == {
        "devices_per_host": d, "hosts": h}

    # ownership is strategy-invariant (same out_specs as dense), so outputs
    # compare elementwise; symmetric stat quantizes only on inter-host hops
    amax = np.abs(dense_out["fam"]["a"]).max()
    err = np.abs(hier_out["fam"]["a"] - dense_out["fam"]["a"]).max()
    if h == 1:
        assert err <= 1e-5 * amax, (err, amax)   # pure f32 psum_scatter
    else:
        assert err <= 0.1 * amax, (err, amax)    # (h-1) fp8 roundings
    # non-symmetric stat never quantizes
    np.testing.assert_allclose(hier_out["fam"]["d"], dense_out["fam"]["d"],
                               rtol=1e-5, atol=1e-5)


@needs_devices
def test_hier_level_ledger_on_mesh():
    mesh = make_mesh((4, 2), ("data", "model"))
    shapes = {"a": (8, 2, 16, 16), "uw": (3, 4)}   # uw: replicated fallback
    red = FactorReducer(mesh, manual_axes="all",
                        comm=make_comm_config("hier", devices_per_host=4),
                        template=_template(shapes),
                        sym_fn=lambda fam, key: key == "a")
    levels = red.wire_bytes_per_stat_levels()
    packed = sym_packed_bytes(shapes["a"])
    fp8 = encoded_nbytes(shapes["a"], symmetric=True)
    dense_a = int(np.prod(shapes["a"])) * 4
    assert levels["fam.a"] == (packed, fp8 // 4)
    assert levels["fam.a"][1] <= 0.2 * dense_a       # acceptance bound
    # replication fallback bills dense f32 to the inter column
    assert levels["fam.uw"] == (0, int(np.prod(shapes["uw"])) * 4)
    # flat sum stays consistent with the scalar ledger
    per_stat = red.wire_bytes_per_stat()
    assert per_stat["fam.a"] == sum(levels["fam.a"])

    ctrl = IntervalController(list(per_stat), wire_bytes_per_stat=per_stat,
                              wire_level_bytes_per_stat=levels)
    ctrl.record_comm(red.scatter_report())
    flags = {n: True for n in per_stat}
    ctrl.update(1, flags, {n: (0.0, 0.0) for n in per_stat})
    s = ctrl.summary()["comm"]
    assert s["total_wire_intra_bytes"] == packed
    assert s["total_wire_inter_bytes"] == fp8 // 4 + 3 * 4 * 4
    assert s["hier_topology"] == {"devices_per_host": 4, "hosts": 2}


# ---------------------------------------------------------------------------
# fused capture: lookup spy + e2e parity
# ---------------------------------------------------------------------------

def _setup(factor_wire: str = "", n_layers: int = 0):
    from repro.configs import get_config
    from repro.core.ngd import NGDConfig, SPNGD
    from repro.models.transformer import DecoderLM
    cfg = get_config("llama3_2_1b").reduced(head_dim=32, d_ff=128,
                                            vocab=256, kfac_max_dim=64)
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    if factor_wire:
        cfg = dataclasses.replace(cfg, factor_wire=factor_wire)
    model = DecoderLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = SPNGD(model.loss, model.site_infos(), model.fstats,
                model.site_counts, NGDConfig(damping=1e-3))
    state = opt.init(params)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (8, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab, (8, 16)),
                                   jnp.int32)}
    flags = {k: jnp.asarray(True) for k in opt.stat_names()}
    return model, opt, params, state, batch, flags


def test_wire_template_and_state_shapes():
    """Wire capture changes the raw-stat template to payload/scale dicts but
    leaves the optimizer state (history, preconditioner) dense."""
    from repro import quant
    model, opt, params, state, *_ = _setup(factor_wire="e4m3")
    template = jax.eval_shape(opt.fstats_fn)
    wired = [(fam, k) for fam, stats in template.items()
             for k, leaf in stats.items() if quant.is_wire(leaf)]
    assert wired, "no wire-format stats captured"
    for fam, k in wired:
        entry = template[fam][k]
        assert entry["payload"].dtype == jnp.float8_e4m3fn
        assert entry["scale"].dtype == jnp.float32
        dense = quant.wire_dense_shape(entry)
        assert state["curv"][fam]["prev"][k].shape == dense
    # ledger prices the decoded dense shape, not the packed payload
    model_d, opt_d, *_ = _setup()
    assert opt.stat_bytes() == opt_d.stat_bytes()


@needs_devices
def test_fused_spy_syrk_emits_wire_no_ring_hop_pack(monkeypatch):
    """Acceptance: under the fused strategy the SYRK call site emits
    wire-format payloads (factor_sum_wire dispatches) and the reducer
    consumes them pre-packed — ZERO separate ring_hop_pack dispatches."""
    from repro.launch.train import make_shardmap_train_step
    calls = []
    real_lookup = dispatch.lookup

    def spy(op, backend):
        calls.append(op)
        return real_lookup(op, backend)

    monkeypatch.setattr(dispatch, "lookup", spy)
    model, opt, params, state, batch, flags = _setup(factor_wire="e4m3")
    mesh = make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh):
        step = make_shardmap_train_step(model, opt, mesh,
                                        comm=make_comm_config("fused"))
        jax.jit(step).lower(params, state, batch, flags,
                            jnp.float32(1e-3), jnp.float32(5e-3),
                            jnp.float32(0.9))
    assert calls.count("factor_sum_wire") > 0, set(calls)
    assert calls.count("ring_hop_pack") == 0, set(calls)
    assert calls.count("ring_hop_unpack") > 0, set(calls)  # decode side


def test_accum_wire_guard():
    from repro.launch.train import make_train_step
    model, opt, *_ = _setup(factor_wire="e4m3")
    with pytest.raises(ValueError, match="accumulate wire-format"):
        make_train_step(model, opt, accum=2)
    make_train_step(model, opt, accum=1)      # fine without accumulation
    model_d, opt_d, *_ = _setup()
    make_train_step(model_d, opt_d, accum=2)  # dense capture accumulates


@needs_devices
def test_e2e_fused_matches_dense_20_steps():
    """Acceptance: 20-step fused-vs-dense loss parity under jit AND
    shard_map. Mesh (2, 4) so the layer axis scatters and every factor
    family's wire payload actually rides the all_to_all."""
    from repro.launch.train import make_shardmap_train_step, make_train_step
    losses = {}
    for label, wire, strat, sharded in (
            ("dense", "", "dense", True),
            ("fused", "e4m3", "fused", True),
            ("fused_jit", "e4m3", None, False)):
        model, opt, params, state, batch, flags = _setup(factor_wire=wire)
        if sharded:
            mesh = make_mesh((2, 4), ("data", "model"))
            with jax.set_mesh(mesh):
                step = jax.jit(make_shardmap_train_step(
                    model, opt, mesh, comm=make_comm_config(strat)))
                out = []
                for _ in range(20):
                    params, state, m = step(params, state, batch, flags,
                                            1e-3, 5e-3, 0.9)
                    out.append(float(m["loss"]))
            assert step.reducer.replicated == []
        else:
            step = jax.jit(make_train_step(model, opt))
            out = []
            for _ in range(20):
                params, state, m = step(params, state, batch, flags,
                                        1e-3, 5e-3, 0.9)
                out.append(float(m["loss"]))
        losses[label] = out
    for label in ("fused", "fused_jit"):
        assert np.isfinite(losses[label]).all()
        assert losses[label][-1] < losses[label][0]          # it trains
        # fused quantizes the captured statistics themselves, so the
        # overfit fixture's bitwise chaos onsets a little earlier than the
        # ring_fp8 wire (~step 5, loss already < 0.1): pin the descent
        # prefix tightly, then require both runs to stay trained
        np.testing.assert_allclose(losses["dense"][:5], losses[label][:5],
                                   rtol=2e-2, atol=2e-2)
        assert max(losses[label][5:]) < 1.0
    assert max(losses["dense"][5:]) < 1.0


@needs_devices
def test_e2e_hier_matches_dense_20_steps():
    """Acceptance: 20-step hier-vs-dense loss parity on the simulated
    2-host x 4-device topology. Mesh (8, 1) with n_layers=8 so the layer
    axis scatters 8-ways and both hier levels run."""
    from repro.launch.train import make_shardmap_train_step
    mesh = make_mesh((8, 1), ("data", "model"))
    losses = {}
    for strat in ("dense", "hier"):
        model, opt, params, state, batch, flags = _setup(n_layers=8)
        comm = make_comm_config(strat, devices_per_host=4)
        with jax.set_mesh(mesh):
            step = jax.jit(make_shardmap_train_step(model, opt, mesh,
                                                    comm=comm))
            out = []
            for _ in range(20):
                params, state, m = step(params, state, batch, flags,
                                        1e-3, 5e-3, 0.9)
                out.append(float(m["loss"]))
        losses[strat] = out
        # the 8-way scatter replicates the two nb=4 vocab-side stats
        # (genuinely indivisible — exact psum, so parity is unaffected);
        # every layer-stacked family must still scatter so both hier
        # levels actually run
        assert set(step.reducer.replicated) <= {"embed.g", "head.a"}
        assert not any(n.startswith("blk/") for n in step.reducer.replicated)
        if strat == "hier":
            rep = step.reducer.scatter_report()
            assert rep["hier_topology"] == {"devices_per_host": 4,
                                            "hosts": 2}
            levels = step.reducer.wire_bytes_per_stat_levels()
            assert any(inter > 0 for _, inter in levels.values())
    assert np.isfinite(losses["hier"]).all()
    assert losses["hier"][-1] < losses["hier"][0]
    # the inter-host leg fp8-rounds every refresh, so the overfit
    # fixture's bitwise chaos onsets once the loss is tiny (~step 4):
    # pin the descent prefix tightly, then require both runs to stay
    # trained for the remaining 16 steps
    np.testing.assert_allclose(losses["dense"][:4], losses["hier"][:4],
                               rtol=2e-2, atol=2e-2)
    assert max(losses["dense"][4:]) < 1.0
    assert max(losses["hier"][4:]) < 1.0
