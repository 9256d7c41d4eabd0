"""Every registered Pallas kernel compiles for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the Mosaic
compiler refuses: block shapes that are not (8, 128)-aligned, scalar stores
to VMEM, more VMEM than a core has. These cases lower each kernel wrapper
in ``repro.kernels.ops`` with ``interpret=False`` at the widths of
Qwen1.5-4B (d_model 2560, 20 heads of 128, d_ff 6912) for one chip of a
described ``v5e:2x2`` topology and compile it with the TPU compiler that
ships with jaxlib. Nothing runs; no chip is needed.

The topology is described inside a module fixture (never at import time),
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

F32, BF16, FP8 = jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn


def _decode_scaled(q, k, v, pos, k_scale, v_scale, **kw):
    """swa_decode with its per-slot fp8 dequant scales as positional args."""
    return ops.swa_decode(q, k, v, pos, k_scale=k_scale, v_scale=v_scale,
                          **kw)

# name -> (ops wrapper, arg shapes/dtypes, static kwargs). Shapes follow the
# training step at Qwen1.5-4B widths: the SYRK over 4096 tokens x d_model,
# block preconditioning and inversion at the factor block sizes the step
# produces, attention at batch 2 x 20 heads x 2048 tokens, and decode over
# a 4096-slot fp8 ring cache.
CASES = {
    "kfac_factor.f32": (ops.kfac_factor, [((4096, 2560), F32)], {}),
    "kfac_factor.bf16": (ops.kfac_factor, [((4096, 2560), BF16)], {}),
    "kfac_factor_wire.b256": (ops.kfac_factor_wire,
                              [((4096, 256), BF16)], {}),
    "kfac_factor_wire.b1024": (ops.kfac_factor_wire,
                               [((4096, 1024), BF16)], {}),
    "kfac_block_precond": (ops.kfac_block_precond,
                           [((2, 1024, 1024), F32), ((2, 1024, 2560), F32)],
                           {}),
    "ns_inverse.b256": (ops.ns_inverse, [((4, 256, 256), F32)],
                        dict(iters=30, tol=1e-4)),
    "ns_inverse.b1024": (ops.ns_inverse, [((2, 1024, 1024), F32)],
                         dict(iters=30, tol=1e-4)),
    "ns_inverse_tiled.b2048": (ops.ns_inverse_tiled,
                               [((2, 2048, 2048), F32)],
                               dict(iters=30, tol=1e-4)),
    "swa_attention": (ops.swa_attention,
                      [((40, 2048, 128), BF16)] * 3, {}),
    "swa_attention_fwd_res": (ops.swa_attention_fwd_res,
                              [((40, 1, 2048, 128), BF16),
                               ((40, 2048, 128), BF16),
                               ((40, 2048, 128), BF16)], {}),
    "swa_attention_bwd": (ops.swa_attention_bwd,
                          [((40, 1, 2048, 128), BF16),
                           ((40, 2048, 128), BF16),
                           ((40, 2048, 128), BF16),
                           ((40, 1, 2048, 128), BF16),
                           ((40, 1, 2048), F32),
                           ((40, 1, 2048, 128), BF16)], {}),
    "fp8_quant_rows": (ops.fp8_quant_rows, [((8, 32896), F32)], {}),
    "fp8_dequant_rows": (ops.fp8_dequant_rows,
                         [((8, 32896), FP8), ((8,), F32)], {}),
    "swa_decode.fp8": (_decode_scaled,
                       [((40, 1, 128), F32), ((40, 4096, 128), FP8),
                        ((40, 4096, 128), FP8), ((40,), jnp.int32),
                        ((40, 4096), F32), ((40, 4096), F32)],
                       dict(window=4096)),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip; keep these off it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, args, kw = CASES[name]
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
             for s, dt in args]
    lowered = jax.jit(functools.partial(fn, interpret=False, **kw)
                      ).lower(*specs)
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel in the HLO"
