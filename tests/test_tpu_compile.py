"""Compile-only guards: the main-path Pallas kernels at deepseek-moe-16b's
expert widths (K=2048, N=1408, G=64), compiled for a described TPU v5e.

Nothing runs: the TPU compiler accepts or refuses each kernel, which is
what interpret mode cannot show (Mosaic refuses a ``dynamic_slice`` of a
value, or a block whose last two dims break the (8, 128) tiling).  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one running this file loads
the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.epilogue_kernel import act_quantize_pallas
from repro.kernels.grouped_gemm_kernel import (gmm_pallas, gmm_pallas_bf16,
                                               gmm_pallas_quant)
from repro.kernels.quant_kernel import quantize_tilewise_pallas
from repro.kernels.wgrad_kernel import gmm_pallas_wgrad, gmm_pallas_wgrad_fp8

M, K, N, G = 8192, 2048, 1408, 64
DECODE_M = 96                       # 16 requests x top-6
F8 = jnp.float8_e4m3fn
F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _fp8_gemm_args(m):
    return ((m, K), F8), ((m, K // 128), F32), ((G, K, N), F8), \
        ((G, K // 128, N // 128), F32), ((G,), jnp.int32)


# name -> (kernel, ((shape, dtype), ...) of its arguments)
CASES = {
    "gemm_fp8": (gmm_pallas, _fp8_gemm_args(M)),
    "gemm_fp8_decode_tile": (functools.partial(gmm_pallas, block_m=16),
                             _fp8_gemm_args(DECODE_M)),
    "gemm_bf16": (gmm_pallas_bf16, (((M, K), jnp.bfloat16),
                                    ((G, K, N), jnp.bfloat16),
                                    ((G,), jnp.int32))),
    "gemm_quant": (gmm_pallas_quant, _fp8_gemm_args(M)),
    "wgrad_bf16": (functools.partial(gmm_pallas_wgrad, num_groups=G),
                   (((M, K), jnp.bfloat16), ((M, N), jnp.bfloat16),
                    ((G,), jnp.int32))),
    "wgrad_fp8": (functools.partial(gmm_pallas_wgrad_fp8, num_groups=G),
                  (((M, K), F8), ((M, K // 128), F32), ((M, N), F8),
                   ((M, N // 128), F32), ((G,), jnp.int32))),
    "quantize": (quantize_tilewise_pallas, (((M, K), F32),)),
    "act_quant": (functools.partial(act_quantize_pallas, act="silu_mul"),
                  (((M, N), F32), ((M, N), F32))),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, arg_specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
