"""The main path's Pallas kernels, compiled for a described TPU v5e.

Interpret mode (every other kernel test) runs the kernel bodies on the
CPU and cannot see what the chip's compiler refuses: block shapes the TPU
tiling rejects, or dtype conversions Mosaic cannot lower. These tests
compile each kernel at real widths for one chip of a ``v5e:2x2`` topology
that is described, not attached, and check that the kernel is in the
program. Nothing runs, so they say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library, and every
worker of a parallel test run imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.engine import SubLayerEngine
from repro.kernels.flash_attention import flash_attention
from repro.kernels.streamed_matmul import (streamed_matmul,
                                           streamed_matmul_int4,
                                           streamed_matmul_int8)
from repro.models import build_model

NEMO_D, NEMO_F = 4096, 14336          # nemo8b FFN
QWEN_D, QWEN_F = 896, 4864            # qwen2-0.5b FFN


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [4, 256])
def test_streamed_matmul_bf16_compiles(shape, m):
    _assert_kernel(streamed_matmul, shape((m, NEMO_D), jnp.bfloat16),
                   shape((NEMO_D, NEMO_F), jnp.bfloat16))


def test_streamed_matmul_int8_compiles(shape):
    groups = NEMO_D // 512
    _assert_kernel(lambda x, w, s: streamed_matmul_int8(x, w, s,
                                                        block_k=512),
                   shape((256, NEMO_D), jnp.bfloat16),
                   shape((NEMO_D, NEMO_F), jnp.int8),
                   shape((groups, 1, NEMO_F), jnp.float32))


@pytest.mark.parametrize("m", [4, 64])
@pytest.mark.parametrize("k,n", [(QWEN_D, QWEN_F), (QWEN_F, QWEN_D)])
def test_streamed_matmul_int4_compiles(shape, m, k, n):
    groups = k // 128
    _assert_kernel(streamed_matmul_int4, shape((m, k), jnp.bfloat16),
                   shape((k // 2, n), jnp.uint8),
                   shape((groups, n), jnp.float16),
                   shape((groups, n), jnp.uint8))


@pytest.mark.parametrize("hd", [64, 128])
def test_flash_attention_compiles(shape, hd):
    _assert_kernel(flash_attention, shape((1, 8, 512, hd), jnp.bfloat16),
                   shape((1, 2, 512, hd), jnp.bfloat16),
                   shape((1, 2, 512, hd), jnp.bfloat16))


def test_engine_int4_ffn_step_compiles(shape):
    """The served int4 FFN step at qwen2-0.5b width routes its matmuls
    through the fused-dequant kernel (decode shape: four slots)."""
    cfg = get_config("qwen2-0.5b").replace(weight_quant="int4")
    tree = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda a: shape(a.shape[1:], a.dtype),
                         tree["layers"])
    eng = SubLayerEngine(cfg, use_streamed_mm=True)
    eng._mm_interpret = False        # compile the kernel, not its interpreter
    _assert_kernel(lambda w, x: eng._ffn_step(w, x, streamed=True),
                   {"ffn": layer["ffn"], "ln2": layer["ln2"]},
                   shape((4, 1, QWEN_D), jnp.bfloat16))
