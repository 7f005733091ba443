"""Compile-only rehearsals against a described TPU v5e (nothing runs).

The chip's compiler refuses what interpret mode accepts: a block that
breaks the (8, 128) rule, a dynamic slice it cannot prove aligned, more
VMEM than the scoped limit.  Each test compiles one kernel of the main
path at real width for one chip of a ``v5e:2x2`` topology, with the
config dispatch would choose, and checks the kernel is in the program.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU compiler library, and every test worker
imports this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.neuromax_cnn import CONFIG
from repro.kernels import autotune
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.log_conv2d import log_conv2d_fused_pallas
from repro.kernels.log_matmul import log_matmul_pallas
from repro.kernels.wkv6 import wkv6_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the program"


# (x shape, K, Cout, stride, groups): the two shapes Mosaic refused for an
# unaligned tap slice, the batch-8 table miss that overran VMEM, the
# ResNet-34 stem, and lane-packed depthwise layers (4 superblocks; batch 8
# stride 2)
CONV_SHAPES = [
    ((1, 7, 7, 512), 3, 512, 1, 1),
    ((1, 14, 14, 256), 3, 512, 2, 1),
    ((8, 56, 56, 64), 3, 64, 1, 1),
    ((1, 224, 224, 3), 5, 64, 2, 1),
    ((1, 14, 14, 512), 3, 512, 1, 512),
    ((8, 112, 112, 64), 3, 64, 2, 64),
]


@pytest.mark.parametrize("xshape,K,Cout,stride,groups", CONV_SHAPES,
                         ids=lambda v: "x".join(map(str, v))
                         if isinstance(v, tuple) else str(v))
def test_fused_conv_compiles(one_chip, xshape, K, Cout, stride, groups):
    B, H, W, C = xshape
    kw = dict(stride=stride, padding="SAME", groups=groups)
    key = autotune.conv_key(B, H, W, C, K, Cout, cfg=CONFIG.qcfg,
                            backend="tpu", **kw)
    config = (autotune.lookup(key)
              or autotune.default_config(B, H, W, C, K, Cout, **kw))
    assert autotune.estimate_vmem_bytes(B, H, W, C, K, Cout, **kw,
                                        **config) \
        <= autotune.VMEM_BUDGET_BYTES
    fn = functools.partial(log_conv2d_fused_pallas, cfg=CONFIG.qcfg,
                           interpret=False, **kw, **config)
    _compile(fn, one_chip, (xshape, jnp.float32),
             ((K, K, C // groups, Cout), jnp.int8),
             ((1, 1, 1, Cout), jnp.float32))


def test_log_matmul_compiles(one_chip):
    fn = functools.partial(log_matmul_pallas, interpret=False)
    _compile(fn, one_chip, ((2048, 4096), jnp.bfloat16),
             ((4096, 4096), jnp.int8), ((1, 4096), jnp.float32))


@pytest.mark.parametrize("case", ["gqa_prefill_2k", "mqa_decode_4k"])
def test_flash_attention_compiles(one_chip, case):
    # (B, Tq, Tk, H, Hkv, D): GQA prefill at 2k; gemma3-1b-shaped MQA
    # decode (head_dim 256) against a 4k cache at a traced position
    B, Tq, Tk, H, Hkv, D = {"gqa_prefill_2k": (1, 2048, 2048, 8, 2, 128),
                            "mqa_decode_4k": (4, 1, 4096, 4, 1, 256)}[case]
    cfg = autotune.default_attention_config(B, Tq, Tk, H, Hkv, D)

    def fn(q, k, v, pos):
        return flash_attention_pallas(q, k, v, causal=True, q_offset=pos,
                                      interpret=False, **cfg)

    _compile(fn, one_chip, ((B, Tq, H, D), jnp.bfloat16),
             ((B, Tk, Hkv, D), jnp.bfloat16), ((B, Tk, Hkv, D), jnp.bfloat16),
             ((), jnp.int32))


def test_wkv6_compiles(one_chip):
    # RWKV6-1.6B widths: 32 heads of 64 channels, one 512-token window
    B, T, H, K = 1, 512, 32, 64
    fn = functools.partial(wkv6_pallas, chunk=64, interpret=False)
    act = ((B, T, H, K), jnp.float32)
    _compile(fn, one_chip, act, act, act, act, ((H, K), jnp.float32))
