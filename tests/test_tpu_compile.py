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

import contextlib
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.neuromax_cnn import CONFIG
from repro.kernels import autotune
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.log_conv2d import (fused_conv_geometry,
                                      log_conv2d_fused_pallas)
from repro.kernels.log_matmul import log_matmul_pallas
from repro.kernels.wkv6 import wkv6_pallas
from repro.models.cnn import make_cnn
from repro.serving.quantize import quantize_cnn_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import scopes  # noqa: E402


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
    return text


# (x shape, K, Cout, stride, groups, padding): the two shapes Mosaic
# refused for an unaligned tap slice, the batch-8 table miss that overran
# VMEM, the ResNet-34 stem, lane-packed depthwise layers (4 superblocks;
# batch 8 stride 2), folded stems: the ResNet-34 and MobileNet v1 stems at
# batch 32 and the published 7×7 ResNet stem (147 contraction lanes), the
# packaged table's SqueezeNet and VGG-16 3×3 winners that counting the halo
# stack in `conv_traffic_bytes` re-picked, and VGG-16 at batch 32: its
# classifier as dense-evaluation convs (FC6 a VALID 7×7 conv whose 102.8 MB
# of codes dwarf its 3.2 MB input, FC7 and FC8 1×1 convs over one pixel;
# FC6 at batch 1 too, with the packaged table's config) and `convs.1`, the
# 3×3 conv on the zoo's largest maps; and the other batch-32 convs whose
# row tiles overlap and are read in place: ResNet-34's stage 0 and
# MobileNet v1's depthwise layers at 112² (stride 1 and 2)
CONV_SHAPES = [
    ((1, 7, 7, 512), 3, 512, 1, 1, "SAME"),
    ((1, 14, 14, 256), 3, 512, 2, 1, "SAME"),
    ((8, 56, 56, 64), 3, 64, 1, 1, "SAME"),
    ((1, 224, 224, 3), 5, 64, 2, 1, "SAME"),
    ((1, 14, 14, 512), 3, 512, 1, 512, "SAME"),
    ((8, 112, 112, 64), 3, 64, 2, 64, "SAME"),
    ((32, 224, 224, 3), 5, 64, 2, 1, "SAME"),
    ((32, 224, 224, 3), 3, 32, 2, 1, "SAME"),
    ((1, 224, 224, 3), 7, 64, 2, 1, "SAME"),
    ((1, 55, 55, 32), 3, 128, 1, 1, "SAME"),
    ((1, 56, 56, 128), 3, 256, 1, 1, "SAME"),
    ((1, 56, 56, 256), 3, 256, 1, 1, "SAME"),
    ((32, 7, 7, 512), 7, 4096, 1, 1, "VALID"),
    ((32, 1, 1, 4096), 1, 4096, 1, 1, "VALID"),
    ((32, 1, 1, 4096), 1, 1000, 1, 1, "VALID"),
    ((1, 7, 7, 512), 7, 4096, 1, 1, "VALID"),
    ((32, 224, 224, 64), 3, 64, 1, 1, "SAME"),
    ((32, 56, 56, 64), 3, 64, 1, 1, "SAME"),
    ((32, 112, 112, 32), 3, 32, 1, 32, "SAME"),
    ((32, 112, 112, 64), 3, 64, 2, 64, "SAME"),
]


def _case_id(xshape, K, Cout, stride, groups, padding):
    parts = ["x".join(map(str, xshape)), K, Cout, stride, groups]
    return "-".join(map(str, parts + ([padding] if padding != "SAME"
                                      else [])))


@pytest.mark.parametrize("xshape,K,Cout,stride,groups,padding",
                         [pytest.param(*c, id=_case_id(*c))
                          for c in CONV_SHAPES])
def test_fused_conv_compiles(one_chip, xshape, K, Cout, stride, groups,
                             padding):
    B, H, W, C = xshape
    kw = dict(stride=stride, padding=padding, groups=groups)
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


def test_overlapping_row_tiles_are_read_in_place(one_chip):
    """VGG-16's `convs.1` at batch 32 has 28 overlapping row tiles per
    image.  The kernel reads them out of the padded input as the pad
    writes it: its first operand is the pad, with no copy between, and no
    instruction builds the tiles (the ``halo`` scope of the stack that
    did)."""
    B, H, W, C, K, Cout = 32, 224, 224, 64, 3, 64
    config = autotune.default_config(B, H, W, C, K, Cout)
    g = fused_conv_geometry(B, H, W, C, K, Cout, **config)
    assert g["n_rt"] * g["rows_in"] > g["Hp"]
    fn = functools.partial(log_conv2d_fused_pallas, cfg=CONFIG.qcfg,
                           interpret=False, **config)
    text = _compile(fn, one_chip, ((B, H, W, C), jnp.float32),
                    ((K, K, C, Cout), jnp.int8), ((1, 1, 1, Cout), jnp.float32))
    instrs = scopes.entry_instructions(text)
    assert not [name for name, _, op_name in instrs if "/halo/" in op_name]
    op_names = {name: op_name for name, _, op_name in instrs}
    kernel = next(hlo for _, hlo, _ in instrs if scopes.KERNEL_TARGET in hlo)
    x_in = re.search(r"custom-call\(%([^\s,()]+)", kernel).group(1)
    assert "/pad/" in op_names[x_in], (x_in, op_names[x_in])


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


# net → (conv kernels, the layers that hold them)
SCOPED_NETS = {"resnet34": (36, r"stem|stages\.\d\.\d\.(c1|c2|proj)"),
               "mobilenet_v1": (27, r"stem|pairs\.\d+\.(dw|pw)"),
               "vgg16": (16, r"convs\.\d+|fcs\.[012]")}


@pytest.fixture(scope="module", params=sorted(SCOPED_NETS))
def scoped_texts(request, one_chip):
    """``compiled.as_text()`` of a width-0.25 forward at batch 1 (64 px) on
    the fused Pallas conv, with its named scopes and with
    `jax.named_scope` made a no-op; both compiled from one call site, so
    their source locations agree."""
    net, qcfg = request.param, CONFIG.qcfg

    def build(key):
        params, apply = make_cnn(net, key, width_mult=0.25, qcfg=qcfg,
                                 conv_impl="pallas", interpret=False)
        return quantize_cnn_params(params, qcfg), apply

    _, apply = build(jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: build(k)[0], jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((1, 64, 64, 3), jnp.float32, sharding=one_chip)
    texts = {}
    for scoped in (True, False):
        jax.clear_caches()
        with pytest.MonkeyPatch.context() as mp:
            if not scoped:
                mp.setattr(jax, "named_scope",
                           lambda name: contextlib.nullcontext())
            texts[scoped] = jax.jit(apply).lower(params, x).compile().as_text()
    return net, texts


def test_every_kernel_in_a_conv_layer(scoped_texts):
    net, texts = scoped_texts
    n_kernels, layers = SCOPED_NETS[net]
    text = texts[True]
    assert text.startswith(f"HloModule jit_{net}_apply,")
    smap = scopes.scope_map(text)
    kernels = [(name, smap[name]) for name, hlo, _
               in scopes.entry_instructions(text)
               if 'custom_call_target="tpu_custom_call"' in hlo]
    assert len(kernels) == n_kernels
    for name, (layer, role) in kernels:
        assert role == scopes.KERNEL, name
        assert re.fullmatch(layers, layer), (name, layer)


def test_ops_have_a_layer(scoped_texts):
    smap = scopes.scope_map(scoped_texts[1][True])
    ops = [name for name in smap
           if not name.startswith(("copy-start", "copy-done"))]
    named = [name for name in ops if smap[name][0] is not None]
    assert len(named) >= 0.95 * len(ops), sorted(set(ops) - set(named))
    roles = {role for _, role in smap.values()}
    assert {"pad", "fold", scopes.KERNEL, scopes.GLUE} <= roles


def test_scopes_are_metadata_only(scoped_texts):
    """With the op_name metadata and the source tables stripped, and the
    instructions renamed in order of first appearance (a scope can shift
    the numbering of an instruction's name), the program is the same with
    and without the scopes."""
    def program(text):
        lines = re.sub(r", metadata=\{[^}]*\}", "", text).splitlines()
        first = next(i for i, line in enumerate(lines)
                     if line.startswith(("%", "ENTRY")))
        ids = {}
        return re.sub(r"%[\w.\-]+",
                      lambda m: ids.setdefault(m.group(0), f"%v{len(ids)}"),
                      "\n".join(lines[:1] + lines[first:]))

    texts = scoped_texts[1]
    assert "metadata={" in texts[True]
    assert program(texts[True]) == program(texts[False])
    assert texts[True] != texts[False]
