"""Smoke + numerics tests for the CNN substrate (paper's own workload)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.neuromax_cnn import CONFIG
from repro.kernels.log_conv2d import fused_conv_geometry
from repro.models.cnn import CNNS, cnn_loss, make_cnn, trace_conv_shapes
from repro.obs import metrics as obs_metrics
from repro.serving.quantize import quantize_cnn_params

RED = CONFIG.reduced()


@pytest.mark.parametrize("name", sorted(CNNS))
def test_cnn_forward_shapes_and_finiteness(name):
    key = jax.random.PRNGKey(0)
    params, apply_fn = make_cnn(name, key, n_classes=RED.n_classes,
                                width_mult=RED.width_mult)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, RED.img, RED.img, 3))
    logits = apply_fn(params, x)
    assert logits.shape == (2, RED.n_classes)
    assert np.all(np.isfinite(np.asarray(logits)))


@pytest.mark.parametrize("name", ["vgg16", "mobilenet_v1"])
def test_cnn_logq6_close_to_fp(name):
    """Fake log-quant numerics stay within the base-√2 error envelope."""
    key = jax.random.PRNGKey(2)
    params, apply_fp = make_cnn(name, key, n_classes=10, width_mult=0.25)
    _, apply_q = make_cnn(name, key, n_classes=10, width_mult=0.25,
                          quant="logq6")
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32, 3))
    lf = np.asarray(apply_fp(params, x))
    lq = np.asarray(apply_q(params, x))
    assert np.all(np.isfinite(lq))
    # logits correlate strongly (quant noise, not garbage)
    c = np.corrcoef(lf.ravel(), lq.ravel())[0, 1]
    assert c > 0.9


@pytest.mark.parametrize("name", sorted(CNNS))
def test_cnn_conv_impl_blockwise_matches_fake_quant(name):
    """conv_impl routes convs through kernels/ops.conv2d on packed codes;
    same quantization grid as fake-quant ⇒ logits match within quant/conv
    float tolerance."""
    key = jax.random.PRNGKey(6)
    params, apply_fq = make_cnn(name, key, n_classes=10, width_mult=0.25,
                                quant="logq6")
    _, apply_bw = make_cnn(name, key, n_classes=10, width_mult=0.25,
                           quant="logq6", conv_impl="blockwise")
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 32, 3))
    lf = np.asarray(apply_fq(params, x))
    lb = np.asarray(apply_bw(params, x))
    np.testing.assert_allclose(lb, lf, atol=1e-4 * (np.abs(lf).max() + 1))


def test_cnn_packed_at_load_matches_on_the_fly():
    """serving.quantize_cnn_params packs once; forward equals per-call
    packing and most parameter bytes become int8 codes."""
    from repro.serving.quantize import (quantize_cnn_params,
                                        quantized_fraction)
    key = jax.random.PRNGKey(8)
    params, apply_bw = make_cnn("mobilenet_v1", key, n_classes=10,
                                width_mult=0.25, quant="logq6",
                                conv_impl="blockwise")
    qparams = quantize_cnn_params(params)
    assert quantized_fraction(qparams) > 0.5
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, 32, 3))
    np.testing.assert_array_equal(np.asarray(apply_bw(qparams, x)),
                                  np.asarray(apply_bw(params, x)))


@pytest.mark.parametrize("name", sorted(CNNS))
def test_cnn_conv_impl_fused_pallas_matches_blockwise(name):
    """The served weights, made and packed at load in one jitted call as
    the benchmark makes them (`quantize_cnn_params`: HWIO codes), through
    the model zoo's conv_impl="pallas" route onto the fused
    implicit-im2col kernel (interpret mode on CPU): the jitted forward's
    logits match the blockwise lowering of the same packed tree."""
    applies = {}

    def served_weights(key):
        for impl in ("blockwise", "pallas"):
            params, applies[impl] = make_cnn(
                name, key, n_classes=10, width_mult=0.25, quant="logq6",
                conv_impl=impl, interpret=True)
        return quantize_cnn_params(params)

    qparams = jax.jit(served_weights)(jax.random.PRNGKey(12))
    x = jax.random.normal(jax.random.PRNGKey(13), (1, 16, 16, 3))
    lb = np.asarray(jax.jit(applies["blockwise"])(qparams, x))
    lz = np.asarray(jax.jit(applies["pallas"])(qparams, x))
    np.testing.assert_allclose(lz, lb, atol=1e-3 * (np.abs(lb).max() + 1))


@pytest.mark.parametrize("batch", [1, 32])
@pytest.mark.parametrize("name", sorted(CNNS))
def test_only_the_first_conv_folds(name, batch):
    """At full width and 224 px the fold rule holds for each net's
    3-channel first conv and for no other."""
    folds = [fused_conv_geometry(s["B"], s["H"], s["W"], s["C"], s["K"],
                                 s["Cout"], stride=s["stride"],
                                 padding=s["padding"],
                                 groups=s["groups"])["fold"]
             for s in trace_conv_shapes(name, batch=batch)]
    assert folds == [True] + [False] * (len(folds) - 1)


@pytest.mark.parametrize("name,n_convs", [("resnet34", 36),
                                          ("mobilenet_v1", 27)])
def test_trace_conv_shapes_unchanged_by_the_fold(name, n_convs):
    """The walker records the convs as the nets call them, folded or not:
    the benchmark's counts read the same work."""
    records = trace_conv_shapes(name)
    assert len(records) == n_convs
    assert all(set(r) == {"B", "H", "W", "C", "K", "Cout", "stride",
                          "padding", "groups"} for r in records)
    assert records[0] == dict(B=1, H=224, W=224, C=3,
                              K=5 if name == "resnet34" else 3,
                              Cout=64 if name == "resnet34" else 32,
                              stride=2, padding="SAME", groups=1)


@pytest.mark.parametrize("name,n_convs", [("resnet34", 36),
                                          ("mobilenet_v1", 27),
                                          ("vgg16", 16)])
def test_traced_forward_counts_one_fold(name, n_convs):
    """Each fused dispatch counts `conv_fold` once: one forward of the
    full-width net at 224 px folds its first conv and no other."""
    init, apply = CNNS[name]

    def counts():
        return [obs_metrics.REGISTRY.counter("conv_fold", result=r).value
                for r in ("folded", "direct")]

    def forward(key, x):
        return apply(quantize_cnn_params(init(key), CONFIG.qcfg), x,
                     conv_impl="pallas")

    before = counts()
    jax.eval_shape(forward, jax.ShapeDtypeStruct((2,), jnp.uint32),
                   jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32))
    after = counts()
    assert [a - b for a, b in zip(after, before)] == [1, n_convs - 1]


@pytest.mark.parametrize("name,n_overlap,n_convs", [("resnet34", 6, 36),
                                                   ("mobilenet_v1", 4, 27),
                                                   ("vgg16", 6, 16)])
def test_traced_forward_reads_overlapping_row_tiles_in_place(name, n_overlap,
                                                             n_convs):
    """At batch 32 and 224 px the convs whose row tiles overlap (ResNet-34's
    stage 0, MobileNet v1's depthwise convs at 112² and 56², VGG-16's
    `convs.1`–`convs.6`) read them in place: a traced forward counts each
    once under `conv_row_tiles{layout="overlap_in_place"}`."""
    init, apply = CNNS[name]
    layouts = ("overlap_in_place", "reshape", "single")

    def counts():
        return [obs_metrics.REGISTRY.counter("conv_row_tiles",
                                             layout=k).value
                for k in layouts]

    def forward(key, x):
        return apply(quantize_cnn_params(init(key), CONFIG.qcfg), x,
                     conv_impl="pallas")

    before = counts()
    jax.eval_shape(forward, jax.ShapeDtypeStruct((2,), jnp.uint32),
                   jax.ShapeDtypeStruct((32, 224, 224, 3), jnp.float32))
    delta = [a - b for a, b in zip(counts(), before)]
    assert delta[0] == n_overlap and sum(delta) == n_convs


def test_vgg16_has_the_published_parameter_count():
    """Simonyan & Zisserman 2015, Table 2: 138M for config D; 89% of it is
    FC6, the 7×7 conv from 512 to 4096 channels."""
    init, _ = CNNS["vgg16"]
    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert n == 138_357_544
    assert params["fcs"][0]["w"].shape == (7, 7, 512, 4096)
    assert [p["w"].shape[:2] for p in params["fcs"][1:]] == [(1, 1)] * 2


def test_vgg16_classifier_runs_on_packed_codes(monkeypatch):
    """With ``conv_impl="auto"`` and packed weights the three layers of
    the classifier dispatch through `kernels/ops.conv2d` like every conv:
    16 launches, the last three a VALID 7×7 and two 1×1s."""
    from repro.core.logquant import QuantizedTensor
    from repro.kernels import ops as kops
    calls = []
    dispatch = kops.conv2d

    def spy(x, qt, **kw):
        calls.append((isinstance(qt, QuantizedTensor), tuple(qt.shape),
                      kw["padding"], kw["impl"]))
        return dispatch(x, qt, **kw)

    monkeypatch.setattr(kops, "conv2d", spy)
    init, apply = CNNS["vgg16"]
    jax.eval_shape(lambda k, x: apply(quantize_cnn_params(init(k)), x,
                                      conv_impl="auto"),
                   jax.ShapeDtypeStruct((2,), jnp.uint32),
                   jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.float32))
    assert len(calls) == 16
    assert all(packed and impl == "auto" for packed, _, _, impl in calls)
    assert [c[1:3] for c in calls[13:]] == [
        ((7, 7, 512, 4096), "VALID"), ((1, 1, 4096, 4096), "VALID"),
        ((1, 1, 4096, 1000), "VALID")]


def test_cnn_train_step_reduces_loss():
    key = jax.random.PRNGKey(4)
    params, apply_fn = make_cnn("squeezenet", key, n_classes=4,
                                width_mult=0.25, quant="logq6")
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 32, 32, 3))
    y = jnp.arange(8) % 4
    batch = {"images": x, "labels": y}

    @jax.jit
    def step(p):
        (loss, _), g = jax.value_and_grad(
            lambda pp: cnn_loss(apply_fn, pp, batch), has_aux=True)(p)
        return loss, jax.tree.map(lambda a, b: a - 0.05 * b, p, g)

    loss0, params = step(params)
    for _ in range(10):
        loss, params = step(params)
    assert float(loss) < float(loss0)
    assert np.isfinite(float(loss))
