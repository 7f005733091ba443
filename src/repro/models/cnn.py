"""CNN substrate in JAX — the networks the paper benchmarks (VGG-16,
MobileNet v1, ResNet-34, SqueezeNet) with optional base-√2 log fake-quant
on conv weights *and* post-ReLU activations (paper §3: ReLU removes the
need for an activation sign bit).

These are real, trainable JAX models.  Two orthogonal knobs:

  * ``quant="logq6"`` inserts `fake_log_quant` (straight-through estimator)
    on conv/dense weights and post-ReLU activations — the QAT path, fully
    differentiable.
  * ``conv_impl="pallas"|"blockwise"|"ref"|"auto"`` routes every conv
    through the unified log-domain dispatcher `kernels/ops.conv2d`:
    weights are packed int8 HWIO log codes (once at load via
    `serving.quantize.quantize_cnn_params`, or on the fly) and the conv
    executes against the codes — the true deployed numerics, top tier of
    the three-tier conv stack (fused implicit-im2col Pallas kernel with
    autotuned block sizes ↔ blockwise fallback ↔ `core/pe_grid.py`
    hardware oracle).  Inference-only: packing is not differentiable, so
    training keeps ``conv_impl=None`` (fake-quant).

Layer lists intentionally mirror `core/accelerator.py` so the analytical
dataflow model and the executable model describe the same networks.

Every layer of an apply runs under a `jax.named_scope` named after its
place in the net, the same path as its weights in the param tree
(``stem``, ``stages.1.0.c1``, ``pairs.3.dw``, ``fcs.0``, ``head``): its
conv, bias and ReLU carry the name as ``op_name`` metadata through to the
compiled program, where a profile's device ops can be traced back to
their layer.
Scopes are metadata only; the compiled program is the same without them.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from ..core.logquant import DEFAULT as LOGQ_DEFAULT
from ..core.logquant import (LogQuantConfig, QuantizedTensor, fake_log_quant,
                             quantize_tensor)
from ..kernels import ops as kops

# ---------------------------------------------------------------------------
# quant-aware primitives
# ---------------------------------------------------------------------------


def _maybe_fq(w, quant: str | None, cfg: LogQuantConfig):
    return fake_log_quant(w, cfg) if quant == "logq6" else w


def conv2d(p, x, *, stride=1, pad="SAME", quant=None, qcfg=LOGQ_DEFAULT,
           groups=1, conv_impl=None, interpret=None):
    """x: [B, H, W, Cin]; p['w']: [K, K, Cin//groups, Cout] (float array or
    packed `QuantizedTensor`).

    With ``conv_impl`` set (or a pre-packed weight), the conv dispatches to
    `kernels.ops.conv2d` on int8 log codes ("pallas" = the fused
    implicit-im2col kernel, block sizes from the autotuning table);
    otherwise it is the fake-quant `lax.conv` QAT path.
    """
    w = p["w"]
    if _CONV_SHAPE_TRACE is not None:
        _CONV_SHAPE_TRACE.append(dict(
            B=int(x.shape[0]), H=int(x.shape[1]), W=int(x.shape[2]),
            C=int(x.shape[3]), K=int(w.shape[0]), Cout=int(w.shape[-1]),
            stride=int(stride), padding=pad, groups=int(groups)))
    if conv_impl is not None or isinstance(w, QuantizedTensor):
        qt = w if isinstance(w, QuantizedTensor) else quantize_tensor(w, qcfg)
        y = kops.conv2d(x, qt, stride=stride, padding=pad, groups=groups,
                        impl=conv_impl or "auto", interpret=interpret,
                        out_dtype=x.dtype)
    else:
        w = _maybe_fq(w, quant, qcfg)
        y = jax.lax.conv_general_dilated(
            x, w, window_strides=(stride, stride), padding=pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups)
    if "b" in p:
        y = y + p["b"]
    return y


def conv_init(key, k, cin, cout, groups=1, dtype=jnp.float32):
    fan_in = k * k * cin // groups
    w = jax.random.normal(key, (k, k, cin // groups, cout), dtype)
    return {"w": w * (2.0 / fan_in) ** 0.5, "b": jnp.zeros((cout,), dtype)}


def relu_q(x, quant=None, qcfg=LOGQ_DEFAULT):
    """ReLU then (optionally) log-requantize — the paper's post-processing
    block: ReLU + log-table requantization before writing back to DDR."""
    x = jax.nn.relu(x)
    return _maybe_fq(x, quant, qcfg) if quant == "logq6" else x


def avgpool_global(x):
    return jnp.mean(x, axis=(1, 2))


def maxpool(x, k=2, s=2):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, s, s, 1), "VALID")


def _head(p, x):
    """Global average pool and the dense classifier, scoped ``head``."""
    with jax.named_scope("head"):
        return avgpool_global(x) @ p["w"] + p["b"]


# ---------------------------------------------------------------------------
# VGG-16
# ---------------------------------------------------------------------------

_VGG_PLAN = [  # (Cout, pool_after)
    (64, False), (64, True), (128, False), (128, True),
    (256, False), (256, False), (256, True),
    (512, False), (512, False), (512, True),
    (512, False), (512, False), (512, True),
]
_VGG_MAP = 7  # side of the map the classifier reads: 224 px / 2**5


def vgg16_init(key, *, n_classes=1000, cin=3, width_mult=1.0):
    """The 13 convs of Table 1 column D and the published classifier,
    FC-4096, FC-4096 and FC-n_classes, held as the convs `vgg16_apply`
    runs them: a 7×7 conv over the 7×7 map, then two 1×1 convs."""
    keys = jax.random.split(key, len(_VGG_PLAN) + 3)
    params, c = [], cin
    for i, (cout, _) in enumerate(_VGG_PLAN):
        cout = max(8, int(cout * width_mult))
        params.append(conv_init(keys[i], 3, c, cout))
        c = cout
    f = max(8, int(4096 * width_mult))
    k6, k7, k8 = keys[len(_VGG_PLAN):]
    fcs = [conv_init(k6, _VGG_MAP, c, f), conv_init(k7, 1, f, f),
           conv_init(k8, 1, f, n_classes)]
    return {"convs": params, "fcs": fcs}


def _bins(n: int, out: int) -> list[tuple[int, int]]:
    """The ``[start, end)`` of each of ``out`` adaptive-pool bins over
    ``n`` pixels, as torchvision's `AdaptiveAvgPool2d` draws them."""
    return [(i * n // out, -(-(i + 1) * n // out)) for i in range(out)]


def adaptive_avgpool(x, out: int):
    """[B, H, W, C] → [B, out, out, C], each pixel the mean of its bin
    (bins overlap where ``out`` does not divide the side, and repeat a
    pixel where the side is shorter than ``out``)."""
    x = jnp.stack([jnp.mean(x[:, a:b], axis=1)
                   for a, b in _bins(x.shape[1], out)], axis=1)
    return jnp.stack([jnp.mean(x[:, :, a:b], axis=2)
                      for a, b in _bins(x.shape[2], out)], axis=2)


def vgg16_apply(params, x, *, quant=None, qcfg=LOGQ_DEFAULT, conv_impl=None,
                interpret=None):
    """The convs and pools, then the classifier as dense evaluation (§3.2):
    FC6 a VALID 7×7 conv over the 7×7 map, FC7 and FC8 1×1 convs, ReLU
    after FC6 and FC7 (dropout is training-only).  At 224 px the map is
    7×7, and this is flatten and dense exactly; a smaller image's map is
    first average-pooled to 7×7 (``fcs.pool``)."""
    cv = functools.partial(conv2d, quant=quant, qcfg=qcfg,
                           conv_impl=conv_impl, interpret=interpret)
    for i, (p, (_, pool)) in enumerate(zip(params["convs"], _VGG_PLAN)):
        with jax.named_scope(f"convs.{i}"):
            x = relu_q(cv(p, x), quant, qcfg)
        if pool and min(x.shape[1], x.shape[2]) >= 2:
            with jax.named_scope(f"convs.{i}.pool"):
                x = maxpool(x)
    if x.shape[1:3] != (_VGG_MAP, _VGG_MAP):
        with jax.named_scope("fcs.pool"):
            x = adaptive_avgpool(x, _VGG_MAP)
    fc6, fc7, fc8 = params["fcs"]
    with jax.named_scope("fcs.0"):
        x = relu_q(cv(fc6, x, pad="VALID"), quant, qcfg)
    with jax.named_scope("fcs.1"):
        x = relu_q(cv(fc7, x, pad="VALID"), quant, qcfg)
    with jax.named_scope("fcs.2"):
        return cv(fc8, x, pad="VALID").reshape(x.shape[0], -1)


# ---------------------------------------------------------------------------
# MobileNet v1 (depthwise separable — the paper's separable mode)
# ---------------------------------------------------------------------------

_MBN_PAIRS = [(64, 1), (128, 2), (128, 1), (256, 2), (256, 1), (512, 2)] + \
             [(512, 1)] * 5 + [(1024, 2), (1024, 1)]


def mobilenet_v1_init(key, *, n_classes=1000, cin=3, width_mult=1.0):
    n = 1 + 2 * len(_MBN_PAIRS) + 1
    keys = jax.random.split(key, n)
    c0 = max(8, int(32 * width_mult))
    params = {"stem": conv_init(keys[0], 3, cin, c0), "pairs": []}
    c = c0
    for i, (cout, _) in enumerate(_MBN_PAIRS):
        cout = max(8, int(cout * width_mult))
        dw = conv_init(keys[1 + 2 * i], 3, c, c, groups=c)
        pw = conv_init(keys[2 + 2 * i], 1, c, cout)
        params["pairs"].append({"dw": dw, "pw": pw})
        c = cout
    params["head"] = {"w": jax.random.normal(keys[-1], (c, n_classes))
                      * (1 / c) ** 0.5, "b": jnp.zeros((n_classes,))}
    return params


def mobilenet_v1_apply(params, x, *, quant=None, qcfg=LOGQ_DEFAULT,
                       conv_impl=None, interpret=None):
    cv = functools.partial(conv2d, quant=quant, qcfg=qcfg,
                           conv_impl=conv_impl, interpret=interpret)
    with jax.named_scope("stem"):
        x = relu_q(cv(params["stem"], x, stride=2), quant, qcfg)
    for i, (pair, (_, stride)) in enumerate(zip(params["pairs"], _MBN_PAIRS)):
        c = x.shape[-1]
        with jax.named_scope(f"pairs.{i}.dw"):
            x = relu_q(cv(pair["dw"], x, stride=stride, groups=c), quant,
                       qcfg)
        with jax.named_scope(f"pairs.{i}.pw"):
            x = relu_q(cv(pair["pw"], x), quant, qcfg)
    return _head(params["head"], x)


# ---------------------------------------------------------------------------
# ResNet-34
# ---------------------------------------------------------------------------

_R34_STAGES = [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)]


def resnet34_init(key, *, n_classes=1000, cin=3, width_mult=1.0):
    blocks = sum(b for _, b, _ in _R34_STAGES)
    keys = iter(jax.random.split(key, 2 + 3 * blocks))
    c0 = max(8, int(64 * width_mult))
    params = {"stem": conv_init(next(keys), 5, cin, c0), "stages": []}
    cin_cur = c0
    for cout, nblocks, first_stride in _R34_STAGES:
        cout = max(8, int(cout * width_mult))
        stage = []
        for b in range(nblocks):
            st = first_stride if b == 0 else 1
            blk = {"c1": conv_init(next(keys), 3, cin_cur, cout),
                   "c2": conv_init(next(keys), 3, cout, cout)}
            if st != 1 or cin_cur != cout:
                blk["proj"] = conv_init(next(keys), 1, cin_cur, cout)
            stage.append(blk)
            cin_cur = cout
        params["stages"].append(stage)
    params["head"] = {"w": jax.random.normal(next(keys), (cin_cur, n_classes))
                      * (1 / cin_cur) ** 0.5, "b": jnp.zeros((n_classes,))}
    return params


def resnet34_apply(params, x, *, quant=None, qcfg=LOGQ_DEFAULT,
                   conv_impl=None, interpret=None):
    cv = functools.partial(conv2d, quant=quant, qcfg=qcfg,
                           conv_impl=conv_impl, interpret=interpret)
    with jax.named_scope("stem"):
        x = relu_q(cv(params["stem"], x, stride=2), quant, qcfg)
    if min(x.shape[1], x.shape[2]) >= 2:
        with jax.named_scope("pool"):
            x = maxpool(x)
    for i, (stage, (_, _, first_stride)) in enumerate(
            zip(params["stages"], _R34_STAGES)):
        for b, blk in enumerate(stage):
            st = first_stride if b == 0 else 1
            with jax.named_scope(f"stages.{i}.{b}.c1"):
                y = relu_q(cv(blk["c1"], x, stride=st), quant, qcfg)
            with jax.named_scope(f"stages.{i}.{b}.c2"):
                y = cv(blk["c2"], y)
            sc = x
            if "proj" in blk:
                with jax.named_scope(f"stages.{i}.{b}.proj"):
                    sc = cv(blk["proj"], x, stride=st)
            with jax.named_scope(f"stages.{i}.{b}.add_relu"):
                x = relu_q(y + sc, quant, qcfg)
    return _head(params["head"], x)


# ---------------------------------------------------------------------------
# SqueezeNet v1.0 (Fig-1 net)
# ---------------------------------------------------------------------------

_FIRES = [(96, 16, 64), (128, 16, 64), (128, 32, 128), (256, 32, 128),
          (256, 48, 192), (384, 48, 192), (384, 64, 256), (512, 64, 256)]


def squeezenet_init(key, *, n_classes=1000, cin=3, width_mult=1.0):
    keys = iter(jax.random.split(key, 2 + 3 * len(_FIRES)))
    m = lambda c: max(4, int(c * width_mult))
    params = {"stem": conv_init(next(keys), 5, cin, m(96)), "fires": []}
    for cin_f, sq, ex in _FIRES:
        params["fires"].append({
            "squeeze": conv_init(next(keys), 1, m(cin_f), m(sq)),
            "e1": conv_init(next(keys), 1, m(sq), m(ex)),
            "e3": conv_init(next(keys), 3, m(sq), m(ex))})
    params["final"] = conv_init(next(keys), 1, m(512), n_classes)
    return params


def squeezenet_apply(params, x, *, quant=None, qcfg=LOGQ_DEFAULT,
                     conv_impl=None, interpret=None):
    cv = functools.partial(conv2d, quant=quant, qcfg=qcfg,
                           conv_impl=conv_impl, interpret=interpret)
    with jax.named_scope("stem"):
        x = relu_q(cv(params["stem"], x, stride=2), quant, qcfg)
    if min(x.shape[1], x.shape[2]) >= 2:
        with jax.named_scope("pool"):
            x = maxpool(x, 3, 2)
    for i, fire in enumerate(params["fires"]):
        if i in (3, 7) and min(x.shape[1], x.shape[2]) >= 2:
            with jax.named_scope(f"fires.{i}.pool"):
                x = maxpool(x, 3, 2)
        with jax.named_scope(f"fires.{i}.squeeze"):
            s = relu_q(cv(fire["squeeze"], x), quant, qcfg)
        with jax.named_scope(f"fires.{i}.e1"):
            e1 = relu_q(cv(fire["e1"], s), quant, qcfg)
        with jax.named_scope(f"fires.{i}.e3"):
            e3 = relu_q(cv(fire["e3"], s), quant, qcfg)
        with jax.named_scope(f"fires.{i}.concat"):
            x = jnp.concatenate([e1, e3], axis=-1)
    with jax.named_scope("final"):
        x = relu_q(cv(params["final"], x), quant, qcfg)
    with jax.named_scope("head"):
        return avgpool_global(x)


# ---------------------------------------------------------------------------
# registry + loss
# ---------------------------------------------------------------------------

CNNS = {
    "vgg16": (vgg16_init, vgg16_apply),
    "mobilenet_v1": (mobilenet_v1_init, mobilenet_v1_apply),
    "resnet34": (resnet34_init, resnet34_apply),
    "squeezenet": (squeezenet_init, squeezenet_apply),
}

CNN_ZOO = CNNS  # the paper's four networks — the warm-start tuning target


# ---------------------------------------------------------------------------
# conv-shape walker (feeds the packaged autotune warm-start tier)
# ---------------------------------------------------------------------------

_CONV_SHAPE_TRACE: list | None = None


@contextlib.contextmanager
def _capture_conv_shapes(records: list):
    global _CONV_SHAPE_TRACE
    prev = _CONV_SHAPE_TRACE
    _CONV_SHAPE_TRACE = records
    try:
        yield records
    finally:
        _CONV_SHAPE_TRACE = prev


def trace_conv_shapes(name: str, *, batch=1, img=224, n_classes=1000, cin=3,
                      width_mult=1.0) -> list[dict]:
    """Every conv dispatch of one zoo network, as launch-geometry records
    ``{B, H, W, C, K, Cout, stride, padding, groups}`` in call order.

    Shape tracing only: `init` runs *inside* `jax.eval_shape` (so python
    strides in the param tree stay static) and no parameters or
    activations are ever materialised — walking all four networks at the
    paper's 224 px takes seconds, not a forward pass."""
    init, apply = CNNS[name]
    records: list[dict] = []

    def run(key, x):
        return apply(init(key, n_classes=n_classes, cin=cin,
                          width_mult=width_mult), x)

    with _capture_conv_shapes(records):
        jax.eval_shape(run, jax.ShapeDtypeStruct((2,), jnp.uint32),
                       jax.ShapeDtypeStruct((batch, img, img, cin),
                                            jnp.float32))
    return records


def zoo_conv_shapes(*, batch=1, img=224, n_classes=1000, cin=3,
                    width_mult=1.0) -> list[dict]:
    """Deduped union of conv launch shapes across the whole zoo — the
    shape list the packaged autotune tier must cover (each record gains a
    ``nets`` list naming the networks that dispatch it)."""
    seen: dict[tuple, dict] = {}
    for name in CNNS:
        for r in trace_conv_shapes(name, batch=batch, img=img,
                                   n_classes=n_classes, cin=cin,
                                   width_mult=width_mult):
            sig = tuple(sorted((k, str(v)) for k, v in r.items()))
            if sig not in seen:
                seen[sig] = dict(r, nets=[name])
            elif name not in seen[sig]["nets"]:
                seen[sig]["nets"].append(name)
    return list(seen.values())


def make_cnn(name: str, key, *, n_classes=1000, cin=3, width_mult=1.0,
             quant=None, qcfg=LOGQ_DEFAULT, conv_impl=None, interpret=None):
    init, apply = CNNS[name]
    params = init(key, n_classes=n_classes, cin=cin, width_mult=width_mult)
    # named after the net's apply, so its jit (and the compiled module) is
    # `jit_resnet34_apply`, not `jit__unknown`
    forward = functools.partial(apply, quant=quant, qcfg=qcfg,
                                conv_impl=conv_impl, interpret=interpret)
    return params, functools.update_wrapper(forward, apply)


def cnn_loss(apply_fn, params, batch):
    logits = apply_fn(params, batch["images"])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)
    acc = jnp.mean(jnp.argmax(logits, -1) == batch["labels"])
    return jnp.mean(nll), {"acc": acc}
