"""Serving-time weight quantization: replace matmul/conv kernels with packed
6-bit(+sign) base-√2 QuantizedTensors (the paper's storage format).

On TPU the packed codes are decoded in VMEM by the log_matmul / log_conv2d
Pallas kernels right next to the MXU — weight HBM traffic drops 4× vs f32 /
2.67× vs bf16, which is the dominant term of weight-bound decode.  The
CPU/XLA fallback decodes via jnp (fused where XLA can); tests assert
numerical equivalence to dequantize-then-matmul.

`quantize_params` packs transformer/LM matmul kernels;
`quantize_cnn_params` packs a CNN's 4-D conv kernels once at load, so the
model's convs dispatch straight onto the log-conv stack
(`kernels/ops.conv2d`) with no per-step packing.
"""

from __future__ import annotations

import jax

from ..core.logquant import (LogQuantConfig, QuantizedTensor, _scale_for,
                             log_quantize, quantize_tensor)

# matmul kernels eligible for packed serving weights (2D [in, out] layout;
# embeddings stay fp — gathers don't go through log_matmul)
QUANT_LEAVES = frozenset(
    {"wq", "wk", "wv", "wo", "w1", "w2", "w3",
     "ck", "cv", "cr", "wg", "wr"})


def _leaf_name(path) -> str | None:
    for entry in reversed(path):
        if hasattr(entry, "key"):
            return entry.key
    return None


def quantize_params(params, qcfg: LogQuantConfig = LogQuantConfig()):
    """Pack every eligible kernel; leaves stacked scan dims intact (the
    layer scan slices the QuantizedTensor's children per iteration)."""
    import jax.numpy as jnp

    def leaf(path, x):
        name = _leaf_name(path)
        if name in QUANT_LEAVES and x.ndim >= 2:
            if x.ndim >= 3:
                # stacked scan leaf [n_rep, K, N]: the layer scan slices
                # every child along axis 0, so scale per (rep, channel) —
                # the same grid fake-quant sees on each sliced [K, N]
                # (a rep-collapsed max would quantize on a coarser grid).
                axis = tuple(range(1, x.ndim - 1)) if qcfg.per_channel \
                    else tuple(range(1, x.ndim))
                packed, scale = log_quantize(x, qcfg,
                                             scale=_scale_for(x, qcfg, axis))
                return QuantizedTensor(packed, scale, qcfg, x.shape)
            return quantize_tensor(x, qcfg)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


def quantize_cnn_params(params, qcfg: LogQuantConfig = LogQuantConfig()):
    """Pack every conv kernel (4-D ``w`` leaf: [K, K, Cin_g, Cout]) of a
    `models/cnn.py` parameter tree into a `QuantizedTensor` — one packing
    at load time, per-output-channel scales, codes in the kernel's HWIO
    layout.  Biases and the 2-D dense head stay fp (gathers/heads don't go
    through the log kernels)."""

    def leaf(path, x):
        if _leaf_name(path) == "w" and getattr(x, "ndim", 0) == 4:
            return quantize_tensor(x, qcfg)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


def abstract_quantized_params(params_abs, qcfg: LogQuantConfig =
                              LogQuantConfig()):
    """ShapeDtypeStruct version (dry-run path, no allocation)."""
    return jax.eval_shape(lambda p: quantize_params(p, qcfg), params_abs)


def quantized_fraction(params) -> float:
    """Fraction of parameter bytes now stored as 1-byte codes."""
    import jax.numpy as jnp
    total = packed = 0
    for x in jax.tree_util.tree_leaves(params):
        if not hasattr(x, "dtype"):  # e.g. python-int strides in CNN trees
            continue
        n = x.size * getattr(x.dtype, "itemsize", 4)
        total += n
        if x.dtype == jnp.int8:
            packed += n
    return packed / max(total, 1)
