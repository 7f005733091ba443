"""Plain float32 building blocks of the CNN references.

Nothing here imports the program.  Weights are made from the seed by the
same random draws the program's initialisers make (`conv_init` below
follows the published He initialisation the program uses), and packed to
the paper's 6-bit base-√2 log codes by this file's own quantizer: the
reference takes no weights, scales or tables from the program.

``act`` selects the precision of every conv and dense input, one step
below the bfloat16 MXU inputs the configurations state for the controls:

  None       float32, dots at HIGHEST precision: the reference
  "fp8"      float8 e4m3 with one scale per image (its largest magnitude
             maps to 448): the control
  "int8"     symmetric int8 with one scale per image (largest magnitude
             / 127): the second control, the step an int8-activation
             path would take
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A raw threefry key holding all 64 bits of ``seed`` (equal to
    ``jax.random.PRNGKey(seed)`` for seeds below 2**32)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jnp.array([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32)


def conv_init(key, k: int, cin: int, cout: int, groups: int = 1) -> dict:
    """He-normal HWIO kernel and a zero bias (`fill_biases` sets it)."""
    fan_in = k * k * cin // groups
    w = jax.random.normal(key, (k, k, cin // groups, cout), jnp.float32)
    return {"w": w * (2.0 / fan_in) ** 0.5, "b": jnp.zeros((cout,))}


def dense_init(key, cin: int, cout: int) -> dict:
    w = jax.random.normal(key, (cin, cout), jnp.float32) * (1.0 / cin) ** 0.5
    return {"w": w, "b": jnp.zeros((cout,))}


def fill_biases(params, key, std: float):
    """Every leaf named ``b`` becomes ``std`` · N(0, 1), drawn from ``key``
    folded with a hash of the leaf's path.  The harness applies it to the
    program's tree and the reference to its own: equal paths, equal
    biases."""
    def leaf(path, x):
        last = path[-1]
        if getattr(last, "key", None) != "b":
            return x
        k = jax.random.fold_in(key, zlib.crc32(
            jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)
        return std * jax.random.normal(k, x.shape, x.dtype)
    return jax.tree_util.tree_map_with_path(leaf, params)


def log_quantize(w, bits: int, frac_bits: int):
    """Round each weight to ±scale · 2^(c / 2^frac_bits), c an integer in
    [-(2^bits - 2), 0], with one scale per output channel (the largest
    magnitude of that channel); exact zeros stay zero."""
    steps = 1 << frac_bits
    scale = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True)
    scale = jnp.where(scale > 0, scale, 1.0)
    mag = jnp.abs(w) / scale
    code = jnp.round(jnp.log2(jnp.maximum(mag, 1e-38)) * steps)
    code = jnp.clip(code, -((1 << bits) - 2), 0)
    q = jnp.sign(w) * jnp.exp2(code / steps) * scale
    return jnp.where(w == 0, 0.0, q)


def cast_act(x, act: str | None):
    if act is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=tuple(range(1, x.ndim)), keepdims=True)
    if act == "fp8":
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if act == "int8":
        s = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    raise ValueError(f"unknown activation precision {act!r}")


def conv(p: dict, x, *, stride: int = 1, groups: int = 1, act=None):
    """SAME-padded NHWC conv of the decoded log codes, plus bias."""
    y = jax.lax.conv_general_dilated(
        cast_act(x, act), p["wq"], window_strides=(stride, stride),
        padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST)
    return y + p["b"]


def dense(p: dict, x, act=None):
    return jnp.dot(cast_act(x, act), p["w"], precision=HIGHEST) + p["b"]


def maxpool(x, k: int, s: int):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, k, k, 1),
                                 (1, s, s, 1), "VALID")


def global_avgpool(x):
    return jnp.mean(x, axis=(1, 2))


def quantize_convs(params, bits: int, frac_bits: int):
    """Add ``wq``, the log-quantized kernel, beside every 4-D ``w``."""
    def walk(t):
        if isinstance(t, dict):
            out = {k: walk(v) for k, v in t.items()}
            if "w" in t and t["w"].ndim == 4:
                out["wq"] = log_quantize(t["w"], bits, frac_bits)
            return out
        if isinstance(t, list):
            return [walk(v) for v in t]
        return t
    return walk(params)
