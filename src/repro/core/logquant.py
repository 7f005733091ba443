"""Base-√2 logarithmic quantization (NeuroMAX §3, eqs. 1-4).

A log quantizer with parameters ⟨m, n, b⟩ maps x → x' = round(log_b |x|),
clipped to a signed Qm.n range.  For b = 2^(1/2^n) (n = 1 → b = √2) a code is
an integer count of 1/2^n octaves, i.e. log2 with `n` fractional bits.  This
is exactly what makes the hardware cheap: the product of two codes is an
integer add, and 2^(code/2^n) decomposes into a 2^n-entry LUT times a shift
(eq. 8) — see `core/logmath.py` for the bit-exact fixed-point semantics.

Storage layout (matches the paper's w'[6]-is-sign convention):
    packed int8 = (sign << bits) | biased_code,   biased_code ∈ [0, 2^bits)
with a per-channel (or per-tensor) fp scale so the largest magnitude maps to
the top code.  Exact zeros get the *smallest* magnitude code with sign 0 and a
dedicated zero flag folded in: we reserve biased code 0 as "zero" (the paper
special-cases x = 0 in eq. 4).

Also includes the linear Qm.n quantizer (eqs. 1-2) used for the Fig-1
comparison, and a straight-through-estimator fake-quant for training.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "LogQuantConfig",
    "log_quantize",
    "log_dequantize",
    "fake_log_quant",
    "linear_quantize",
    "quantize_tensor",
    "QuantizedTensor",
]


@dataclasses.dataclass(frozen=True)
class LogQuantConfig:
    """⟨m, n, b⟩ of the paper, expressed in bits.

    bits:       exponent-code width (signed range, excludes the sign bit).
                Paper uses 6 ("6-bit log" in Table 2, +1 sign bit on weights).
    frac_bits:  n — fractional bits of the log2 exponent. n=1 → base √2,
                n=0 → base 2. steps-per-octave = 2^n. LUT size = 2^n.
    per_channel: quantize with one scale per trailing channel (axis -1 of the
                canonical [in, out] weight layout) instead of per tensor.
    """

    bits: int = 6
    frac_bits: int = 1
    per_channel: bool = True

    @property
    def steps(self) -> int:  # steps per octave
        return 1 << self.frac_bits

    @property
    def base(self) -> float:
        return float(2.0 ** (1.0 / self.steps))

    @property
    def code_min(self) -> int:
        # biased code 0 is reserved for exact zero; magnitude codes occupy
        # [1, 2^bits - 1], representing unbiased [cmin, 0] with 0 ↦ top code.
        return -((1 << self.bits) - 2)

    @property
    def code_max(self) -> int:
        return 0  # after max-abs normalisation, log2(|x|/scale) ≤ 0

    @property
    def zero_code(self) -> int:
        return 0  # biased

    @property
    def bias(self) -> int:
        # biased = unbiased + bias; unbiased cmin ↦ 1, 0 ↦ 2^bits - 1
        return (1 << self.bits) - 1

    @property
    def storage_bits(self) -> int:
        return self.bits + 1  # + sign

    @property
    def bytes_per_weight(self) -> float:
        return self.storage_bits / 8.0


DEFAULT = LogQuantConfig()


def _scale_for(x: jnp.ndarray, cfg: LogQuantConfig, axis=None):
    a = jnp.abs(x)
    if axis is None:
        s = jnp.max(a)
    else:
        s = jnp.max(a, axis=axis, keepdims=True)
    # avoid log(0); an all-zero tensor/channel quantizes to all-zero codes.
    return jnp.where(s > 0, s, jnp.ones_like(s))


def log_quantize(x: jnp.ndarray, cfg: LogQuantConfig = DEFAULT, scale=None):
    """x → (packed int8 codes, scale).  packed = (sign << bits) | biased_code."""
    if scale is None:
        axis = tuple(range(x.ndim - 1)) if (cfg.per_channel and x.ndim >= 2) else None
        scale = _scale_for(x, cfg, axis)
    mag = jnp.abs(x) / scale
    # log2 with frac_bits of precision; round-to-nearest on the half-step grid
    code = jnp.round(jnp.log2(jnp.maximum(mag, 1e-38)) * cfg.steps)
    code = jnp.clip(code, cfg.code_min, cfg.code_max)
    biased = code.astype(jnp.int32) + cfg.bias
    biased = jnp.where(x == 0, cfg.zero_code, biased)
    sign = (x < 0).astype(jnp.int32)
    packed = (sign << cfg.bits) | biased
    return packed.astype(jnp.int8), scale


def unpack(packed: jnp.ndarray, cfg: LogQuantConfig = DEFAULT):
    """packed int8 → (unbiased code int32, sign ±1, nonzero mask)."""
    p = packed.astype(jnp.int32)
    biased = p & ((1 << cfg.bits) - 1)
    sign = 1 - 2 * ((p >> cfg.bits) & 1)
    nonzero = biased != cfg.zero_code
    code = biased - cfg.bias
    return code, sign, nonzero


def log_dequantize(packed: jnp.ndarray, scale, cfg: LogQuantConfig = DEFAULT,
                   dtype=jnp.float32):
    """Vectorised eq. (8): sign · LUT(FRAC) · 2^INT  ≡  sign · 2^(code/steps)."""
    code, sign, nonzero = unpack(packed, cfg)
    mag = jnp.exp2(code.astype(dtype) / cfg.steps)
    out = sign.astype(dtype) * jnp.where(nonzero, mag, 0.0)
    return (out * scale).astype(dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def fake_log_quant(x, cfg: LogQuantConfig = DEFAULT):
    """Quantize-dequantize with straight-through gradients (for QAT)."""
    packed, scale = log_quantize(x, cfg)
    return log_dequantize(packed, scale, cfg, dtype=x.dtype)


def _fq_fwd(x, cfg):
    return fake_log_quant(x, cfg), None


def _fq_bwd(cfg, _, g):
    return (g,)  # straight-through


fake_log_quant.defvjp(_fq_fwd, _fq_bwd)


def linear_quantize(x: jnp.ndarray, int_bits: int, frac_bits: int):
    """Linear Qm.n quantizer, eqs. (1)-(2), for the Fig-1 comparison."""
    eps = 2.0 ** (-frac_bits)
    lo, hi = -(2.0 ** (int_bits - 1)), 2.0 ** (int_bits - 1) - eps
    return jnp.clip(jnp.round(x / eps) * eps, lo, hi)


# ---------------------------------------------------------------------------
# Pytree container for a quantized parameter, used by serving / kernels.
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """A log-quantized array: int8 packed codes + fp scale (+ static cfg).

    ``packed`` has the natural layout of ``shape`` (a conv kernel is HWIO
    ``[K, K, Cin_g, Cout]``); kernels arrange the codes themselves."""

    def __init__(self, packed, scale, cfg: LogQuantConfig = DEFAULT,
                 shape=None):
        self.packed = packed
        self.scale = scale
        self.cfg = cfg
        self.shape = shape if shape is not None else packed.shape

    def dequantize(self, dtype=jnp.bfloat16):
        return log_dequantize(self.packed, self.scale, self.cfg, dtype=dtype)

    def tree_flatten(self):
        return (self.packed, self.scale), (self.cfg, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def __repr__(self):
        return f"QuantizedTensor(shape={self.shape}, cfg={self.cfg})"


def quantize_tensor(x, cfg: LogQuantConfig = DEFAULT) -> QuantizedTensor:
    packed, scale = log_quantize(x, cfg)
    return QuantizedTensor(packed, scale, cfg, x.shape)


def quantization_snr_db(x, xq):
    """Signal-to-quantization-noise ratio in dB (used by the Fig-1 bench)."""
    x = np.asarray(x, np.float64)
    xq = np.asarray(xq, np.float64)
    num = np.sum(x * x)
    den = np.sum((x - xq) ** 2) + 1e-30
    return float(10.0 * np.log10(num / den + 1e-30))
