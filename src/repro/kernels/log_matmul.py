"""Pallas TPU kernel: matmul against 6-bit(+sign) log-quantized weights.

This is the TPU-native realisation of the NeuroMAX PE (paper §4) + 2D
weight-broadcast dataflow (§5):

  * Weights live in HBM as packed int8 log codes (sign in bit 6, biased
    base-√2 exponent in bits 0-5) — 2.67× fewer weight bytes than bf16, the
    same saving the paper gets on DDR traffic and SRAM.
  * Each grid step loads one (bk × bn) code block into VMEM **once** and
    broadcasts it across the whole (bm) activation block — the weight-
    stationary "2D broadcast" of §5 mapped onto VMEM tiling.
  * The decode is eq. (8) vectorised: sign · 2^(code/2).  On the VPU
    `exp2` of a half-integer is exactly the LUT(FRAC)·2^INT decomposition
    (2-entry LUT × barrel shift); the MXU then plays the role of the
    108-PE grid + adder nets, accumulating psums in a VMEM scratch so they
    never travel to HBM (the paper's "only 11 % of psums stored" property —
    here it is 0 %: psums stay in the accumulator until the final k step).

Block shapes default to MXU-aligned (128) multiples.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.logquant import LogQuantConfig

DEFAULT_CFG = LogQuantConfig()


def _decode_block(codes, cfg: LogQuantConfig, dtype):
    """Vectorised eq. (8): packed int8 → float block (VPU LUT+shift)."""
    p = codes.astype(jnp.int32)
    mask = (1 << cfg.bits) - 1
    biased = p & mask
    sign = 1.0 - 2.0 * ((p >> cfg.bits) & 1).astype(dtype)
    code = (biased - cfg.bias).astype(dtype)
    mag = jnp.exp2(code / cfg.steps)
    nonzero = (biased != cfg.zero_code).astype(dtype)
    return sign * mag * nonzero


def _log_matmul_kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *,
                       cfg: LogQuantConfig, acc_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # decode the weight block in VMEM (weight-stationary broadcast), then MXU
    w = _decode_block(w_ref[...], cfg, acc_dtype)
    acc_ref[...] += jnp.dot(x_ref[...].astype(acc_dtype), w,
                            preferred_element_type=acc_dtype)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        # per-output-channel scale applied once at psum flush (post-processing
        # block of Fig. 2); psums never left VMEM.
        o_ref[...] = (acc_ref[...] * scale_ref[...].astype(acc_dtype)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "block_m", "block_k",
                                             "block_n", "interpret",
                                             "out_dtype"))
def log_matmul_pallas(x, packed, scale, cfg: LogQuantConfig = DEFAULT_CFG,
                      block_m: int = 128, block_k: int = 128,
                      block_n: int = 128, interpret: bool = False,
                      out_dtype=None):
    """x: [M, K] float; packed: [K, N] int8 codes; scale: [1, N] or [] float.

    Shapes need not be block-aligned; we pad (zero codes decode to 0.0, so
    padding contributes nothing).
    """
    M, K = x.shape
    K2, N = packed.shape
    assert K == K2, (x.shape, packed.shape)
    out_dtype = out_dtype or x.dtype
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (1, N))

    pm, pk, pn = (-M) % block_m, (-K) % block_k, (-N) % block_n
    xp = jnp.pad(x, ((0, pm), (0, pk)))
    wp = jnp.pad(packed, ((0, pk), (0, pn)))  # code 0 ≡ exact zero
    sp = jnp.pad(scale, ((0, 0), (0, pn)))
    Mp, Kp, Np = M + pm, K + pk, N + pn

    acc_dtype = jnp.float32
    out = pl.pallas_call(
        functools.partial(_log_matmul_kernel, cfg=cfg, acc_dtype=acc_dtype),
        grid=(Mp // block_m, Np // block_n, Kp // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), acc_dtype)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(xp, wp, sp)
    return out[:M, :N]
