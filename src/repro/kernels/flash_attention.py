"""Pallas TPU kernel: blockwise online-softmax attention, GQA-native.

Perf-critical hot spot for the prefill_32k / long-context cells: a full
[Tq, Tk] score matrix at 32k² is ~4 GB per head in fp32 — blockwise online
softmax keeps the working set at (bq × bk) in VMEM.  Supports causal
masking and sliding windows (gemma3 local layers, RecurrentGemma local
attention).

GQA/MQA is native: the grid carries an explicit kv-head dimension and the
`rep = H // Hkv` query heads of each group are folded into the q-row axis,
so one K/V tile is DMA'd into VMEM per (batch, kv head, q block, kv block)
step and broadcast across all of its query heads — the paper's 2D
weight-broadcast dataflow, applied to K/V operands.  K/V HBM traffic
scales with Hkv, not H (no `jnp.repeat` expansion anywhere).

Decode offsets (`q_offset`, `k_offset`) are scalar-prefetch operands, so
they may be traced values: single-token decode at a dynamic cache index
runs on this kernel instead of falling back to the jnp path.

Grid: (batch, kv_heads, q_blocks, kv_blocks), kv innermost ("arbitrary"
semantics) with running (m, l, acc) scratch carried across kv steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                 *, scale, causal, window, block_q, block_k, q_len, kv_len):
    kv = pl.program_id(3)

    @pl.when(kv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)               # [bk, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]

    # q rows are the folded (rep · Tq) axis: row r belongs to query head
    # r // Tq of the group at in-head position r % Tq — all rep heads of a
    # kv group share positions, so only r % Tq feeds the mask.
    row = (pl.program_id(2) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0))
    qpos = jax.lax.rem(row, q_len) + off_ref[0]
    kidx = (kv * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1))
    kpos = kidx + off_ref[1]
    # padded kv columns never contribute; ring slots at absolute pos < 0
    # (never written) are masked by k_offset semantics
    mask = (kidx < kv_len) & (kpos >= 0)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (exp(NEG_INF - NEG_INF) would be 1)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv == pl.num_programs(3) - 1)
    def _flush():
        l = jnp.where(l_ref[...] > 0, l_ref[...], 1.0)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret", "scale"))
def flash_attention_pallas(q, k, v, *, causal=True, window=None, scale=None,
                           q_offset=0, k_offset=0, block_q=128, block_k=128,
                           interpret=False):
    """q: [B, Tq, H, D]; k, v: [B, Tk, Hkv, D] with H a multiple of Hkv.

    `q_offset` is the absolute position of q[0] (decode: Tk - Tq);
    `k_offset` the absolute position of k[0] (ring caches) — both may be
    traced scalars (scalar-prefetch operands, not trace-time constants).
    Tq/Tk are padded to block multiples; padded kv columns are masked by
    index and padded q rows are sliced off."""
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)

    # fold each kv group's `rep` query heads into the row axis, THEN pad:
    # a q block packs rows of several heads (decode: all rep heads of the
    # group in one block) so the K/V tile in VMEM serves every one of them.
    qf = q.reshape(B, Tq, Hkv, rep, D).transpose(0, 2, 3, 1, 4) \
          .reshape(B, Hkv, rep * Tq, D)
    rows = rep * Tq
    pq, pk = (-rows) % block_q, (-Tk) % block_k
    qp = jnp.pad(qf, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pk), (0, 0)))
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)])

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, q_len=Tq, kv_len=Tk)

    grid = (B, Hkv, (rows + pq) // block_q, (Tk + pk) // block_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, i, j, off: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, i, j, off: (b, h, j, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, i, j, off: (b, h, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, D),
                                   lambda b, h, i, j, off: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows + pq, D), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(offs, qp, kp, vp)
    return out[:, :, :rows].reshape(B, Hkv, rep, Tq, D) \
              .transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, D)


# ---------------------------------------------------------------------------
# analytic HBM traffic
# ---------------------------------------------------------------------------


def attention_traffic_bytes(impl: str, B: int, Tq: int, Tk: int, H: int,
                            Hkv: int, D: int, *, block_q: int = 128,
                            block_k: int = 128, itemsize: int = 4) -> dict:
    """Bytes moved HBM↔VMEM for one attention call, per implementation.

    First-order model (same conventions as `log_conv2d.conv_traffic_bytes`):
    counts every block fetch the grid performs — K/V tiles are re-read once
    per q block, q and out move once — plus any HBM materialisation the
    path needs.  ``"repeat"`` models the legacy dispatch that expanded K/V
    to H heads with `jnp.repeat` before a per-(batch·head) kernel: the
    expanded arrays are written to HBM and then streamed per q block, so
    its K/V term scales with H while the native ``"pallas"`` path scales
    with Hkv.  Returns ``{"q", "kv", "out", "total"}``.
    """
    rep = H // Hkv
    q_b = B * Tq * H * D * itemsize
    out_b = q_b
    kv_arr = 2 * B * Tk * Hkv * D * itemsize         # K and V as stored
    if impl == "pallas":                              # native GQA kernel
        n_qb = -(-rep * Tq // block_q)                # folded-row q blocks
        kv = kv_arr * n_qb
    elif impl == "repeat":                            # legacy expand path
        n_qb = -(-Tq // block_q)                      # per-head q blocks
        kv = kv_arr * rep + kv_arr * rep * n_qb       # materialise + stream
    elif impl == "blockwise":
        # lax.scan over kv chunks with all heads resident: K/V once.
        kv = kv_arr
    elif impl == "ref":
        # full score matrix hits HBM (write + read), K/V rep-expanded.
        kv = kv_arr * rep + 2 * B * H * Tq * Tk * itemsize
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return {"q": int(q_b), "kv": int(kv), "out": int(out_b),
            "total": int(q_b + kv + out_b)}
