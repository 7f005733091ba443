"""Pallas TPU kernel: chunked RWKV6 (Finch) WKV with data-dependent decay.

The WKV recurrence  S_t = diag(w_t) S_{t-1} + k_t v_tᵀ,
                    o_t = r_t (S_{t-1} + diag(u) k_t v_tᵀ)
is sequential per token; the chunked form turns it into MXU matmuls.
With P̃_t = Σ_{s≤t} log w_s (per k-channel cumulative log decay):

  intra-chunk:  o = (q̃ K̃ᵀ ⊙ strict-causal) V + diag(r·(u⊙k)) V + q̃ S₀
                q̃_t = r_t ⊙ exp(P̃_{t-1}),   K̃_s = k_s ⊙ exp(−P̃_s)
  state update: S_L = diag(exp(P̃_L)) S₀ + K̂ᵀ V,  K̂_t = k_t ⊙ exp(P̃_L − P̃_t)

The exp factorisation is exact; within a chunk P̃ ∈ [Σlog w, 0] so both
factors are bounded by exp(|Σ log w|) — chunk length bounds the dynamic
range (default 64, safe in fp32 for log w ≥ −40/chunk in practice).

Grid: (B·H, chunks) with chunks innermost/sequential; the running state
S [K, V] lives in VMEM scratch and is carried across chunk steps — the same
"psums never leave the core" property as the paper's adder nets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_math(r, k, v, logw, u, S0):
    """One chunk of the closed form above.  All inputs fp32.

    r, k, logw: [L, K]; v: [L, V]; u: [K] or [1, K]; S0: [K, V]
    → (o [L, V], S_L)."""
    L = r.shape[0]
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # P̃_t as a lower-triangular matmul: Pallas TPU has no cumsum
    p = jnp.dot((ii >= jj).astype(f32), logw, precision=hi,
                preferred_element_type=f32)            # P̃_t, [L, K]
    p_prev = p - logw                                  # P̃_{t-1}
    q_t = r * jnp.exp(p_prev)
    k_t = k * jnp.exp(-p)
    a = jax.lax.dot_general(q_t, k_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)  # [L, L]
    a = jnp.where(ii > jj, a, 0.0)                     # strict causal
    o = jnp.dot(a, v, preferred_element_type=f32)
    o += jnp.sum(r * (u.reshape(1, -1) * k), axis=1, keepdims=True) * v
    o += jnp.dot(q_t, S0, preferred_element_type=f32)
    pL = p[L - 1:]                                     # P̃_L, [1, K]
    k_hat = k * jnp.exp(pL - p)
    # exp(P̃_L) as a [K, 1] column, by a dot that contracts the unit dims
    decay = jax.lax.dot_general(jnp.exp(pL), jnp.ones((1, 1), f32),
                                (((0,), (0,)), ((), ())), precision=hi,
                                preferred_element_type=f32)
    S = decay * S0 + jax.lax.dot_general(
        k_hat, v, (((0,), (0,)), ((), ())), preferred_element_type=f32)
    return o, S


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
                S_ref, *, chunk):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        S_ref[...] = s0_ref[0].astype(jnp.float32)

    o, S = _chunk_math(r_ref[0].astype(jnp.float32),
                       k_ref[0].astype(jnp.float32),
                       v_ref[0].astype(jnp.float32),
                       w_ref[0].astype(jnp.float32),
                       u_ref[0].astype(jnp.float32),
                       S_ref[...])
    o_ref[0] = o.astype(o_ref.dtype)
    S_ref[...] = S

    @pl.when(c == pl.num_programs(1) - 1)
    def _flush():
        sT_ref[0] = S_ref[...].astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, logw, u, state=None, *, chunk=64, interpret=False):
    """r, k: [B, T, H, K]; v: [B, T, H, V]; logw: [B, T, H, K]; u: [H, K].

    Returns (o: [B, T, H, V], S_T: [B, H, K, V]).  T padded to chunk
    multiples with log w = 0, k = 0 (identity updates)."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    if state is None:
        state = jnp.zeros((B, H, K, V), jnp.float32)

    pt = (-T) % chunk
    pad4 = ((0, 0), (0, pt), (0, 0), (0, 0))
    rp, kp, vp, wp = (jnp.pad(a, pad4) for a in (r, k, v, logw))
    Tp = T + pt

    def bh(a):  # [B, T, H, X] → [B·H, T, X]
        return a.transpose(0, 2, 1, 3).reshape(B * H, Tp, -1)

    rp, kp, vp, wp = bh(rp), bh(kp), bh(vp), bh(wp)
    # u rides as [B·H, 1, K] so its (1, K) block spans the array's last two
    # dims, as the TPU's (8, 128) block rule requires
    up = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, 1, K)
    sp = state.reshape(B * H, K, V)

    o, sT = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=(B * H, Tp // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, K), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, K, V), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, K, V), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, V), r.dtype),
            jax.ShapeDtypeStruct((B * H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(rp, kp, vp, wp, up, sp)

    o = o.reshape(B, H, Tp, V).transpose(0, 2, 1, 3)[:, :T]
    return o, sT.reshape(B, H, K, V)


def wkv6_chunked_jnp(r, k, v, logw, u, state=None, *, chunk=64):
    """Pure-jnp chunked fallback (same math; used for CPU lowering paths)."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    f32 = jnp.float32
    if state is None:
        state = jnp.zeros((B, H, K, V), f32)
    pt = (-T) % chunk
    pad4 = ((0, 0), (0, pt), (0, 0), (0, 0))
    rp, kp, vp, wp = (jnp.pad(a.astype(f32), pad4) for a in (r, k, v, logw))
    Tp = T + pt
    nC = Tp // chunk

    def to_chunks(a):  # [B, Tp, H, X] → [nC, B, H, chunk, X]
        X = a.shape[-1]
        return a.reshape(B, nC, chunk, H, X).transpose(1, 0, 3, 2, 4)

    rc, kc, vc, wc = to_chunks(rp), to_chunks(kp), to_chunks(vp), to_chunks(wp)
    uf = u.astype(f32)

    vmapped = jax.vmap(jax.vmap(_chunk_math, in_axes=(0, 0, 0, 0, 0, 0)),
                       in_axes=(0, 0, 0, 0, None, 0))

    def step(S, inp):
        rci, kci, vci, wci = inp
        o, S = vmapped(rci, kci, vci, wci, uf, S)
        return S, o

    S, o = jax.lax.scan(step, state.astype(f32), (rc, kc, vc, wc))
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, Tp, H, V)[:, :T]
    return o.astype(r.dtype), S
