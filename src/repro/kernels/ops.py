"""Jit'd public wrappers around the Pallas kernels, with pure-jnp fallbacks.

The unified kernel-call surface.  Every public op takes the same trio of
dispatch knobs, resolved by `resolve_impl` with one precedence order:

  impl=       "pallas" | "blockwise" | "ref" | "auto" (+ op-specific
              aliases, e.g. conv2d's "pallas_im2col").  "auto" → pallas
              on TPU, blockwise elsewhere.
  config=     a per-op frozen config dataclass (`AttentionConfig`,
              `ConvConfig`, `WkvConfig`) holding block sizes / math
              knobs.  Fields left at None are filled from the autotune
              table (`kernels/autotune.py`) when an entry exists for the
              shape, else from per-op heuristics.
  interpret=  None → interpret off-TPU (so Pallas kernels run anywhere);
              an explicit bool always wins.

Plus ``autotune=True`` on the tiled kernels (conv2d, attention) to
measure candidates for the call's shape first and persist the winner.

Implementations per op:
  "pallas"    — the Pallas kernel (TPU; `interpret=True` executes on CPU)
  "blockwise" — pure-jnp blockwise/chunked math (same memory behaviour under
                XLA; this is what model lowering uses on every backend)
  "ref"       — full-materialisation oracle (small shapes / tests)
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.logquant import (LogQuantConfig, QuantizedTensor,
                                 quantize_tensor)
from repro.obs import kernel_profile as _kprof
from . import autotune as _autotune
from . import ref as _ref
from .flash_attention import attention_traffic_bytes, flash_attention_pallas
from .log_conv2d import (conv_traffic_bytes, lane_unpack_codes,
                         log_conv2d_blockwise, log_conv2d_fused_pallas,
                         log_conv2d_pallas, log_conv2d_ref)
from .log_matmul import log_matmul_pallas
from .wkv6 import wkv6_chunked_jnp, wkv6_pallas


def _on_tpu() -> bool:
    # a backend that fails to initialise raises here: picking the CPU
    # fallback instead would hide a missing chip behind a slow run
    return jax.default_backend() == "tpu"


_OP_IMPLS = {
    "log_matmul": ("pallas", "blockwise", "ref"),
    "conv2d": ("pallas", "pallas_im2col", "blockwise", "ref"),
    "attention": ("pallas", "blockwise", "ref"),
    "wkv6": ("pallas", "blockwise", "ref"),
}


def resolve_impl(op: str, impl: str = "auto",
                 interpret: bool | None = None) -> tuple[str, bool]:
    """Resolve (impl, interpret) for one op.  The single precedence order:

    1. an explicit ``impl`` (validated against the op's implementations)
       beats ``"auto"``, which picks "pallas" on TPU and "blockwise"
       elsewhere;
    2. an explicit ``interpret`` bool beats the default ``None``, which
       means "interpret when not on TPU" (Pallas kernels stay runnable on
       CPU CI).  The returned bool only matters for Pallas impls.
    """
    choices = _OP_IMPLS[op]
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "blockwise"
    if impl not in choices:
        raise ValueError(f"unknown {op} impl {impl!r}; expected "
                         f"{'|'.join(choices)}|auto")
    if interpret is None:
        interpret = not _on_tpu()
    return impl, interpret


# ---------------------------------------------------------------------------
# per-op kernel configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    """Tiling/math spec for `attention`.  None block sizes are filled from
    the autotune table (key: `autotune.attention_key`) or heuristics."""
    block_q: int | None = None       # pallas q tile (folded rep·Tq rows)
    block_k: int | None = None       # pallas kv tile / blockwise scan chunk
    acc_dtype: Any = jnp.float32     # blockwise score/accum math dtype
    gqa_broadcast: bool = False      # blockwise: einsum-broadcast GQA


@dataclasses.dataclass(frozen=True)
class ConvConfig:
    """Tiling spec for `conv2d`'s fused kernel; None fields let
    `log_conv2d_fused_pallas` clamp to the layer geometry.

    ``lane_pack`` controls the grouped-conv lane-packed layout (see
    `log_conv2d.lane_pack_geometry`): ``None`` auto-packs narrow groups
    into shared 128-lane blocks, ``1`` forces the padded per-group path,
    ``n ≥ 2`` packs up to ``n`` groups per block.  Precedence: an
    explicit value here beats a `QuantizedTensor`'s baked-in
    ``"lane_packed"`` layout (which is unpacked if they disagree), which
    beats the autotune table, which beats the auto heuristic."""
    block_cin: int | None = None
    block_cout: int | None = None
    rows_per_tile: int | None = None
    batch_per_tile: int | None = None
    lane_pack: int | None = None


@dataclasses.dataclass(frozen=True)
class WkvConfig:
    """Chunking spec for `wkv6` (chunk length bounds the exp dynamic
    range — see `kernels/wkv6.py`)."""
    chunk: int = 64


def _conv_config_dict(config) -> dict | None:
    if config is None:
        return None
    if isinstance(config, ConvConfig):
        return {k: v for k, v in dataclasses.asdict(config).items()
                if v is not None}
    return dict(config)


_CONV_CONFIG_FIELDS = tuple(f.name for f in dataclasses.fields(ConvConfig))

_WARNED_ONCE: set[str] = set()  # one-shot UserWarning dedupe, per process


def _warn_once(msg: str) -> None:
    if msg not in _WARNED_ONCE:
        _WARNED_ONCE.add(msg)
        warnings.warn(msg, UserWarning, stacklevel=3)


def _itemsize(x) -> int:
    try:
        return jnp.dtype(x.dtype).itemsize
    except TypeError:  # pragma: no cover - non-array convenience inputs
        return 4


def _profile_backend(interp: bool) -> str:
    return "interpret" if interp else jax.default_backend()


# ---------------------------------------------------------------------------
# log_matmul
# ---------------------------------------------------------------------------


def log_matmul(x, qt: QuantizedTensor, *, impl: str = "auto",
               interpret: bool | None = None):
    """x: [..., K] @ dequant(qt [K, N]) → [..., N]."""
    impl, interp = resolve_impl("log_matmul", impl, interpret)
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    scale = jnp.broadcast_to(jnp.asarray(qt.scale, jnp.float32),
                             (1, qt.packed.shape[-1]))
    if impl == "pallas":
        call = lambda: log_matmul_pallas(x2, qt.packed, scale, qt.cfg,
                                         interpret=interp, out_dtype=x.dtype)
    else:
        # blockwise == ref for a matmul: XLA fuses decode into the dot's
        # operand; weight bytes moved stay int8.
        call = lambda: _ref.ref_log_matmul(x2, qt.packed, scale, qt.cfg,
                                           out_dtype=x.dtype)
    if _kprof.PROFILER.enabled():
        M, N = x2.shape[0], qt.packed.shape[-1]
        it = _itemsize(x)
        act, w, outb = M * K * it, K * N, M * N * it  # codes move as int8
        traffic = {"act": act, "w": w, "out": outb,
                   "total": act + w + outb}
        key = f"log_matmul|{_profile_backend(interp)}|m{M}|k{K}|n{N}"
        out = _kprof.dispatch("log_matmul", impl, key, traffic, call,
                              traced=_kprof.is_traced(x))
    else:
        out = call()
    return out.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# conv2d — the unified log-domain conv dispatch layer
# ---------------------------------------------------------------------------


def _hashable_padding(padding):
    if isinstance(padding, (list, tuple)):
        return tuple(tuple(p) if isinstance(p, (list, tuple)) else p
                     for p in padding)
    return padding


def conv2d(x, qt, *, stride: int = 1, padding="SAME", groups: int = 1,
           impl: str = "auto", interpret: bool | None = None,
           out_dtype=None, qcfg: LogQuantConfig | None = None,
           config: ConvConfig | dict | None = None, autotune: bool = False):
    """x: [B, H, W, Cin] ⊛ dequant(qt [K, K, Cin//groups, Cout]) → NHWC out.

    The single entry point of the three-tier conv stack (see
    `kernels/log_conv2d.py`): ``impl="pallas"`` is the fused
    implicit-im2col kernel (block sizes from the autotuner's on-disk table
    when present, heuristics otherwise; ``config=`` — a `ConvConfig` or
    plain dict — overrides, ``autotune=True`` measures candidates for
    this shape first and persists the winner), ``"pallas_im2col"`` the
    explicit-im2col fallback on `log_matmul_pallas`, ``"blockwise"`` the
    jnp fallback, ``"ref"`` the full-materialisation oracle; `auto` means
    pallas on TPU and blockwise elsewhere.  `qt` is a `QuantizedTensor`
    of packed log codes (per-output-channel scales supported; the
    serving-time ``layout="conv_taps"`` pre-reshape is accepted); a plain
    float array is packed on the fly as a convenience (inference only —
    quantization is not differentiable).  Supports stride,
    SAME/VALID/explicit padding, and grouped/depthwise convs
    (``groups=Cin``).
    """
    if not isinstance(qt, QuantizedTensor):
        qt = quantize_tensor(jnp.asarray(qt), qcfg or LogQuantConfig())
    packed = qt.packed
    layout = getattr(qt, "layout", None)
    lane_meta = None
    if layout == "conv_taps":
        packed = packed.reshape(qt.shape)  # [taps, cin_g, Cout] → 4-D HWIO
    elif layout == "lane_packed":
        lane_meta = tuple(qt.layout_meta)  # (g_b, cin_lane, groups)
    assert packed.ndim == 4, f"conv weights must be [K,K,Cin_g,Cout], " \
        f"got {packed.shape}"
    impl, interp = resolve_impl("conv2d", impl, interpret)
    padding = _hashable_padding(padding)
    config = _conv_config_dict(config)
    kw = dict(stride=stride, padding=padding, groups=groups,
              out_dtype=out_dtype)
    B, H, W, C = x.shape
    hwio = tuple(qt.shape) if lane_meta is not None else packed.shape
    K, Cout = hwio[0], hwio[-1]
    shape_kw = dict(stride=stride, padding=padding, groups=groups)
    prepacked = False
    if lane_meta is not None:
        # a baked "lane_packed" layout rides straight onto the fused
        # kernel when it matches this call; any disagreement (different
        # groups, an explicit conflicting lane_pack, a non-fused impl, or
        # an autotune sweep) falls back to unpacking the compact codes to
        # HWIO — always correct, just without the pre-arranged layout.
        g_b, cin_lane, meta_groups = lane_meta
        want = (config or {}).get("lane_pack")
        usable = (impl == "pallas" and meta_groups == groups
                  and want in (None, g_b) and not autotune)
        if usable:
            prepacked = True
        else:
            if (autotune and impl == "pallas" and meta_groups == groups
                    and want in (None, g_b)):
                # the sweep still runs, but silently discarding the baked
                # layout surprises callers expecting the prepacked path
                _warn_once(
                    "ops.conv2d: autotune=True unpacked the baked "
                    "'lane_packed' weight layout for the tuning sweep; the "
                    "tuned entry applies to the unpacked HWIO path")
            packed = lane_unpack_codes(packed, hwio, meta_groups, g_b,
                                       cin_lane)
    if impl == "pallas":
        explicit = config or {}
        if autotune and explicit:
            _warn_once(
                f"ops.conv2d: autotune=True is a no-op because config= pins "
                f"{sorted(explicit)}; drop the explicit config to run the "
                f"tuning sweep for this shape")
        if autotune and not explicit:
            config = _autotune.autotune_conv2d(
                x, packed, qt.scale, qt.cfg, interpret=interp, **shape_kw)
        elif any(f not in explicit for f in _CONV_CONFIG_FIELDS):
            # the documented contract: fields left unset are filled
            # per-field from the layered autotune table (or heuristics) —
            # a partial config (e.g. only lane_pack) keeps the tuned tiling
            key = _autotune.conv_key(
                B, H, W, C, K, Cout, cfg=qt.cfg, **shape_kw,
                backend=("interpret" if interp else None))
            tuned = _autotune.lookup(key) or _autotune.default_config(
                B, H, W, C, K, Cout, **shape_kw)
            config = {**tuned, **explicit}
        else:
            config = explicit
        if prepacked:  # the baked layout forces its own lane_pack factor
            config = dict(config, lane_pack=lane_meta[0])
        call = lambda: log_conv2d_fused_pallas(x, packed, qt.scale, qt.cfg,
                                               interpret=interp,
                                               prepacked=prepacked, **kw,
                                               **config)
    elif impl == "pallas_im2col":
        call = lambda: log_conv2d_pallas(x, packed, qt.scale, qt.cfg,
                                         interpret=interp, **kw)
    elif impl == "ref":
        call = lambda: log_conv2d_ref(x, packed, qt.scale, qt.cfg, **kw)
    else:
        call = lambda: log_conv2d_blockwise(x, packed, qt.scale, qt.cfg,
                                            **kw)
    if not _kprof.PROFILER.enabled():
        return call()
    # the oracle materialises full-precision patches: model it as "fp32"
    traffic_impl = {"ref": "fp32"}.get(impl, impl)
    traffic = conv_traffic_bytes(
        traffic_impl, B, H, W, C, K, Cout, **shape_kw,
        config=(config if impl == "pallas" else None))
    key = _autotune.conv_key(B, H, W, C, K, Cout, cfg=qt.cfg, **shape_kw,
                             backend=_profile_backend(interp))
    return _kprof.dispatch("conv2d", impl, key, traffic, call,
                           traced=_kprof.is_traced(x, packed))


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _blockwise_attention(q, k, v, *, causal, window, scale, q_offset,
                         k_offset=0, block_k: int = 1024,
                         acc_dtype=jnp.float32, gqa_broadcast: bool = False):
    """Online-softmax over kv blocks with lax.scan — O(Tq·bk) live memory.

    q: [B, Tq, H, D]; k, v: [B, Tk, Hkv, D].

    §Perf knobs: `acc_dtype` runs the score/accumulator math in bf16
    (running max/sum stay f32 for stability); `gqa_broadcast` reshapes q to
    [B,Tq,Hkv,rep,D] and contracts against unexpanded K/V instead of
    materialising rep× repeated K/V blocks."""
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    f32 = jnp.float32
    cdt = acc_dtype

    pk = (-Tk) % block_k
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nkv = (Tk + pk) // block_k
    # [nkv, B, bk, Hkv, D]
    kc = kp.reshape(B, nkv, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(B, nkv, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)

    use_bcast = gqa_broadcast and rep > 1
    qf = (q.astype(cdt) * jnp.asarray(scale, cdt))
    if use_bcast:
        qf = qf.reshape(B, Tq, Hkv, rep, D)
    qpos = jnp.arange(Tq) + q_offset

    def step(carry, inp):
        m, l, acc = carry                 # [B,H,Tq,1], [B,H,Tq,1], [B,H,Tq,D]
        kb, vb, kv_idx = inp
        if use_bcast:
            # s: [B, Hkv, rep, Tq, bk] without expanding K
            s = jnp.einsum("bqhrd,bkhd->bhrqk", qf, kb.astype(cdt))
            s = s.reshape(B, H, Tq, block_k)
        else:
            if rep > 1:
                kb = jnp.repeat(kb, rep, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(cdt))
        s = s.astype(f32)
        kpos = kv_idx * block_k + jnp.arange(block_k) + k_offset
        mask = (kpos[None, :] < Tk + k_offset) & (kpos[None, :] >= 0)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= (qpos[:, None] - kpos[None, :]) < window
        s = jnp.where(mask[None, None], s, -1e30)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.where(mask[None, None], jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        if use_bcast:
            pv = jnp.einsum("bhrqk,bkhd->bqhrd",
                            p.reshape(B, Hkv, rep, Tq, block_k).astype(cdt),
                            vb.astype(cdt))
            pv = pv.reshape(B, Tq, H, D).transpose(0, 2, 1, 3)
        else:
            vb_ = jnp.repeat(vb, rep, axis=2) if rep > 1 else vb
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(cdt),
                            vb_.astype(cdt))
        acc = alpha * acc + pv.astype(f32)
        return (m_new, l, acc), None

    init = (jnp.full((B, H, Tq, 1), -1e30, f32),
            jnp.zeros((B, H, Tq, 1), f32),
            jnp.zeros((B, H, Tq, D), f32))
    (m, l, acc), _ = jax.lax.scan(step, init,
                                  (kc, vc, jnp.arange(nkv)))
    out = acc / jnp.where(l > 0, l, 1.0)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


_UNSET = object()  # legacy-kwarg sentinel: distinguishes "not passed"

_LEGACY_ATTN_FIELDS = ("block_k", "acc_dtype", "gqa_broadcast")


def _translate_legacy_attn_kwargs(config, legacy: dict):
    """One-release deprecation shim: `block_k=`/`acc_dtype=`/
    `gqa_broadcast=` become `AttentionConfig` fields."""
    passed = {n: v for n, v in legacy.items() if v is not _UNSET}
    if not passed:
        return config or AttentionConfig()
    warnings.warn(
        f"ops.attention({', '.join(sorted(passed))}=…) is deprecated; pass "
        f"config=AttentionConfig(...) instead (legacy kwargs are removed "
        f"next release)", DeprecationWarning, stacklevel=3)
    if config is not None:
        raise ValueError("pass either config=AttentionConfig(...) or the "
                         f"legacy kwargs {sorted(passed)}, not both")
    return AttentionConfig(**passed)


def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              scale=None, q_offset=0, k_offset=0, impl: str = "auto",
              config: AttentionConfig | None = None, autotune: bool = False,
              interpret: bool | None = None, block_k=_UNSET,
              acc_dtype=_UNSET, gqa_broadcast=_UNSET):
    """GQA/MQA attention.  q: [B, Tq, H, D]; k, v: [B, Tk, Hkv, D] with H a
    multiple of Hkv.

    The Pallas impl is GQA-native: an explicit kv-head grid dimension
    loads each kv head's K/V tiles into VMEM once and broadcasts them
    across its H/Hkv query heads, so K/V HBM traffic scales with Hkv (no
    `jnp.repeat` anywhere).  `q_offset`/`k_offset` may be traced scalars
    (decode at a dynamic cache index) on every impl — the kernel takes
    them as scalar-prefetch operands.

    Block sizes come from ``config=AttentionConfig(...)``; fields left at
    None are filled from the autotune table (``autotune=True`` measures
    candidates for this shape first) or heuristics.  ``block_k=`` /
    ``acc_dtype=`` / ``gqa_broadcast=`` remain accepted as deprecated
    aliases for one release.
    """
    config = _translate_legacy_attn_kwargs(
        config, dict(block_k=block_k, acc_dtype=acc_dtype,
                     gqa_broadcast=gqa_broadcast))
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    if k.shape != v.shape or k.shape[0] != B or k.shape[3] != D:
        raise ValueError(f"inconsistent attention operands: q {q.shape}, "
                         f"k {k.shape}, v {v.shape}")
    if Hkv == 0 or H % Hkv != 0:
        raise ValueError(
            f"GQA requires query heads divisible by kv heads; got H={H} "
            f"query heads vs Hkv={Hkv} kv heads (q {q.shape}, k {k.shape})")
    impl, interp = resolve_impl("attention", impl, interpret)
    traffic_kw = {}
    if impl == "ref":
        call = lambda: _ref.ref_attention(q, k, v, causal=causal,
                                          window=window, scale=scale,
                                          q_offset=q_offset,
                                          k_offset=k_offset)
    elif impl == "blockwise":
        call = lambda: _blockwise_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            q_offset=q_offset, k_offset=k_offset,
            block_k=config.block_k or 1024, acc_dtype=config.acc_dtype,
            gqa_broadcast=config.gqa_broadcast)
    else:
        # pallas (GQA-native; dynamic offsets ride the scalar-prefetch
        # operand)
        bq, bk = config.block_q, config.block_k
        if autotune and bq is not None and bk is not None:
            _warn_once(
                "ops.attention: autotune=True is a no-op because config= "
                "pins both block_q and block_k; leave one unset to run the "
                "tuning sweep for this shape")
        if bq is None or bk is None:
            if autotune:
                tuned = _autotune.autotune_attention(
                    q, k, v, causal=causal, window=window, scale=scale,
                    interpret=interp)
            else:
                key = _autotune.attention_key(
                    B, Tq, Tk, H, Hkv, D, causal=causal, window=window,
                    backend=("interpret" if interp else None))
                tuned = _autotune.lookup(key) or \
                    _autotune.default_attention_config(B, Tq, Tk, H, Hkv, D)
            bq = bq if bq is not None else tuned["block_q"]
            bk = bk if bk is not None else tuned["block_k"]
        traffic_kw = dict(block_q=bq, block_k=bk)
        call = lambda: flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale,
            q_offset=q_offset, k_offset=k_offset, block_q=bq, block_k=bk,
            interpret=interp)
    if not _kprof.PROFILER.enabled():
        return call()
    traffic = attention_traffic_bytes(impl, B, Tq, Tk, H, Hkv, D,
                                      itemsize=_itemsize(q), **traffic_kw)
    key = _autotune.attention_key(B, Tq, Tk, H, Hkv, D, causal=causal,
                                  window=window,
                                  backend=_profile_backend(interp))
    return _kprof.dispatch(
        "attention", impl, key, traffic, call,
        traced=_kprof.is_traced(q, k, v, q_offset, k_offset))


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------


def wkv6(r, k, v, logw, u, state=None, *, impl: str = "auto",
         config: WkvConfig | None = None, chunk: int | None = None,
         interpret: bool | None = None):
    """RWKV6 WKV.  ``config=WkvConfig(chunk=…)`` is the spec'd surface;
    ``chunk=`` stays as a positional-friendly alias."""
    impl, interp = resolve_impl("wkv6", impl, interpret)
    chunk = chunk if chunk is not None else (config or WkvConfig()).chunk
    if impl == "ref":
        call = lambda: _ref.ref_wkv6(r, k, v, logw, u, state)
    elif impl == "blockwise":
        call = lambda: wkv6_chunked_jnp(r, k, v, logw, u, state, chunk=chunk)
    else:
        call = lambda: wkv6_pallas(r, k, v, logw, u, state, chunk=chunk,
                                   interpret=interp)
    if not _kprof.PROFILER.enabled():
        return call()
    B, T, H, K = r.shape
    V = v.shape[-1]
    it = _itemsize(r)
    rkw = 3 * B * T * H * K * it            # r, k and per-step decay logw
    vb = 2 * B * T * H * V * it             # v in, wkv out
    st = 2 * B * H * K * V * 4              # state read + write (f32)
    traffic = {"rkw": rkw, "v": vb, "state": st, "u": H * K * it,
               "total": rkw + vb + st + H * K * it}
    key = (f"wkv6|{_profile_backend(interp)}|b{B}|t{T}|h{H}|k{K}|v{V}"
           f"|c{chunk}")
    return _kprof.dispatch("wkv6", impl, key, traffic, call,
                           traced=_kprof.is_traced(r, k, v, logw, u, state))
