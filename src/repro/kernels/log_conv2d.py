"""NHWC conv2d against packed 6-bit(+sign) log-quantized weights.

This is the conv realisation of the NeuroMAX log-PE + 2D weight-broadcast
dataflow on TPU, and the middle of the repo's three-tier conv stack:

    kernels/log_conv2d.py  (this file, Pallas + blockwise + ref)
        ↕  numerics cross-checked in tests/test_conv2d.py
    core/pe_grid.py        (cycle-accurate 6×3×6 PE-grid hardware oracle)

Three implementations share one contract (see `kernels/ops.conv2d` for the
dispatch layer):

  * ``log_conv2d_fused_pallas`` — direct NHWC conv: patch extraction
    happens *in VMEM* (implicit im2col).  The grid walks (batch·row tiles,
    groups, output-channel tiles, reduction over Cin blocks), each step
    looping over the K² taps; an activation slab is loaded once per tile
    and re-sliced for every tap (line-buffer-style reuse of the paper's §5
    weight broadcast — no K²× patch blow-up in HBM), weight codes stay
    packed int8 in HBM and decode next to the MXU (eq. 8's LUT+shift as
    `exp2` of a half-integer), and psums stay in the VMEM accumulator
    until flush.  Grouped/depthwise convs are a grid dimension over
    groups — each step contracts only its group's `cin_g` slice, so no
    block-diagonal `groups`× byte/FLOP waste.
    Block sizes (`block_cin/block_cout/rows_per_tile/batch_per_tile`) are
    tunable; `kernels/autotune.py` measures and persists winners.
    A dense conv with too few input channels to fill the 128 lanes (the
    3-channel first conv of each zoo net) has its taps **folded** into
    channels instead: its patches (K²·Cin features per output pixel) are
    built in HBM and run as a 1×1 conv (see `_fold_pays`), both at the
    kernel's `DOT_PRECISION`.  Unfolded, each (8, 128) tile of its input
    would carry 3 useful lanes of 128, and each of the K² taps would
    contract 3 lanes.
  * ``log_conv2d_blockwise`` — decode-then-`lax.conv` in jnp.  XLA fuses the
    int8→float decode into the convolution's weight operand, so the weight
    bytes that move stay int8 (same memory behaviour as the kernel); this
    is what model lowering uses on every backend without Pallas.
  * ``log_conv2d_ref`` — full-materialisation oracle: explicit im2col
    patches against `ref.ref_log_matmul` at highest precision.  Independent
    of `lax.conv`, so it cross-validates the patch extraction itself.

All three take the same packed layout: ``packed [K, K, Cin//groups, Cout]``
int8 codes with a per-output-channel (or scalar) fp scale, `stride`,
`padding` ("SAME"/"VALID"/int/explicit pairs) and `groups`; the fused
kernel arranges the codes for its launch itself, under its ``weights``
scope.  `conv_traffic_bytes` is the shared analytic HBM-traffic model.

Grouped/depthwise convs are additionally **lane-packed** inside the
fused kernel (see `lane_pack_geometry`): on real TPUs the MXU/VPU
lane dimension is 128 wide, so a contraction over one group's `cin_g`
channels occupies a full 128-lane block no matter how narrow the group —
at depthwise `cin_g = 1` that is 1/128 lane density.  Lane packing
arranges ``G_b = floor(128 / cin_lane)`` groups side by side in one lane
block (``cin_lane`` = `cin_g` padded to a power of two) so one MXU pass
contracts `G_b` groups at once; the compact codes are **unpacked next to
the MXU** by an in-kernel masked broadcast (lane `l` serves group
``l // cin_lane``; out-of-group taps multiply by an exact 0), so HBM
weight traffic stays compact — no block-diagonal expansion ever leaves
VMEM.  `ops.ConvConfig(lane_pack=...)`, the autotune table or the
heuristic selects the packing per call.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.logquant import LogQuantConfig, log_dequantize
from .log_matmul import _decode_block
from .ref import ref_log_matmul

DEFAULT_CFG = LogQuantConfig()

# Precision of the fused kernel's dots and of the folded conv's patch build
# (`_fold_patches`): one setting, because on a TPU it decides how both round
# float32 operands (DEFAULT: to bfloat16, in XLA and in Mosaic alike).  A
# folded conv is the same arithmetic as the unfolded one only while the
# patches carry x as the kernel's dot would have rounded it.
DOT_PRECISION = jax.lax.Precision.DEFAULT


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------


def _pad_pair(size: int, k: int, stride: int) -> tuple[int, int]:
    """XLA-style SAME padding for one spatial dim."""
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return total // 2, total - total // 2


def normalize_padding(padding, K: int, stride: int, H: int, W: int):
    """→ ((lo_h, hi_h), (lo_w, hi_w)), accepting SAME/VALID/int/pairs."""
    if isinstance(padding, str):
        p = padding.upper()
        if p == "VALID":
            return (0, 0), (0, 0)
        if p == "SAME":
            return _pad_pair(H, K, stride), _pad_pair(W, K, stride)
        raise ValueError(f"unknown padding {padding!r}")
    if isinstance(padding, int):
        return (padding, padding), (padding, padding)
    (ph, pw) = padding
    if isinstance(ph, int):
        return (ph, ph), (pw, pw)
    return tuple(ph), tuple(pw)


def _out_size(size: int, k: int, stride: int, pads: tuple[int, int]) -> int:
    return (size + pads[0] + pads[1] - k) // stride + 1


def _im2col(x, K: int, stride: int, pads):
    """x: [B, H, W, C] → patches [B, Ho, Wo, K*K*C], tap-major (kh, kw, c).

    The tap ordering matches ``w.reshape(K*K*Cin, Cout)`` of an HWIO kernel,
    so a plain matmul against the reshaped weight is the convolution.
    """
    B, H, W, C = x.shape
    (ph0, ph1), (pw0, pw1) = pads
    xp = jnp.pad(x, ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0)))
    Ho = _out_size(H, K, stride, (ph0, ph1))
    Wo = _out_size(W, K, stride, (pw0, pw1))
    taps = []
    for kh in range(K):
        for kw in range(K):
            taps.append(jax.lax.slice(
                xp, (0, kh, kw, 0),
                (B, kh + (Ho - 1) * stride + 1, kw + (Wo - 1) * stride + 1, C),
                (1, stride, stride, 1)))
    patches = jnp.stack(taps, axis=3)            # [B, Ho, Wo, K*K, C]
    return patches.reshape(B, Ho, Wo, K * K * C), Ho, Wo


def _fold_patches(x, K: int, stride: int, pads):
    """x: [B, H, W, C] → its patches as one image ``[1, Ho·B, Wo, K²·C]``
    of the conv's output pixels in (row, column, batch) order, each with
    its features in `_im2col`'s (kh, kw, c) order.

    The patches are a conv of x with a one-hot filter.  XLA's conv reads a
    narrow-channel input once (a strided tap slice of it is a slow pass
    over lanes) and writes its output with the batch beside the features,
    so this layout costs no relayout.  Each feature is one product with
    an exact 1.0 at `DOT_PRECISION`, the kernel's own: it holds x rounded
    as the kernel's dot would round it."""
    B, _, _, C = x.shape
    onehot = jnp.eye(K * K * C, dtype=x.dtype).reshape(K, K, C, K * K * C)
    p = jax.lax.conv_general_dilated(
        x, onehot, window_strides=(stride, stride), padding=pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=DOT_PRECISION)
    _, Ho, Wo, F = p.shape
    return p.transpose(1, 2, 0, 3).reshape(1, Ho * B, Wo, F)


def _block_diag_codes(packed, groups: int):
    """packed [K, K, cin_g, Cout] → [K*K*(groups·cin_g), Cout] block-diagonal
    int8 codes: row (tap, g, i) holds the code for output channels of group
    g only; everywhere else the zero code (int8 0), which decodes to 0.0."""
    K1, K2, cin_g, Cout = packed.shape
    cout_g = Cout // groups
    taps = K1 * K2
    w = packed.reshape(taps, cin_g, Cout)
    if groups == 1:
        return w.reshape(taps * cin_g, Cout)
    group_of_out = jnp.arange(Cout) // cout_g                 # [Cout]
    in_group = group_of_out[None, :] == jnp.arange(groups)[:, None]
    # [taps, g, i, o] — keep codes only where o belongs to group g
    wbd = w[:, None, :, :] * in_group[None, :, None, :].astype(packed.dtype)
    return wbd.reshape(taps * groups * cin_g, Cout)


def _check_shapes(x, packed, groups):
    B, H, W, C = x.shape
    K1, K2, cin_g, Cout = packed.shape
    assert K1 == K2, f"square kernels only, got {K1}x{K2}"
    assert C == cin_g * groups, (x.shape, packed.shape, groups)
    assert Cout % groups == 0, (Cout, groups)
    return B, H, W, C, K1, Cout


# ---------------------------------------------------------------------------
# the blockwise fallback
# ---------------------------------------------------------------------------


def log_conv2d_blockwise(x, packed, scale, cfg: LogQuantConfig = DEFAULT_CFG,
                         *, stride: int = 1, padding="SAME", groups: int = 1,
                         out_dtype=None):
    """Decode-then-conv fallback; XLA keeps the moved weight bytes int8."""
    B, H, W, C, K, Cout = _check_shapes(x, packed, groups)
    pads = normalize_padding(padding, K, stride, H, W)
    scale = jnp.asarray(scale, jnp.float32).reshape(1, -1)
    w = log_dequantize(packed, scale.reshape(1, 1, 1, -1), cfg,
                       dtype=jnp.float32)
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w, window_strides=(stride, stride),
        padding=pads, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    return y.astype(out_dtype or x.dtype)


# ---------------------------------------------------------------------------
# lane-packed grouped-conv layout
# ---------------------------------------------------------------------------

LANES = 128  # physical MXU/VPU lane width the packed layout targets


def _ceil_to(n: int, b: int) -> int:
    return -(-n // b) * b


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def lane_pack_geometry(groups: int, cin_g: int, lane_pack: int | None = None,
                       lanes: int = LANES) -> dict:
    """Resolve how many groups share one lane block for a grouped conv.

    ``lane_pack``: ``None`` → auto (pack whenever ≥2 groups fit a lane
    block), ``0``/``1`` → disabled (the padded per-group path), ``n ≥ 2``
    → pack up to ``n`` groups (clamped to what the lanes can hold).

    Returns ``{"g_b", "cin_lane", "n_sb"}``: groups per block (1 = off),
    each group's channel slot (`cin_g` padded to a power of two so blocks
    tile the 128 lanes evenly), and the superblock count
    ``ceil(groups / g_b)``.  The packed lane block is ``Lc = g_b *
    cin_lane`` wide; lane ``l`` belongs to group ``l // cin_lane`` — that
    integer map is the whole group-to-lane bookkeeping, recomputed by an
    iota inside the kernel.
    """
    off = dict(g_b=1, cin_lane=cin_g, n_sb=groups)
    if groups <= 1 or (lane_pack is not None and lane_pack <= 1):
        return off
    cin_lane = _next_pow2(cin_g)
    g_b = lanes // cin_lane if cin_lane <= lanes else 0
    if lane_pack is not None:
        g_b = min(g_b, lane_pack)
    g_b = min(g_b, groups)
    if g_b < 2:
        return off
    return dict(g_b=g_b, cin_lane=cin_lane, n_sb=-(-groups // g_b))


def lane_pack_codes(packed, groups: int, g_b: int, cin_lane: int):
    """packed [K, K, cin_g, Cout] → [n_sb, K*K, g_b*cin_lane, Cout//groups]
    int8 codes, lane-major within a superblock (lane ``g*cin_lane + i``
    holds group ``g``'s channel ``i``).  Padding — `cin_g` → `cin_lane`
    and `groups` → `n_sb*g_b` — uses int8 0, the dedicated zero code."""
    K1, K2, cin_g, Cout = packed.shape
    taps, cout_g = K1 * K2, Cout // groups
    n_sb = -(-groups // g_b)
    w = packed.reshape(taps, cin_g, groups, cout_g)
    w = jnp.pad(w, ((0, 0), (0, cin_lane - cin_g),
                    (0, n_sb * g_b - groups), (0, 0)))
    w = w.transpose(2, 0, 1, 3).reshape(n_sb, g_b, taps, cin_lane, cout_g)
    return w.transpose(0, 2, 1, 3, 4).reshape(n_sb, taps, g_b * cin_lane,
                                              cout_g)


# ---------------------------------------------------------------------------
# fused implicit-im2col kernel
# ---------------------------------------------------------------------------


def _fit_dim(x, axis: int, size: int):
    """Pad with zeros or crop so ``x.shape[axis] == size`` (trailing edge)."""
    cur = x.shape[axis]
    if cur < size:
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, size - cur)
        return jnp.pad(x, pads)
    if cur > size:
        return jax.lax.slice_in_dim(x, 0, size, axis=axis)
    return x


SUBLANES = 8  # 32-bit rows per VMEM tile (int8 packs 4× as many)

# VMEM one fused-conv grid step may plan for: half of a v5e's 16 MiB scoped
# default, leaving the rest to Mosaic's own scratch
VMEM_BUDGET_BYTES = 8 << 20


def _vmem_tile_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """Bytes a [rows, cols] array takes in VMEM: the last dim padded to 128
    lanes, the second-last to whole sublane tiles."""
    return (_ceil_to(rows, SUBLANES * 4 // itemsize) * _ceil_to(cols, LANES)
            * itemsize)


def _fused_vmem_bytes(*, bt, rt, rows_in, Wp, Wo, bcin, bcout, ow, taps,
                      stride, g_b) -> int:
    """VMEM one grid step of the fused kernel uses, laid out as the chip
    lays it out (f32 activations, int8 codes): the double-buffered
    activation slab, code, scale and output blocks, the psum scratch, and
    the values one tap keeps live — its strided slab slice, the patch, the
    decoded (lane-packed: mask-expanded) weight block and the dot result."""
    M = bt * rt * Wo
    streamed = (bt * rows_in * _vmem_tile_bytes(Wp, bcin)
                + taps * _vmem_tile_bytes(bcin, bcout, 1)
                + _vmem_tile_bytes(1, ow)
                + bt * rt * _vmem_tile_bytes(Wo, ow))
    acc = _vmem_tile_bytes(M, ow)
    tap = (bt * rt * stride * _vmem_tile_bytes(Wo * stride, bcin)
           + _vmem_tile_bytes(M, bcin) + _vmem_tile_bytes(bcin, ow) + acc)
    if g_b > 1:
        tap += bcin * _vmem_tile_bytes(bcout, g_b)
    return 2 * streamed + acc + tap


def _fold_pays(H: int, W: int, C: int, K: int, groups: int, pads, Ho: int,
               Wo: int) -> bool:
    """Whether a conv's taps fold into its channels: a dense conv whose
    patches ``[Ho, Wo, K²·C]``, padded to whole 128-lane blocks, are
    smaller than its padded input ``[Hp, Wp, C]`` padded the same way.
    That holds where C is too narrow to fill the lanes (the 3-channel
    first conv of every zoo net), and never for a grouped conv."""
    (ph0, ph1), (pw0, pw1) = pads
    return (groups == 1 and K > 1
            and Ho * Wo * _ceil_to(K * K * C, LANES)
            < (H + ph0 + ph1) * (W + pw0 + pw1) * _ceil_to(C, LANES))


def fused_conv_geometry(B: int, H: int, W: int, C: int, K: int, Cout: int,
                        *, stride: int = 1, padding="SAME", groups: int = 1,
                        block_cin: int | None = 128, block_cout: int = 128,
                        rows_per_tile: int | None = None,
                        batch_per_tile: int | None = None,
                        lane_pack: int | None = None) -> dict:
    """Resolve the fused kernel's tiling for one layer shape.

    Shared by the kernel itself, the autotuner's VMEM filter, and the
    analytic traffic model, so all three describe the same launch.
    ``vmem`` is the step's planned VMEM as the chip lays it out
    (`_fused_vmem_bytes`); a ``batch_per_tile=None`` launch takes the
    widest batch tile that keeps it within `VMEM_BUDGET_BYTES`.

    When lane packing engages (``g_b > 1``), the channel axis is tiled by
    superblocks of ``g_b`` groups: ``bcin`` becomes the packed lane width
    ``Lc = g_b*cin_lane`` (one reduction block, ``ncb = 1``), the groups
    grid dimension shrinks to ``n_sb = ceil(groups/g_b)``, and each
    output block is ``ow = bcout*g_b`` channels wide (``bcout`` output
    channels for each of the block's groups, interleaved o-major).

    When the taps fold (``fold``, see `_fold_pays`), the result is the
    folded launch's: a 1×1, stride-1, VALID conv over the patches laid out
    as one image ``[1, Ho·B, Wo, K²·C]`` (`_fold_patches`), that contracts
    all ``K²·C`` lanes in one reduction block (``block_cin`` is ignored,
    and the autotuner stores it as None; rows are the image's,
    ``batch_per_tile`` counts row tiles), with ``fold_pads`` the conv's
    own padding, which the patches take in.  A ``rows_per_tile`` that does
    not divide ``Ho·B`` pads the patches to whole tiles, a copy: the
    autotuner offers only divisors there.

    Where row tiles overlap (K > 1, ``n_rt·rows_in > Hp``) the kernel
    reads each window in place, at one row offset for all images of a
    step, so ``bt`` counts whole images at one row tile.  A
    ``batch_per_tile`` that spans several row tiles of an image resolves
    to one taller window: the same rows and grid steps, with ``rt``,
    ``n_rt`` and ``rows_in`` those of the launch.
    """
    pads = normalize_padding(padding, K, stride, H, W)
    Ho = _out_size(H, K, stride, pads[0])
    Wo = _out_size(W, K, stride, pads[1])
    if _fold_pays(H, W, C, K, groups, pads, Ho, Wo):
        # the folded launch: a 1×1 conv (K = 1 never folds again)
        g = fused_conv_geometry(
            1, Ho * B, Wo, K * K * C, 1, Cout, padding="VALID",
            block_cin=K * K * C, block_cout=block_cout,
            rows_per_tile=rows_per_tile, batch_per_tile=batch_per_tile)
        return dict(g, fold=True, fold_pads=pads)
    cin_g, cout_g = C // groups, Cout // groups
    lp = lane_pack_geometry(groups, cin_g, lane_pack)
    g_b, cin_lane, n_sb = lp["g_b"], lp["cin_lane"], lp["n_sb"]
    rt = Ho if rows_per_tile is None else max(1, min(int(rows_per_tile), Ho))
    n_rt = -(-Ho // rt)
    bcout = max(1, min(block_cout, cout_g))
    cout_gp = _ceil_to(cout_g, bcout)
    if g_b > 1:
        bcin = cin_gp = g_b * cin_lane     # one packed lane block, ncb = 1
    else:
        bcin = max(1, min(block_cin, cin_g))
        cin_gp = _ceil_to(cin_g, bcin)
    Wp = Wo * stride + K - 1
    Hp = n_rt * rt * stride + K - 1        # rows so every tile's halo exists
    BT = B * n_rt

    def launch(d):
        """The launch of a tile of ``d`` (image, row tile) pairs.  Where
        row tiles overlap (K > 1) the kernel reads each step's window in
        place, at one row offset for all its images: the tile is ``b``
        whole images at a window of ``m`` consecutive row tiles, with
        ``m·b = d`` (``m = gcd(d, n_rt)``; then ``b`` divides B)."""
        m = math.gcd(d, n_rt) if K > 1 else 1
        r = rt * m
        rows_in = r * stride + K - 1       # row tile + halo
        return dict(rt=r, n_rt=n_rt // m, bt=d // m, rows_in=rows_in,
                    BT=B * (n_rt // m), vmem=_fused_vmem_bytes(
                        bt=d // m, rt=r, rows_in=rows_in, Wp=Wp, Wo=Wo,
                        bcin=bcin, bcout=bcout, ow=bcout * g_b, taps=K * K,
                        stride=stride, g_b=g_b))

    if batch_per_tile is None:
        # weight-stationary across batch (the paper's multi-threaded weight
        # broadcast): the widest batch tile whose step fits the VMEM budget
        bt = max((d for d in range(1, BT + 1)
                  if BT % d == 0 and launch(d)["vmem"] <= VMEM_BUDGET_BYTES),
                 default=1)
    else:
        bt = max(1, min(int(batch_per_tile), BT))
        while BT % bt:
            bt -= 1
    return dict(launch(bt), pads=pads, Ho=Ho, Wo=Wo, cin_g=cin_g,
                cout_g=cout_g, bcin=bcin, bcout=bcout, cin_gp=cin_gp,
                cout_gp=cout_gp, Wp=Wp, Hp=Hp, ncb=cin_gp // bcin,
                njb=cout_gp // bcout, taps=K * K, g_b=g_b,
                cin_lane=cin_lane, n_sb=n_sb, ow=bcout * g_b, fold=False)


def row_tile_layout(g: dict) -> str:
    """How the row tiles of a launch (`fused_conv_geometry`) reach its
    kernel: ``single`` (one tile), ``reshape`` (several that do not
    overlap, K = 1: the padded rows as they lie) or ``overlap_in_place``
    (each step's window read at its row offset in the padded input)."""
    if g["n_rt"] == 1:
        return "single"
    if g["n_rt"] * g["rows_in"] == g["Hp"]:
        return "reshape"
    return "overlap_in_place"


def _fused_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *,
                  cfg: LogQuantConfig, K: int, stride: int, bt: int, rt: int,
                  Wo: int, acc_dtype, g_b: int = 1, cin_lane: int = 0):
    c = pl.program_id(3)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # implicit im2col: slice every tap (kh, kw) out of the VMEM-resident
    # activation slab — the slab was fetched once for this (tile, cin-block)
    # and is re-sliced for all K² taps (line-buffer reuse, no HBM patch
    # blow-up).  The taps are a static loop: Mosaic only accepts a W
    # (sublane) offset it can prove 8-aligned when the offset is dynamic.
    SH, SW = rt * stride, Wo * stride
    for t in range(K * K):
        kh, kw = divmod(t, K)
        xs = x_ref[:, kh:kh + SH, kw:kw + SW, :]         # [bt, SH, SW, bcin]
        if stride > 1:
            xs = xs.reshape(bt, rt, stride, Wo, stride, -1)[:, :, 0, :, 0, :]
        patch = xs.reshape(bt * rt * Wo, -1).astype(acc_dtype)

        # decode this tap's weight block next to the MXU (eq. 8 LUT+shift)
        w = _decode_block(w_ref[0, t], cfg, acc_dtype)   # [bcin, bcout]
        if g_b > 1:
            # unpack the group-to-lane map next to the MXU: the compact
            # block serves g_b groups at once; lane l belongs to group
            # l//cin_lane, so output column (o, g) is masked to exactly its
            # group's lanes (out-of-group taps contribute an exact 0).
            Lc, bcout = w.shape
            lane_g = jax.lax.broadcasted_iota(jnp.int32, (Lc, g_b),
                                              0) // cin_lane
            col_g = jax.lax.broadcasted_iota(jnp.int32, (Lc, g_b), 1)
            mask = (lane_g == col_g).astype(acc_dtype)   # [Lc, g_b]
            w = (w[:, :, None] * mask[:, None, :]).reshape(Lc, bcout * g_b)
        acc_ref[...] += jnp.dot(patch, w, precision=DOT_PRECISION,
                                preferred_element_type=acc_dtype)

    @pl.when(c == pl.num_programs(3) - 1)
    def _flush():
        out = acc_ref[...] * s_ref[0].astype(acc_dtype)
        o_ref[...] = out.reshape(bt, rt, Wo, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "stride", "padding", "groups", "interpret", "out_dtype",
    "block_cin", "block_cout", "rows_per_tile", "batch_per_tile",
    "lane_pack"))
def log_conv2d_fused_pallas(x, packed, scale,
                            cfg: LogQuantConfig = DEFAULT_CFG, *,
                            stride: int = 1, padding="SAME", groups: int = 1,
                            interpret: bool = False, out_dtype=None,
                            block_cin: int | None = 128, block_cout: int = 128,
                            rows_per_tile: int | None = None,
                            batch_per_tile: int | None = None,
                            lane_pack: int | None = None):
    """Direct NHWC conv with VMEM patch extraction (implicit im2col).

    Grid: (batch·row tiles, group superblocks, cout blocks, cin blocks)
    with the cin reduction innermost; each step loops over all K² taps of
    its activation slab, so the slab is fetched once per (tile, cin block)
    and reused K² times; weight codes stream as packed int8 (all taps of a
    block at once) and decode in VMEM; psums live in a VMEM scratch until
    the last reduction step.  Groups are a grid dimension: each step
    contracts only its group's `cin_g` slice.  Block sizes are the
    autotuner's knobs.

    ``lane_pack`` (see `lane_pack_geometry`) packs ``g_b`` narrow groups
    into one 128-lane channel block: the groups grid dimension collapses
    by ``g_b``, the compact weight block decodes once and is broadcast-
    masked to its block-diagonal form *inside the kernel* (out-of-group
    taps contract as exact zeros), and each MXU pass produces ``g_b``
    groups' outputs — recovering up to 128× lane density for depthwise
    convs on real TPUs.  ``None`` auto-packs grouped shapes; ``1``
    forces the padded per-group path.  `packed` is always the natural
    HWIO codes; the ``weights`` scope arranges them (`lane_pack_codes`,
    or padded per group) for the launch.

    A dense conv folds its taps into channels where its input channels
    are too few to fill the lanes (`_fold_pays`, decided from the shape
    alone by `fused_conv_geometry`): with 3 channels each (8, 128) tile of
    the input carries 3 useful lanes, and each tap's dot contracts 3 lanes
    of 128.  Folded, the patches (`_fold_patches`) are built in HBM and
    the same kernel runs them as a 1×1, stride-1 VALID conv that
    contracts all ``K²·Cin`` lanes in one reduction block, whatever
    ``block_cin`` asks.  Row tiles that do not overlap (K = 1, so every
    folded launch) are a reshape of the input, not a copy.  Row tiles
    that overlap (K > 1) are read in place: the activation goes to the
    kernel padded and whole, and each step's block is a window of
    ``rows_in`` rows at an element offset, so no tile is copied in HBM.

    The steps around the kernel run under `jax.named_scope`s that name
    their role in the compiled program's metadata: ``fold`` (the
    patches), ``pad``, ``weights`` (codes and scales to the kernel's
    layout) and ``unscramble``.  The `pallas_call` itself is left out of
    any scope: the innermost scope would name its custom call in place of
    ``log_conv2d_fused_pallas``, and its target, ``tpu_custom_call``,
    already says what it is.
    """
    B, H, W, C, K, Cout = _check_shapes(x, packed, groups)
    g = fused_conv_geometry(
        B, H, W, C, K, Cout, stride=stride, padding=padding, groups=groups,
        block_cin=block_cin, block_cout=block_cout,
        rows_per_tile=rows_per_tile, batch_per_tile=batch_per_tile,
        lane_pack=lane_pack)
    B0 = B
    if g["fold"]:
        # taps into channels: patches in (kh, kw, c) order, the order of
        # the HWIO codes' rows, so the codes serve unchanged
        with jax.named_scope("fold"):
            x = _fold_patches(x, K, stride, g["fold_pads"])
        packed = packed.reshape(1, 1, K * K * C, Cout)
        B, K, stride = 1, 1, 1
    G, taps = groups, g["taps"]
    (ph0, _), (pw0, _) = g["pads"]
    Ho, Wo, rt, n_rt, bt = g["Ho"], g["Wo"], g["rt"], g["n_rt"], g["bt"]
    cin_g, cout_g, cin_gp, cout_gp = (g["cin_g"], g["cout_g"], g["cin_gp"],
                                      g["cout_gp"])
    bcin, bcout, ncb, njb = g["bcin"], g["bcout"], g["ncb"], g["njb"]
    rows_in, Wp, Hp, BT = g["rows_in"], g["Wp"], g["Hp"], g["BT"]
    g_b, cin_lane, n_sb, ow = g["g_b"], g["cin_lane"], g["n_sb"], g["ow"]

    # pad lead edges, then fit the trailing edge to the tiled extent (extra
    # zero rows/cols are only read into discarded stride phases)
    with jax.named_scope("pad"):
        xp = jnp.pad(x, ((0, 0), (ph0, 0), (pw0, 0), (0, 0)))
        xp = _fit_dim(_fit_dim(xp, 1, Hp), 2, Wp)
        if g_b > 1:
            # lane-packed: pad each group's channels to its cin_lane slot
            # and the group count to whole superblocks — channel l of
            # superblock sb is group (sb*g_b + l//cin_lane), matching the
            # weight lanes
            x5 = xp.reshape(B, Hp, Wp, G, cin_g)
            x5 = jnp.pad(x5, ((0, 0),) * 3 + ((0, n_sb * g_b - G),
                                              (0, cin_lane - cin_g)))
            xp = x5.reshape(B, Hp, Wp, n_sb * cin_gp)
        elif cin_gp != cin_g:
            x5 = xp.reshape(B, Hp, Wp, G, cin_g)
            x5 = jnp.pad(x5, ((0, 0),) * 4 + ((0, cin_gp - cin_g),))
            xp = x5.reshape(B, Hp, Wp, G * cin_gp)
    C_out = n_sb * cout_gp * g_b
    if row_tile_layout(g) != "overlap_in_place":
        # tiles that do not overlap (one tile, or K = 1 as in every folded
        # launch) are the padded rows as they lie: a reshape, not a copy
        x_in = xp.reshape(BT, rows_in, Wp, -1)
        x_spec = pl.BlockSpec((bt, rows_in, Wp, bcin),
                              lambda i, gg, j, c: (i, 0, 0, gg * ncb + c))
        out_spec = pl.BlockSpec((bt, rt, Wo, ow),
                                lambda i, gg, j, c: (i, 0, 0, gg * njb + j))
        out_shape = (BT, rt, Wo, C_out)
    else:
        # overlapping row tiles are read in place: each step's window of
        # `bt` images at row tile i % n_rt starts at element offsets of xp
        # (Mosaic takes element offsets only where every dim is an Element,
        # and can prove a lane offset of 0 only where it is a constant)
        x_in = xp
        x_spec = pl.BlockSpec(
            tuple(map(pl.Element, (bt, rows_in, Wp, bcin))),
            lambda i, gg, j, c: ((i // n_rt) * bt, (i % n_rt) * rt * stride,
                                 0, (gg * ncb + c) * bcin
                                 if n_sb * ncb > 1 else 0))
        out_spec = pl.BlockSpec(
            (bt, None, rt, Wo, ow),
            lambda i, gg, j, c: (i // n_rt, i % n_rt, 0, 0, gg * njb + j))
        out_shape = (B, n_rt, rt, Wo, C_out)

    with jax.named_scope("weights"):
        # codes, still int8 (padding uses code 0, the dedicated zero code):
        #   padded path:      [K, K, cin_g, Cout] → [G, taps, cin_gp, cout_gp]
        #   lane-packed path: `lane_pack_codes` → [n_sb, taps, Lc, cout_gp]
        if g_b > 1:
            w = lane_pack_codes(packed, G, g_b, cin_lane)
            w = jnp.pad(w, ((0, 0),) * 3 + ((0, cout_gp - cout_g),))
        else:
            w = packed.reshape(taps, cin_g, G, cout_g)
            w = jnp.pad(w, ((0, 0), (0, cin_gp - cin_g), (0, 0),
                            (0, cout_gp - cout_g)))
            w = w.transpose(2, 0, 1, 3)

        # scales per superblock, column-matched to the kernel's (o, g)
        # output interleave: column o*g_b + g scales group (sb*g_b + g)'s
        # channel o
        s = jnp.broadcast_to(jnp.asarray(scale, jnp.float32).reshape(-1),
                             (Cout,))
        s = jnp.pad(s.reshape(G, cout_g), ((0, n_sb * g_b - G),
                                           (0, cout_gp - cout_g)))
        s = s.reshape(n_sb, g_b, cout_gp).transpose(0, 2, 1)
        s = s.reshape(n_sb, 1, cout_gp * g_b)

    acc_dtype = jnp.float32
    out = pl.pallas_call(
        functools.partial(_fused_kernel, cfg=cfg, K=K, stride=stride, bt=bt,
                          rt=rt, Wo=Wo, acc_dtype=acc_dtype, g_b=g_b,
                          cin_lane=cin_lane),
        grid=(BT // bt, n_sb, njb, ncb),
        in_specs=[
            x_spec,
            pl.BlockSpec((1, taps, bcin, bcout),
                         lambda i, gg, j, c: (gg, 0, c, j)),
            pl.BlockSpec((1, 1, ow), lambda i, gg, j, c: (gg, 0, j)),
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((bt * rt * Wo, ow), acc_dtype)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(x_in, w, s)
    # unscramble: [.., n_sb, (o, g)] → group-major channels, crop padding
    with jax.named_scope("unscramble"):
        out = out.reshape(B, n_rt * rt, Wo, n_sb, cout_gp, g_b)[:, :Ho]
        out = out.transpose(0, 1, 2, 3, 5, 4).reshape(B, Ho, Wo, n_sb * g_b,
                                                      cout_gp)
        out = out[:, :, :, :G, :cout_g].reshape(B, Ho, Wo, Cout)
        if g["fold"]:   # the patches' pixel order back to NHWC
            out = out.reshape(Ho // B0, Wo, B0, Cout).transpose(2, 0, 1, 3)
        return out


# ---------------------------------------------------------------------------
# analytic HBM-traffic model (the autotune table builder ranks by it; the
# kernel-dispatch profiler reports it per call)
# ---------------------------------------------------------------------------


def conv_traffic_bytes(impl: str, B: int, H: int, W: int, C: int, K: int,
                       Cout: int, *, stride: int = 1, padding="SAME",
                       groups: int = 1, act_itemsize: int = 4,
                       code_itemsize: int = 1, config: dict | None = None,
                       lanes: int = 1) -> dict:
    """Bytes moved HBM↔VMEM for one conv call, per implementation
    (``"fp32"``, ``"blockwise"`` or ``"pallas"``).

    First-order model: counts every block fetch/spill the grid actually
    performs (the fused path's folded patches written and read,
    per-output-block activation re-reads, per-tile weight re-reads) and
    ignores sub-block padding waste.  The fused kernel reads overlapping
    row tiles in place, so their halo rows count once per tile that
    fetches them and are never written.
    Returns ``{"act": ..., "w": ..., "out": ..., "act_w": ..., "total": ...}``.

    ``lanes`` models the physical lane width of the fused path's channel
    blocks: a real TPU DMAs (and contracts) whole 128-lane blocks, so a
    grouped conv's per-group `cin` block costs ``ceil_to(bcin, lanes)``
    channels no matter how narrow the group.  The default ``lanes=1`` is
    the pure byte count (backend-independent); ``lanes=128`` is the
    hardware-honest figure the autotune table builder ranks by.  Fused
    rows also carry ``lane_density`` — useful contraction lanes over
    fetched 128-lane capacity, the utilization lane packing recovers
    (reported per dispatch by `obs/kernel_profile.py`).
    """
    pads = normalize_padding(padding, K, stride, H, W)
    Ho, Wo = _out_size(H, K, stride, pads[0]), _out_size(W, K, stride, pads[1])
    cin_g = C // groups
    x_b = B * H * W * C * act_itemsize
    out_b = B * Ho * Wo * Cout * act_itemsize
    w_codes = K * K * cin_g * Cout * code_itemsize
    density = None

    if impl == "fp32":
        act, w = x_b, K * K * cin_g * Cout * act_itemsize
    elif impl == "blockwise":
        act, w = x_b, w_codes
    elif impl == "pallas":
        cfg = {**dict(block_cin=128, block_cout=128, rows_per_tile=None,
                      batch_per_tile=None, lane_pack=None), **(config or {})}
        g = fused_conv_geometry(B, H, W, C, K, Cout, stride=stride,
                                padding=padding, groups=groups, **cfg)
        n_bt = g["BT"] // g["bt"]
        # fetched channel width per (superblock, reduction step), padded to
        # whole physical lane blocks; g_b=1 ⇒ n_sb=groups, bcin·ncb=cin_gp
        ch = g["n_sb"] * g["ncb"] * _ceil_to(g["bcin"], lanes)
        act = (n_bt * g["bt"] * g["rows_in"] * g["Wp"] * ch * act_itemsize
               * g["njb"])
        w = (g["n_sb"] * g["taps"] * g["ncb"] * _ceil_to(g["bcin"], lanes)
             * g["cout_gp"] * code_itemsize * n_bt)
        if g["fold"]:
            # the patches the launch reads are built in HBM first: x read
            # once, the patches written once
            act += ((B * H * W * C + B * Ho * Wo * _ceil_to(K * K * C, lanes))
                    * act_itemsize)
            cin_g = K * K * C
        density = (groups * cin_g) / (g["n_sb"] * g["ncb"]
                                      * _ceil_to(g["bcin"], LANES))
    else:
        raise ValueError(f"unknown impl {impl!r}")
    out = {"act": int(act), "w": int(w), "out": int(out_b),
           "act_w": int(act + w), "total": int(act + w + out_b)}
    if density is not None:
        out["lane_density"] = round(min(density, 1.0), 4)
    return out


def log_conv2d_ref(x, packed, scale, cfg: LogQuantConfig = DEFAULT_CFG,
                   *, stride: int = 1, padding="SAME", groups: int = 1,
                   out_dtype=None):
    """Full-materialisation oracle: explicit patches × `ref_log_matmul`."""
    B, H, W, C, K, Cout = _check_shapes(x, packed, groups)
    pads = normalize_padding(padding, K, stride, H, W)
    patches, Ho, Wo = _im2col(x.astype(jnp.float32), K, stride, pads)
    codes = _block_diag_codes(packed, groups)
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32).reshape(1, -1),
                             (1, Cout))
    out = ref_log_matmul(patches.reshape(B * Ho * Wo, -1), codes, scale, cfg,
                         out_dtype=out_dtype or x.dtype)
    return out.reshape(B, Ho, Wo, Cout)
