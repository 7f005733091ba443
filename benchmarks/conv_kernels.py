"""Log-conv kernel timings across the paper's CNN layer shapes.

Times `kernels/ops.conv2d` (blockwise jnp path, plus fused and im2col
Pallas probes on a small layer as correctness checks, in interpret mode
off-TPU) against the fp32 `lax.conv` baseline, on VGG-16 / MobileNet-v1
layer shapes from `core/accelerator.py` scaled to a CI-sized image.  Emits
``BENCH_conv.json`` at the repo root via `benchmarks/common.py` (which
also prints a delta table against the previous run).

Timing hygiene: the jitted entry points are hoisted to module level (one
`jax.jit` per function, shapes retrace but calls hit the jit cache — no
per-layer lambda re-tracing), and the first call (compile) is reported
separately from the steady-state mean.

Each row also carries the analytic HBM traffic per impl
(`kernels/log_conv2d.conv_traffic_bytes`): packed int8 codes vs
materialized patches vs fp32, and the fused/im2col activation+weight
ratio — on CPU the timings measure decode overhead, but the bytes-moved
columns are backend-independent and must show the fused kernel winning
≥4× on every 3×3 layer it runs unfolded.  A layer whose taps fold into
channels (the 3-channel first conv) is an explicit im2col by design, so
it is gated on what the fold promises instead (`log_conv2d._fold_pays`):
at the physical 128-lane width its kernel fetches fewer activation bytes
from the patches than the unfolded launch (``"pallas_direct"``) fetches
from the padded input.  The kernel's fetches, not the totals: the model
counts the fold's patch build but not the unfolded launch's pad pass,
the copy that build stands in for.

A second table covers the lane-packed grouped/depthwise layout
(MobileNet-style ``cin_g ∈ {1, 2, 4}``): analytic bytes at the physical
128-lane width, auto-packed vs forced-padded, gated at ≥4× recovery for
every narrow-group shape.

A third, ``cold_start``, section gates the autotune warm-start tier: a
fresh process (empty user cache) tracing quantized inference over all
four paper CNNs at 224 px must resolve **every** conv dispatch from the
packaged table — zero tuning sweeps, zero heuristic fallbacks
(`autotune_lookup` counters: `hit_warm` == dispatches, `miss` == 0).
"""

from __future__ import annotations

import functools
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.neuromax_cnn import CONFIG as CNN_CONFIG
from repro.core.accelerator import mobilenet_v1_layers, vgg16_layers
from repro.core.logquant import quantize_tensor
from repro.kernels import autotune, ops
from repro.kernels.log_conv2d import conv_traffic_bytes, fused_conv_geometry
from repro.models import cnn as cnn_models
from repro.obs import metrics as obs_metrics
from repro.serving.quantize import quantize_cnn_params

from .common import fmt_table, write_json

IMG = 32    # CI-sized spatial scale for the paper's 224px layer stacks
BATCH = 4   # serving-sized microbatch: traffic ratios reflect deployment
TRAFFIC_WIN_3X3 = 4.0  # acceptance: fused moves ≥4× fewer act+w bytes
FOLD_WIN = 1.0         # acceptance: a folded kernel fetches fewer bytes
LANE_PACK_WIN = 4.0    # acceptance: lane-packed ≥4× fewer 128-lane bytes


@functools.partial(jax.jit, static_argnames=("stride", "pads", "groups"))
def _fp32_conv(x, w, *, stride, pads, groups):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


@functools.partial(jax.jit, static_argnames=("impl", "stride", "padding",
                                             "groups", "interpret"))
def _logq_conv(x, qt, *, impl, stride, padding, groups, interpret=None):
    return ops.conv2d(x, qt, impl=impl, stride=stride, padding=padding,
                      groups=groups, interpret=interpret)


def _bench(fn, *args, reps: int = 5, **kw):
    """→ (compile_us, steady_us): first call times compile+run, then the
    steady-state mean over ``reps`` after a warm-up call."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args, **kw))
    compile_us = (time.perf_counter() - t0) * 1e6
    jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return compile_us, (time.perf_counter() - t0) / reps * 1e6


def _pads_for(spec):
    if isinstance(spec.pad, int):
        return ((spec.pad, spec.pad), (spec.pad, spec.pad))
    return spec.pad


def _layer_cases():
    vgg = {l.name: l for l in vgg16_layers(IMG)}
    mbn = {l.name: l for l in mobilenet_v1_layers(IMG)}
    picks = [("vgg16", vgg["CONV1_1"]), ("vgg16", vgg["CONV3_1"]),
             ("mobilenet_v1", mbn["DW2"]), ("mobilenet_v1", mbn["PW2"])]
    for net, spec in picks:
        groups = spec.C if spec.kind == "dwconv" else 1
        yield net, spec, groups


def _autotune_counts() -> dict:
    """Current `autotune_lookup`/`autotune_sweep` totals (conv2d op)."""
    out = {"hit_user": 0, "hit_warm": 0, "miss": 0, "sweeps": 0}
    for name, v in obs_metrics.REGISTRY.snapshot()["counters"].items():
        if name.startswith("autotune_sweep"):
            out["sweeps"] += v
        elif name.startswith("autotune_lookup") and 'op="conv2d"' in name:
            for r in ("hit_user", "hit_warm", "miss"):
                if f'result="{r}"' in name:
                    out[r] += v
    return out


def cold_start_section(img: int = 224, batch: int = 1) -> dict:
    """First-inference warm-start gate: with an **empty user cache** (the
    env tier pointed at a file that doesn't exist), shape-trace quantized
    inference over the four paper CNNs exactly as serving dispatches it
    (packed `QuantizedTensor` weights, ``conv_impl="pallas"``, lane-packed
    depthwise layout) and require every conv dispatch to resolve from the
    packaged warm-start tier.  `jax.eval_shape` runs the real dispatch
    path — config resolution and table lookups happen at trace time — so
    the gate covers the full 224 px layer stacks in seconds."""
    prev = os.environ.get("REPRO_AUTOTUNE_PATH")
    os.environ["REPRO_AUTOTUNE_PATH"] = os.path.join(
        tempfile.mkdtemp(prefix="repro-coldstart-"), "empty.json")
    autotune.reset_cache()
    per_net, before = {}, _autotune_counts()
    try:
        for name in cnn_models.CNN_ZOO:
            init, apply = cnn_models.CNN_ZOO[name]

            def run_net(key, x, init=init, apply=apply):
                qp = quantize_cnn_params(init(key), CNN_CONFIG.qcfg,
                                         conv_layout="lane_packed")
                return apply(qp, x, conv_impl="pallas")

            n0 = _autotune_counts()
            jax.eval_shape(run_net, jax.ShapeDtypeStruct((2,), jnp.uint32),
                           jax.ShapeDtypeStruct((batch, img, img, 3),
                                                jnp.float32))
            n1 = _autotune_counts()
            per_net[name] = {k: n1[k] - n0[k] for k in n0}
    finally:
        if prev is None:
            os.environ.pop("REPRO_AUTOTUNE_PATH", None)
        else:
            os.environ["REPRO_AUTOTUNE_PATH"] = prev
        autotune.reset_cache()
    after = _autotune_counts()
    d = {k: after[k] - before[k] for k in before}
    dispatches = d["hit_user"] + d["hit_warm"] + d["miss"]
    ok = (dispatches > 0 and d["miss"] == 0 and d["sweeps"] == 0
          and d["hit_warm"] == dispatches)
    return {"img": img, "batch": batch, "conv_dispatches": dispatches,
            "hit_warm": d["hit_warm"], "hit_user": d["hit_user"],
            "miss": d["miss"], "sweeps": d["sweeps"],
            "per_net": per_net, "ok": ok}


def run() -> dict:
    rng = np.random.default_rng(0)
    rows, ok = [], True
    for net, spec, groups in _layer_cases():
        H = W = spec.H
        x = jnp.asarray(rng.normal(size=(BATCH, H, W, spec.C))
                        .astype(np.float32))
        w = jnp.asarray(rng.normal(
            size=(spec.K, spec.K, spec.C // groups, spec.P))
            .astype(np.float32))
        qt = quantize_tensor(w)
        shape_kw = dict(stride=spec.stride, padding=spec.pad, groups=groups)

        fp_c, fp_us = _bench(_fp32_conv, x, w, stride=spec.stride,
                             pads=_pads_for(spec), groups=groups)
        bw_c, bw_us = _bench(_logq_conv, x, qt, impl="blockwise", **shape_kw)
        y_fp = _fp32_conv(x, w, stride=spec.stride, pads=_pads_for(spec),
                          groups=groups)
        y_bw = _logq_conv(x, qt, impl="blockwise", **shape_kw)
        # quant error envelope, not a bitwise check: ~|w|·√2-halfstep
        rel = float(jnp.linalg.norm(y_bw - y_fp) /
                    (jnp.linalg.norm(y_fp) + 1e-9))

        tkw = dict(B=BATCH, H=H, W=W, C=spec.C, K=spec.K, Cout=spec.P)
        traffic = {impl: conv_traffic_bytes(impl, **tkw, **shape_kw)
                   for impl in ("fp32", "blockwise", "pallas_im2col",
                                "pallas")}
        win = traffic["pallas_im2col"]["act_w"] / traffic["pallas"]["act_w"]
        folded = fused_conv_geometry(**tkw, **shape_kw)["fold"]
        fold_win = None
        if folded:
            fetched = {impl: conv_traffic_bytes(impl, **tkw, **shape_kw,
                                                lanes=128)["act_kernel"]
                       for impl in ("pallas", "pallas_direct")}
            fold_win = fetched["pallas_direct"] / fetched["pallas"]
            traffic_ok = fold_win > FOLD_WIN
        else:
            traffic_ok = win >= TRAFFIC_WIN_3X3 if spec.K == 3 else True
        row_ok = rel < 0.2 and y_bw.shape == y_fp.shape and traffic_ok
        ok &= row_ok
        rows.append({
            "net": net, "layer": spec.name,
            "shape": f"{BATCH}x{H}x{W}x{spec.C}->{spec.P}",
            "K": spec.K, "stride": spec.stride, "groups": groups,
            "fp32_us": round(fp_us, 1), "fp32_compile_us": round(fp_c, 1),
            "logq_blockwise_us": round(bw_us, 1),
            "logq_compile_us": round(bw_c, 1),
            "overhead_x": round(bw_us / max(fp_us, 1e-9), 2),
            "rel_quant_err": round(rel, 4),
            "bytes_fp32": traffic["fp32"]["act_w"],
            "bytes_blockwise": traffic["blockwise"]["act_w"],
            "bytes_im2col": traffic["pallas_im2col"]["act_w"],
            "bytes_fused": traffic["pallas"]["act_w"],
            "fused_traffic_win_x": round(win, 2), "folded": folded,
            "fold_traffic_win_x": (None if fold_win is None
                                   else round(fold_win, 2)),
            "ok": row_ok,
        })

    # Pallas probes on a small layer (correctness, not speed):
    # fused ≡ im2col ≡ blockwise, compile and steady time reported apart
    x = jnp.asarray(rng.normal(size=(1, 8, 8, 3)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 3, 16)).astype(np.float32))
    qt = quantize_tensor(w)
    pkw = dict(stride=1, padding="SAME", groups=1, interpret=None)
    probes = {}
    y_bw = _logq_conv(x, qt, impl="blockwise", stride=1, padding="SAME",
                      groups=1)
    pallas_ok = True
    for impl in ("pallas", "pallas_im2col"):
        c_us, s_us = _bench(_logq_conv, x, qt, impl=impl, reps=3, **pkw)
        d = float(jnp.max(jnp.abs(_logq_conv(x, qt, impl=impl, **pkw)
                                  - y_bw)))
        probes[impl] = {"compile_us": round(c_us, 1),
                        "steady_us": round(s_us, 1), "maxdiff": d}
        pallas_ok &= d < 1e-3
    ok &= pallas_ok

    # Lane-packed grouped/depthwise section (MobileNet-style narrow
    # groups, cin_g ∈ {1, 2, 4}): analytic HBM bytes at the physical
    # 128-lane width, auto-packed (`lane_pack=None`) vs forced-padded
    # (`lane_pack=1`), plus an interpret-mode correctness probe.  The
    # timing columns above measure CPU decode; these columns are the
    # hardware-honest traffic the packed layout recovers.
    lane_rows, lane_ok = [], True
    lane_cases = [  # (name, C, groups, Cout, K, stride) — cin_g = C//groups
        ("dw_cin1", 64, 64, 64, 3, 1),
        ("dw_cin1_s2", 64, 64, 64, 3, 2),
        ("grp_cin2", 64, 32, 64, 3, 1),
        ("grp_cin4", 64, 16, 64, 3, 1),
    ]
    for name, C, G, Cout, K, stridelp in lane_cases:
        xg = jnp.asarray(rng.normal(size=(1, 8, 8, C)).astype(np.float32))
        wg = jnp.asarray(rng.normal(size=(K, K, C // G, Cout))
                         .astype(np.float32))
        qtg = quantize_tensor(wg)
        gkw = dict(stride=stridelp, padding="SAME", groups=G)
        tkw = dict(B=BATCH, H=IMG, W=IMG, C=C, K=K, Cout=Cout, **gkw)
        packed = conv_traffic_bytes("pallas", lanes=128,
                                    config=dict(lane_pack=None), **tkw)
        padded = conv_traffic_bytes("pallas", lanes=128,
                                    config=dict(lane_pack=1), **tkw)
        win = padded["act_w"] / packed["act_w"]
        y_ref = _logq_conv(xg, qtg, impl="blockwise", **gkw)
        d = float(jnp.max(jnp.abs(
            _logq_conv(xg, qtg, impl="pallas", interpret=None, **gkw)
            - y_ref)))
        cin_g = C // G
        row_ok = (d < 1e-3) and (win >= LANE_PACK_WIN if cin_g <= 4
                                 else True)
        lane_ok &= row_ok
        lane_rows.append({
            "case": name, "cin_g": cin_g, "groups": G, "K": K,
            "stride": stridelp,
            "bytes_padded_128": padded["act_w"],
            "bytes_packed_128": packed["act_w"],
            "lane_pack_win_x": round(win, 2),
            "lane_density_padded": padded["lane_density"],
            "lane_density_packed": packed["lane_density"],
            "maxdiff_vs_blockwise": d, "ok": row_ok,
        })
    ok &= lane_ok

    # Cold-start warm-table gate (ROADMAP "autotune table warm-start"):
    # fresh process ⇒ every conv dispatch of the four CNNs is hit_warm.
    cold = cold_start_section()
    ok &= cold["ok"]

    cols = ["net", "layer", "shape", "K", "stride", "groups", "fp32_us",
            "logq_blockwise_us", "overhead_x", "rel_quant_err",
            "bytes_im2col", "bytes_fused", "fused_traffic_win_x",
            "fold_traffic_win_x", "ok"]
    print(fmt_table(rows, cols))
    print(fmt_table(lane_rows, ["case", "cin_g", "groups", "K", "stride",
                                "bytes_padded_128", "bytes_packed_128",
                                "lane_pack_win_x", "lane_density_packed",
                                "ok"]))
    for impl, p in probes.items():
        print(f"{impl} probe ({jax.default_backend()}): "
              f"compile {p['compile_us']:.0f} µs, "
              f"steady {p['steady_us']:.0f} µs, |Δ vs blockwise| = "
              f"{p['maxdiff']:.2e} ({'OK' if p['maxdiff'] < 1e-3 else 'FAIL'})")
    print(f"cold_start: {cold['conv_dispatches']} conv dispatches over "
          f"{list(cold['per_net'])} @ {cold['img']}px — hit_warm "
          f"{cold['hit_warm']}, hit_user {cold['hit_user']}, miss "
          f"{cold['miss']}, sweeps {cold['sweeps']} "
          f"({'OK' if cold['ok'] else 'FAIL'})")
    mean_over = float(np.mean([r["overhead_x"] for r in rows]))
    min_win = min(r["fused_traffic_win_x"] for r in rows
                  if r["K"] == 3 and not r["folded"])
    min_fold_win = min((r["fold_traffic_win_x"] for r in rows
                        if r["folded"]), default=None)
    out = {"rows": rows, "probes": probes, "lane_rows": lane_rows,
           "cold_start": cold,
           "pallas_interpret_maxdiff": max(p["maxdiff"]
                                           for p in probes.values()),
           "mean_blockwise_overhead_x": mean_over,
           "min_3x3_fused_traffic_win_x": min_win,
           "min_fold_traffic_win_x": min_fold_win,
           "min_lane_pack_win_x": min(r["lane_pack_win_x"]
                                      for r in lane_rows),
           "img": IMG, "batch": BATCH, "ok": ok}
    path = write_json("BENCH_conv.json", out)
    print(f"wrote {path}")
    return out
