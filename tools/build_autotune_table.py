#!/usr/bin/env python
"""Build (or CI-check) the packaged autotune warm-start tables.

The paper's premise (NeuroMAX §IV, like Shen et al.'s partitioning and
MPNA's per-layer dataflows) is that the per-layer schedule is a
*compile-time* artifact — first inference should never pay a tuning
sweep.  This tool walks the model zoo (`models/cnn.py` `CNN_ZOO` +
`configs/neuromax_cnn.py`) by shape tracing (`trace_conv_shapes`: init
and apply under `jax.eval_shape`, no parameters materialised), adds the
serving attention shapes, runs the candidate sweep per shape, and emits
one read-only table per backend under
``src/repro/kernels/autotune_tables/<backend>.json`` — the packaged tier
`kernels/autotune.lookup` consults after the writable user tier.

Two sweep modes:

  * default — the **analytic** sweep: every VMEM-fitting candidate
    (`candidate_configs` / `attention_candidate_configs`) is scored with
    the hardware-honest traffic model (`conv_traffic_bytes(lanes=128)` /
    `attention_traffic_bytes`), ties broken toward larger MXU tiles.
    Fully deterministic: regenerating the table yields a byte-identical
    file, so it can be checked in and diffed.
  * ``--measure`` — time candidates on the live backend via the real
    tuners (`autotune_conv2d` / `autotune_attention`).  Non-deterministic
    by nature; use it to regenerate a table on real hardware (the
    measured winners also land in your user-tier cache).

Usage:

    PYTHONPATH=src python tools/build_autotune_table.py          # rebuild
    PYTHONPATH=src python tools/build_autotune_table.py --check  # CI gate

``--check`` parses each packaged table, verifies the schema version
matches `SCHEMA_VERSION`, re-walks the zoo at the parameters recorded in
the table's ``meta`` block, and fails listing any uncovered key.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis.roofline import device_peaks  # noqa: E402
from repro.configs.neuromax_cnn import CONFIG  # noqa: E402
from repro.kernels import autotune  # noqa: E402
from repro.kernels.flash_attention import attention_traffic_bytes  # noqa: E402
from repro.kernels.log_conv2d import (conv_traffic_bytes,  # noqa: E402
                                      fused_conv_geometry)
from repro.models.cnn import zoo_conv_shapes  # noqa: E402

# serving decode/prefill attention launch shapes (mirrors the
# BENCH_attention.json case list): (B, Tq, Tk, H, Hkv, D, causal, window)
ATTENTION_SHAPES = [
    [1, 1, 4096, 8, 2, 64, True, None],     # decode, GQA rep=4
    [1, 1, 8192, 8, 2, 64, True, None],     # decode, GQA rep=4, 8k ctx
    [1, 1, 4096, 8, 1, 64, True, None],     # decode, MQA
    [1, 128, 4096, 8, 2, 64, True, None],   # prefill chunk, GQA rep=4
    [1, 1, 4096, 8, 8, 64, True, None],     # decode, MHA control
]

DEFAULT_BACKENDS = ("interpret", "cpu", "tpu")

# the analytic `us` estimates are bytes over a TPU v5e's HBM bandwidth
HBM_BW = device_peaks("TPU v5 lite")["hbm_bytes_per_s"]


def _walk_kwargs(args_or_meta) -> dict:
    g = (args_or_meta.get if isinstance(args_or_meta, dict)
         else lambda k, d=None: getattr(args_or_meta, k.replace("-", "_")))
    return dict(batch=g("batch", 1), img=g("img", 224),
                n_classes=g("n_classes", 1000), cin=g("cin", 3),
                width_mult=g("width_mult", 1.0))


def conv_keys_for(shapes: list[dict], backend: str) -> list[tuple[str, dict]]:
    out = []
    for s in shapes:
        key = autotune.conv_key(
            s["B"], s["H"], s["W"], s["C"], s["K"], s["Cout"],
            stride=s["stride"], padding=s["padding"], groups=s["groups"],
            cfg=CONFIG.qcfg, backend=backend)
        out.append((key, s))
    return out


def attention_keys_for(shapes, backend: str) -> list[tuple[str, list]]:
    return [(autotune.attention_key(B, Tq, Tk, H, Hkv, D, causal=causal,
                                    window=window, backend=backend),
             [B, Tq, Tk, H, Hkv, D, causal, window])
            for B, Tq, Tk, H, Hkv, D, causal, window in shapes]


# ---------------------------------------------------------------------------
# analytic sweep (deterministic)
# ---------------------------------------------------------------------------


def analytic_conv_winner(s: dict) -> tuple[dict, float]:
    """Best candidate by modeled 128-lane HBM traffic; ties go to larger
    channel tiles (fewer grid steps).  Returns (config, estimated_us)."""
    shape_kw = dict(stride=s["stride"], padding=s["padding"],
                    groups=s["groups"])
    args = (s["B"], s["H"], s["W"], s["C"], s["K"], s["Cout"])
    cands = (autotune.candidate_configs(*args, **shape_kw)
             or [autotune.default_config(*args, **shape_kw)])
    best, best_score, best_total = None, None, None
    for cfg in cands:
        t = conv_traffic_bytes("pallas", *args, **shape_kw, config=cfg,
                               lanes=128)
        g = fused_conv_geometry(*args, **shape_kw, **cfg)
        score = (t["act_w"], -(g["bcin"] * g["bcout"]))
        if best_score is None or score < best_score:
            best, best_score, best_total = cfg, score, t["total"]
    return best, best_total / HBM_BW * 1e6


def analytic_attention_winner(shape) -> tuple[dict, float]:
    B, Tq, Tk, H, Hkv, D = shape[:6]
    args = (B, Tq, Tk, H, Hkv, D)
    cands = (autotune.attention_candidate_configs(*args)
             or [autotune.default_attention_config(*args)])
    best, best_score, best_total = None, None, None
    for cfg in cands:
        t = attention_traffic_bytes("pallas", *args, **cfg)
        score = (t["total"], -(cfg["block_q"] * cfg["block_k"]))
        if best_score is None or score < best_score:
            best, best_score, best_total = cfg, score, t["total"]
    return best, best_total / HBM_BW * 1e6


# ---------------------------------------------------------------------------
# measured sweep (live backend; non-deterministic)
# ---------------------------------------------------------------------------


def measured_conv_winner(s: dict, backend: str, reps: int) -> tuple[dict,
                                                                    float]:
    from repro.core.logquant import quantize_tensor
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(s["B"], s["H"], s["W"], s["C"]))
                    .astype(np.float32))
    w = jnp.asarray(rng.normal(
        size=(s["K"], s["K"], s["C"] // s["groups"], s["Cout"]))
        .astype(np.float32))
    qt = quantize_tensor(w, CONFIG.qcfg)
    best = autotune.autotune_conv2d(
        x, qt.packed, qt.scale, qt.cfg, stride=s["stride"],
        padding=s["padding"], groups=s["groups"],
        interpret=(backend == "interpret"), reps=reps)
    key = autotune.conv_key(s["B"], s["H"], s["W"], s["C"], s["K"],
                            s["Cout"], stride=s["stride"],
                            padding=s["padding"], groups=s["groups"],
                            cfg=CONFIG.qcfg, backend=backend)
    return best, autotune._load()["entries"][key]["us"]


def measured_attention_winner(shape, backend: str, reps: int) -> tuple[dict,
                                                                       float]:
    B, Tq, Tk, H, Hkv, D, causal, window = shape
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, Tq, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Tk, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Tk, Hkv, D)), jnp.float32)
    best = autotune.autotune_attention(
        q, k, v, causal=causal, window=window,
        interpret=(backend == "interpret"), reps=reps)
    key = autotune.attention_key(B, Tq, Tk, H, Hkv, D, causal=causal,
                                 window=window, backend=backend)
    return best, autotune._load()["entries"][key]["us"]


# ---------------------------------------------------------------------------
# build / check
# ---------------------------------------------------------------------------


def build_table(backend: str, args) -> dict:
    walk = _walk_kwargs(args)
    shapes = zoo_conv_shapes(**walk)
    entries = {}
    for key, s in conv_keys_for(shapes, backend):
        if args.measure:
            cfg, us = measured_conv_winner(s, backend, args.reps)
            how = "measured"
        else:
            cfg, us = analytic_conv_winner(s)
            how = "analytic"
        entries[key] = {"config": cfg, "us": round(us, 2),
                        "when": "packaged", "how": how, "nets": s["nets"]}
    for key, shape in attention_keys_for(ATTENTION_SHAPES, backend):
        if args.measure:
            cfg, us = measured_attention_winner(shape, backend, args.reps)
            how = "measured"
        else:
            cfg, us = analytic_attention_winner(shape)
            how = "analytic"
        entries[key] = {"config": cfg, "us": round(us, 2),
                        "when": "packaged", "how": how}
    return {"version": autotune.SCHEMA_VERSION,
            "generated_by": "tools/build_autotune_table.py",
            "meta": dict(walk, qbits=CONFIG.qcfg.bits,
                         qfrac=CONFIG.qcfg.frac_bits,
                         attention_shapes=ATTENTION_SHAPES),
            "entries": entries}


def check_table(path: str, backend: str) -> list[str]:
    """→ list of problems (empty = table is valid and covers the zoo)."""
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable ({e})"]
    problems = []
    if table.get("version") != autotune.SCHEMA_VERSION:
        problems.append(f"{path}: schema version {table.get('version')} != "
                        f"SCHEMA_VERSION {autotune.SCHEMA_VERSION}")
        return problems
    entries = table.get("entries", {})
    meta = table.get("meta", {})
    shapes = zoo_conv_shapes(**_walk_kwargs(meta))
    for key, _ in conv_keys_for(shapes, backend):
        if key not in entries:
            problems.append(f"{path}: missing conv entry {key}")
    att = meta.get("attention_shapes", ATTENTION_SHAPES)
    att = [tuple(a) for a in att]
    for key, _ in attention_keys_for(att, backend):
        if key not in entries:
            problems.append(f"{path}: missing attention entry {key}")
    for key, e in entries.items():
        if not isinstance(e.get("config"), dict):
            problems.append(f"{path}: entry {key} has no config dict")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="build/check the packaged autotune warm-start tables")
    ap.add_argument("--backends", nargs="*", default=list(DEFAULT_BACKENDS))
    ap.add_argument("--out", default=autotune.PACKAGED_DIR,
                    help="tables directory (default: the packaged tier)")
    ap.add_argument("--img", type=int, default=224)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--n-classes", type=int, default=1000)
    ap.add_argument("--cin", type=int, default=3)
    ap.add_argument("--width-mult", type=float, default=1.0)
    ap.add_argument("--measure", action="store_true",
                    help="time candidates on the live backend instead of "
                         "the deterministic analytic sweep")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--check", action="store_true",
                    help="validate existing tables (schema + zoo coverage) "
                         "instead of building")
    args = ap.parse_args(argv)

    if args.check:
        problems = []
        for backend in args.backends:
            path = os.path.join(args.out, f"{backend}.json")
            probs = check_table(path, backend)
            problems += probs
            if not probs:
                n = len(json.load(open(path))["entries"])
                print(f"{path}: ok ({n} entries cover the zoo)")
        if problems:
            print("\n".join(problems[:40]), file=sys.stderr)
            print(f"FAIL: {len(problems)} problem(s)", file=sys.stderr)
            return 1
        return 0

    if args.measure:
        live = ("interpret" if jax.default_backend() != "tpu"
                else jax.default_backend())
        bad = [b for b in args.backends if b != live]
        if bad:
            print(f"--measure can only time the live backend ({live}); "
                  f"drop {bad} or run without --measure", file=sys.stderr)
            return 1

    os.makedirs(args.out, exist_ok=True)
    for backend in args.backends:
        table = build_table(backend, args)
        path = os.path.join(args.out, f"{backend}.json")
        with open(path, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {path}: {len(table['entries'])} entries "
              f"({'measured' if args.measure else 'analytic'} sweep)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
