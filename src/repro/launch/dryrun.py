import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, extract memory/cost/collective numbers for §Roofline.

MUST be run as its own process (the two lines above must execute before any
jax device initialisation — never import this module from tests).

Per cell this performs up to four compiles:
  prod-single   production program, 16×16 mesh → memory_analysis,
                collective schedule, compile-success
  acct-u1/u2    accounting program (attn_block_k=S, xent_chunk=T — both
                provably cost-identical for our blockwise kernels — layer
                scan unroll 1 and 2) → unroll-diff-corrected cost:
                    true = A + (n_rep−1)·(B−A)
                because the XLA cost model counts while bodies once.
  prod-multi    production program on the (2,16,16) 512-chip mesh →
                compile-success + memory (proves the "pod" axis shards)

Known, documented approximation: inner chunked scans of rwkv6 (wkv chunk
loop) remain while-loops in the accounting program; their bodies are <1–2 %
of layer cost (projections dominate), so the undercount is negligible —
see DESIGN.md §Known deviations.

Results: one JSON per (arch, shape, mesh) under --out (skip-if-exists →
restartable).  EXPERIMENTS.md §Dry-run / §Roofline are generated from these
records by analysis/roofline.py.
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax

from ..analysis.roofline import (DRYRUN_DEVICE_KIND, collective_bytes,
                                 model_flops_for)
from ..configs.base import SHAPES, cell_is_runnable
from ..configs.registry import ARCH_NAMES, get_config
from .mesh import make_production_mesh
from .steps import build_step


def _plain_cost(compiled) -> dict:
    ca = compiled.cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0))}


def _memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {"argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes)}


def _compile(cfg, shape_name, mesh, *, donate=True):
    kind, (fn, abs_args, shard_args) = build_step(cfg, shape_name, mesh)
    donate_argnums = ()
    if donate:
        donate_argnums = (0,) if kind == "train" else \
            ((1,) if kind == "decode" else ())
    with mesh:
        lowered = jax.jit(fn, in_shardings=shard_args,
                          donate_argnums=donate_argnums).lower(*abs_args)
        compiled = lowered.compile()
    return kind, compiled


def _main_seg_reps(cfg) -> int:
    reps = [r for _, r in cfg.segments if r > 1]
    assert len(reps) <= 1, f"{cfg.name}: >1 multi-rep segment {cfg.segments}"
    return reps[0] if reps else 1


def _opt_cfg(cfg, shape_name):
    """The §Perf winning combination per step kind ('--variant opt')."""
    import jax.numpy as jnp
    step = SHAPES[shape_name]["step"]
    if step in ("train", "prefill"):
        # per-arch measured winners (autotuned layout table — both
        # candidate layouts were measured for every regressing cell; see
        # EXPERIMENTS.md §Perf): llama's 53k d_ff makes seq-FSDP gather
        # 13 GiB of FFN weights per layer, so it stays on the baseline
        # Megatron layout; musicgen prefill likewise.
        if (cfg.name, step) in {("llama3-405b", "train"),
                                ("llama3-405b", "prefill"),
                                ("musicgen-large", "prefill")}:
            # pure baseline: even gqa_broadcast regresses here — the
            # [B,T,Hkv,rep,D] reshape splits the head axis and breaks the
            # 128-head model-axis sharding (measured 0.72×).
            return cfg
        return dataclasses.replace(
            cfg, attn_shard="seq", residual_shard="seq",
            attn_acc_dtype=jnp.bfloat16, gqa_broadcast=True)
    # decode: broadcast-GQA only for the measured sweep.  Packed logq6
    # weights (the paper's serving form) win on TPU where log_matmul
    # decodes in VMEM, but XLA-CPU materialises the f32 dequant and
    # inflates the measured memory term — see EXPERIMENTS.md §Perf cell 2.
    return dataclasses.replace(cfg, gqa_broadcast=True)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, verbose: bool = True,
             variant: str = "baseline") -> dict:
    mesh_name = "multi" if multi_pod else "single"
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    if variant == "opt":
        cfg = _opt_cfg(cfg, shape_name)
    sh = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": 512 if multi_pod else 256,
           "device_kind": DRYRUN_DEVICE_KIND,
           "model_flops": model_flops_for(cfg, sh),
           "params": cfg.param_count(),
           "active_params": cfg.active_param_count()}

    if not cell_is_runnable(arch, shape_name):
        rec["skipped"] = "full-attention arch at 500k context"
        _save(path, rec)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)

        # ---- production compile: memory + collective schedule ----------
        kind, compiled = _compile(cfg, shape_name, mesh)
        rec["step_kind"] = kind
        rec["memory"] = _memory(compiled)
        coll_prod = collective_bytes(compiled.as_text())
        rec["collectives_prod_once"] = coll_prod
        rec["cost_prod_once"] = _plain_cost(compiled)
        t_prod = time.time() - t0
        del compiled

        if multi_pod:
            # multi-pod pass = compile success + memory; roofline table is
            # single-pod only (assignment).
            rec["timings"] = {"prod_compile_s": t_prod}
            _save(path, rec)
            return rec

        # ---- accounting compiles: unroll-diff cost correction -----------
        S = sh["seq_len"]
        n_rep = _main_seg_reps(cfg)
        acct = dataclasses.replace(cfg, attn_block_k=S, scan_unroll=1)
        _, cA = _compile(acct, shape_name, mesh, donate=False)
        costA, collA = _plain_cost(cA), collective_bytes(cA.as_text())
        del cA
        if n_rep > 1:
            acct2 = dataclasses.replace(acct, scan_unroll=2)
            _, cB = _compile(acct2, shape_name, mesh, donate=False)
            costB, collB = _plain_cost(cB), collective_bytes(cB.as_text())
            del cB
        else:
            costB, collB = costA, collA

        k = n_rep - 1
        rec["cost_true"] = {
            "flops": costA["flops"] + k * (costB["flops"] - costA["flops"]),
            "bytes": costA["bytes"] + k * (costB["bytes"] - costA["bytes"]),
            "collective_bytes":
                collA["total"] + k * (collB["total"] - collA["total"]),
        }
        rec["cost_acct_u1"] = {**costA, "collective_bytes": collA["total"],
                               "coll_by_type": collA["by_type"]}
        rec["cost_acct_u2"] = {**costB, "collective_bytes": collB["total"]}
        rec["n_rep_main_segment"] = n_rep
        rec["timings"] = {"prod_compile_s": t_prod,
                          "total_s": time.time() - t0}
    except Exception as e:  # record the failure — it is a bug to fix
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"  FAILED {arch}/{shape_name}/{mesh_name}: {rec['error']}")
    _save(path, rec)
    if verbose and "error" not in rec:
        extra = ""
        if "cost_true" in rec:
            extra = (f" flops/dev={rec['cost_true']['flops']:.3e}"
                     f" coll/dev={rec['cost_true']['collective_bytes']:.3e}")
        print(f"  ok {arch}/{shape_name}/{mesh_name}"
              f" mem={rec['memory']['temp_bytes']/2**30:.1f}GiB"
              f"{extra} ({time.time()-t0:.0f}s)")
    return rec


def _save(path, rec):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".tmp", "w") as f:
        json.dump(rec, f, indent=1)
    os.replace(path + ".tmp", path)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_NAMES))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch × shape) × {single, multi}")
    ap.add_argument("--single-only", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "opt"])
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCH_NAMES:
            for shape in SHAPES:
                cells.append((arch, shape, False))
                if not args.single_only:
                    cells.append((arch, shape, True))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch/--shape or --all required")
        cells.append((args.arch, args.shape, args.multi_pod))

    print(f"dry-run: {len(cells)} cells, devices={len(jax.devices())}, "
          f"variant={args.variant}")
    for arch, shape, mp in cells:
        run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                 variant=args.variant)
    # summary
    bad = []
    for arch, shape, mp in cells:
        p = os.path.join(args.out,
                         f"{arch}__{shape}__{'multi' if mp else 'single'}.json")
        with open(p) as f:
            if "error" in json.load(f):
                bad.append(p)
    print(f"done: {len(cells) - len(bad)}/{len(cells)} ok")
    for p in bad:
        print("  FAILED:", p)


if __name__ == "__main__":
    main()
