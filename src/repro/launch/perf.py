import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb harness: lower one (arch × shape) cell with a named
config variant, extract the three roofline terms (unroll-diff-corrected),
and append the iteration to results/perf/<arch>__<shape>.jsonl.

    PYTHONPATH=src python -m repro.launch.perf --arch gemma3-1b \
        --shape train_4k --variant heads_tp

Variants are config transforms registered in VARIANTS — the baseline is the
paper-faithful config ("baseline"); each hillclimb hypothesis is one named
variant so every row in EXPERIMENTS.md §Perf is reproducible.
"""

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from ..analysis.roofline import (DRYRUN_DEVICE_KIND, Roofline,
                                 collective_bytes, model_flops_for)
from ..configs.base import SHAPES
from ..configs.registry import ARCH_NAMES, get_config
from .dryrun import _compile, _main_seg_reps, _memory, _plain_cost
from .mesh import make_production_mesh

# ----------------------------------------------------------------- variants

def _v(**kw):
    return lambda cfg: dataclasses.replace(cfg, **kw)


def _chain(*fns):
    def apply(cfg):
        for f in fns:
            cfg = f(cfg)
        return cfg
    return apply


VARIANTS = {
    "baseline": _v(),
    # H1: Megatron-style head sharding for q/k/v + attention out
    "heads_tp": _v(attn_shard="heads"),
    # H2: bf16 attention math (running stats stay f32)
    "attn_bf16": _v(attn_acc_dtype=jnp.bfloat16),
    # H3: GQA via broadcast einsum (no kv repeat)
    "gqa_bcast": _v(gqa_broadcast=True),
    # combinations
    "heads+bf16": _v(attn_shard="heads", attn_acc_dtype=jnp.bfloat16),
    "heads+bf16+bcast": _v(attn_shard="heads",
                           attn_acc_dtype=jnp.bfloat16, gqa_broadcast=True),
    # H4: paper technique on serving weights — logq6 fake-quant path marks
    # weight reads 6-bit in the kernel; modelled in the memory term
    "logq6": _v(quant="logq6"),
    "heads+bf16+logq6": _v(attn_shard="heads",
                           attn_acc_dtype=jnp.bfloat16, quant="logq6"),
    # H5: block size sweeps for the blockwise kernels
    "block2048": _v(attn_block_k=2048),
    "block4096": _v(attn_block_k=4096),
    # H6: no remat (memory for flops trade)
    "noremat": _v(remat=False),
    "heads+bf16+noremat": _v(attn_shard="heads",
                             attn_acc_dtype=jnp.bfloat16, remat=False),
    # H7: sequence parallelism (query/residual seq-sharded over model)
    "seq_tp": _v(attn_shard="seq"),
    "seq_tp+res": _v(attn_shard="seq", residual_shard="seq"),
    "seq_tp+res+bf16": _v(attn_shard="seq", residual_shard="seq",
                          attn_acc_dtype=jnp.bfloat16),
    "seq_tp+res+bf16+bcast": _v(attn_shard="seq", residual_shard="seq",
                                attn_acc_dtype=jnp.bfloat16,
                                gqa_broadcast=True),
    # H8: decode combos — head-whole layouts + no kv repeat + packed 6-bit
    # serving weights (the paper's storage format end to end)
    "heads+bcast": _v(attn_shard="heads", gqa_broadcast=True),
    "heads+bcast+logq6": _v(attn_shard="heads", gqa_broadcast=True,
                            quant="logq6"),
    "bcast+logq6": _v(gqa_broadcast=True, quant="logq6"),
    # H9: bf16 parameters — halves FSDP weight gathers AND grad reductions
    # (optimizer keeps f32 mu/nu as master statistics)
    "params_bf16": _v(param_dtype=jnp.bfloat16),
    "seq+all+params_bf16": _v(attn_shard="seq", residual_shard="seq",
                              attn_acc_dtype=jnp.bfloat16,
                              gqa_broadcast=True,
                              param_dtype=jnp.bfloat16),
    # H10: Megatron-SP — activations gathered at block input, weights stay
    # TP-sharded, residual reduce-scattered (wins when weights ≫ acts)
    "megatron_sp": _v(attn_shard="seq", residual_shard="seq",
                      sp_style="megatron", attn_acc_dtype=jnp.bfloat16,
                      gqa_broadcast=True),
    "megatron_sp+heads": _v(attn_shard="heads", residual_shard="seq",
                            sp_style="megatron",
                            attn_acc_dtype=jnp.bfloat16,
                            gqa_broadcast=True),
}


def run_variant(arch: str, shape_name: str, variant: str, *,
                out_dir: str = "results/perf", note: str = "") -> dict:
    cfg = VARIANTS[variant](get_config(arch))
    sh = SHAPES[shape_name]
    mesh = make_production_mesh()
    t0 = time.time()

    # production compile (memory + schedule)
    kind, compiled = _compile(cfg, shape_name, mesh)
    mem = _memory(compiled)
    del compiled

    # accounting compiles
    S = sh["seq_len"]
    n_rep = _main_seg_reps(cfg)
    acct = dataclasses.replace(cfg, attn_block_k=S, scan_unroll=1)
    _, cA = _compile(acct, shape_name, mesh, donate=False)
    costA, collA = _plain_cost(cA), collective_bytes(cA.as_text())
    del cA
    if n_rep > 1:
        _, cB = _compile(dataclasses.replace(acct, scan_unroll=2),
                         shape_name, mesh, donate=False)
        costB, collB = _plain_cost(cB), collective_bytes(cB.as_text())
        del cB
    else:
        costB, collB = costA, collA
    k = n_rep - 1
    true = {
        "flops": costA["flops"] + k * (costB["flops"] - costA["flops"]),
        "bytes": costA["bytes"] + k * (costB["bytes"] - costA["bytes"]),
        "collective_bytes":
            collA["total"] + k * (collB["total"] - collA["total"]),
    }
    coll_by_type = {t: collA["by_type"].get(t, 0)
                    + k * (collB["by_type"].get(t, 0)
                           - collA["by_type"].get(t, 0))
                    for t in set(collA["by_type"]) | set(collB["by_type"])}

    r = Roofline(arch=arch, shape=shape_name, mesh="single", chips=256,
                 device_kind=DRYRUN_DEVICE_KIND,
                 flops_per_dev=true["flops"], bytes_per_dev=true["bytes"],
                 coll_bytes_per_dev=true["collective_bytes"],
                 model_flops=model_flops_for(cfg, sh),
                 memory_per_dev=mem["temp_bytes"] + mem["argument_bytes"])
    rec = {"arch": arch, "shape": shape_name, "variant": variant,
           "note": note, "cost_true": true, "coll_by_type": coll_by_type,
           "memory": mem, "row": r.row(),
           "compile_s": round(time.time() - t0, 1)}
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{arch}__{shape_name}.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(f"{arch}/{shape_name} [{variant}] "
          f"comp={r.t_compute*1e3:.1f}ms mem={r.t_memory*1e3:.1f}ms "
          f"coll={r.t_collective*1e3:.1f}ms → {r.bottleneck} "
          f"| step≥{r.step_time*1e3:.1f}ms mfu={r.mfu*100:.1f}% "
          f"| hbm/dev={r.memory_per_dev/2**30:.1f}GiB "
          f"({time.time()-t0:.0f}s)")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_NAMES), required=True)
    ap.add_argument("--shape", choices=list(SHAPES), required=True)
    ap.add_argument("--variant", default="baseline",
                    choices=list(VARIANTS), nargs="+")
    ap.add_argument("--note", default="")
    args = ap.parse_args()
    for v in (args.variant if isinstance(args.variant, list)
              else [args.variant]):
        run_variant(args.arch, args.shape, v, note=args.note)


if __name__ == "__main__":
    main()
