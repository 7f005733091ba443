"""Three-term roofline from compiled dry-run artifacts.

    compute    = HLO_FLOPs   / (chips × peak FLOP/s)
    memory     = HLO_bytes   / (chips × HBM bandwidth)
    collective = coll_bytes  / (chips × ICI link bandwidth)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` after the
unroll-diff correction (launch/dryrun.py): the XLA cost model counts a
while-loop body ONCE, so the dry-run lowers each program twice (layer-scan
unroll 1 and 2) and extrapolates  true = A + (trips−1)·(B−A).

collective_bytes is not in cost_analysis — ``collective_bytes()`` below
parses the post-SPMD optimized HLO (``compiled.as_text()``, where partitioner
-inserted collectives are explicit) and sums operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
Shapes in that text are already per-device.

Hardware model: per-chip peaks come from `PEAKS`, keyed by the JAX
``device_kind`` of the chip the numbers are for; the dry-run models a
TPU v5e (`DRYRUN_DEVICE_KIND`) with ~50 GB/s/link ICI (per assignment).
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from collections import defaultdict

# Published per-chip peaks, keyed by JAX's `device_kind`.  Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
# HBM at 819 GB/s).  A chip missing here has no roofline: add it with its
# source rather than borrowing another chip's numbers.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}

DRYRUN_DEVICE_KIND = "TPU v5 lite"  # the chip the dry-run's meshes model
ICI_BW = 50e9                       # bytes/s per link


def device_peaks(device_kind: str) -> dict:
    """Peak rates of one chip; raises for a `device_kind` not in `PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
}

# a shape token, e.g. ``bf16[16,4096,128]{2,1,0}`` (layout optional)
_SHAPE_RE = re.compile(r"\b([a-z]\w*?)\[([0-9,]*)\]")
# an HLO instruction line using a collective:
#   %x = RESULT_TYPE(S) all-gather(%operand, ...), replica_groups=...
# Post-optimization HLO prints operands untyped, so sizes come from the
# RESULT type(s), with per-op wire accounting below.
_COLL_RE = re.compile(
    r"=\s+(.*?)\b(" + "|".join(COLLECTIVES) + r")(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))            # [n_groups, group_size]
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return m.group(1).count(",") + 1  # explicit first group
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Per-device wire bytes of every collective op in optimized HLO.

    Accounting per op (result-shape based, since operands are untyped):
      all-gather          result bytes           (≈ (n−1)/n received)
      all-reduce          2 × result bytes       (ring: reduce-scatter +
                                                  all-gather phases)
      reduce-scatter      result bytes × group   (operand crosses the wire)
      all-to-all          Σ result tuple bytes
      collective-permute  result bytes

    Returns {"total": int, "by_type": {op: bytes}, "counts": {op: n}}."""
    by_type: dict = defaultdict(int)
    counts: dict = defaultdict(int)
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # async pair: the -start carries the shapes
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_part, op = m.group(1), m.group(2)
        size = sum(_shape_bytes(d, dims)
                   for d, dims in _SHAPE_RE.findall(result_part))
        if op == "all-reduce":
            size *= 2
        elif op == "reduce-scatter":
            size *= _group_size(line)
        by_type[op] += size
        counts[op] += 1
    return {"total": int(sum(by_type.values())),
            "by_type": dict(by_type), "counts": dict(counts)}


# ---------------------------------------------------------------------------
# roofline terms
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_kind: str
    flops_per_dev: float          # unroll-diff-corrected, per device
    bytes_per_dev: float
    coll_bytes_per_dev: float
    model_flops: float            # 6·N_active·D (train) or 2·N_active·D
    memory_per_dev: float         # peak (temp+args) from memory_analysis

    @property
    def t_compute(self) -> float:
        return (self.flops_per_dev
                / device_peaks(self.device_kind)["bf16_flops"])

    @property
    def t_memory(self) -> float:
        return (self.bytes_per_dev
                / device_peaks(self.device_kind)["hbm_bytes_per_s"])

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_dev / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Lower bound: perfectly overlapped terms → max; report max."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — catches remat/redundancy waste."""
        total_hlo = self.flops_per_dev * self.chips
        return self.model_flops / total_hlo if total_hlo else float("nan")

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilisation at the roofline-bound step time."""
        t = self.step_time
        peak = self.chips * device_peaks(self.device_kind)["bf16_flops"]
        return (self.model_flops / peak) / t if t else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_flops_ratio,
            "roofline_mfu": self.mfu,
            "mem_gb_per_dev": self.memory_per_dev / 2**30,
        }


def model_flops_for(cfg, shape: dict) -> float:
    """Analytic MODEL_FLOPS for the cell: 6·N_active·D (train) /
    2·N_active·D (inference), D = tokens processed in the step."""
    n = cfg.active_param_count()
    if shape["step"] == "train":
        tokens = shape["seq_len"] * shape["global_batch"]
        return 6.0 * n * tokens
    if shape["step"] == "prefill":
        tokens = shape["seq_len"] * shape["global_batch"]
        return 2.0 * n * tokens
    return 2.0 * n * shape["global_batch"]  # decode: 1 token/seq


def from_record(rec: dict) -> Roofline:
    """Build a Roofline from one dry-run JSON record."""
    return Roofline(
        arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"],
        chips=rec["chips"], device_kind=rec["device_kind"],
        flops_per_dev=rec["cost_true"]["flops"],
        bytes_per_dev=rec["cost_true"]["bytes"],
        coll_bytes_per_dev=rec["cost_true"]["collective_bytes"],
        model_flops=rec["model_flops"],
        memory_per_dev=rec["memory"]["temp_bytes"]
        + rec["memory"]["argument_bytes"])


def load_records(directory: str) -> list[dict]:
    recs = []
    for f in sorted(os.listdir(directory)):
        if f.endswith(".json"):
            with open(os.path.join(directory, f)) as fh:
                recs.append(json.load(fh))
    return recs


def table(directory: str) -> str:
    """Markdown roofline table from a directory of dry-run records."""
    rows = []
    for rec in load_records(directory):
        if rec.get("skipped") or rec.get("mesh") != "single":
            continue
        rows.append(from_record(rec).row())
    hdr = ("| arch | shape | t_comp (ms) | t_mem (ms) | t_coll (ms) | "
           "bottleneck | useful | roofline-MFU | GB/dev |")
    sep = "|" + "---|" * 9
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']*1e3:.2f} "
            f"| {r['t_memory_s']*1e3:.2f} | {r['t_collective_s']*1e3:.2f} "
            f"| {r['bottleneck']} | {r['useful_ratio']:.2f} "
            f"| {r['roofline_mfu']*100:.1f}% | {r['mem_gb_per_dev']:.1f} |")
    return "\n".join(lines)


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    args = ap.parse_args()
    print(table(args.dir))


if __name__ == "__main__":
    main()
