"""Run every paper table/figure benchmark.  One module per artifact.

    PYTHONPATH=src python -m benchmarks.run [--only fig19_utilization ...]

Prints each benchmark's table, then a ``name,us_per_call,derived`` CSV
summary (derived = the headline number + REPRODUCED/FAIL verdict).
"""

from __future__ import annotations

import argparse
import sys

from repro.runtime.compile_cache import setup_compile_cache

from . import (ablation_grad_compress, attention_kernels, fig1_quant,
               fig17_pe_cost, fig19_utilization, fig20_throughput,
               table2_comparison, table3_latency, telemetry_overhead)
from .common import timed

BENCHES = {
    "fig1_quant": (fig1_quant, "snr_gain_db"),
    "attention_kernels": (attention_kernels, "min_gqa4_traffic_win_x"),
    "fig17_pe_cost": (fig17_pe_cost, "tput_per_pe"),
    "fig19_utilization": (fig19_utilization, None),
    "fig20_throughput": (fig20_throughput, "adjusted_pes"),
    "table2_comparison": (table2_comparison, "peak_gops"),
    "table3_latency": (table3_latency, "total_ms"),
    "ablation_grad_compress": (ablation_grad_compress, "ef_gap"),
    "telemetry_overhead": (telemetry_overhead, "overhead_pct"),
}


ALIASES = {"attention": "attention_kernels",  # short names for --only
           "telemetry": "telemetry_overhead"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*",
                    choices=list(BENCHES) + list(ALIASES))
    args = ap.parse_args(argv)
    setup_compile_cache()
    names = [ALIASES.get(n, n) for n in (args.only or list(BENCHES))]

    summary = []
    ok_all = True
    for name in names:
        mod, key = BENCHES[name]
        print(f"\n=== {name} " + "=" * max(0, 60 - len(name)))
        out, us = timed(mod.run)
        derived = f"{out.get(key):.4g}" if key and out.get(key) is not None \
            else ("ok" if out.get("ok") else "fail")
        verdict = "REPRODUCED" if out.get("ok") else "FAIL"
        ok_all &= bool(out.get("ok"))
        summary.append(f"{name},{us:.0f},{derived} [{verdict}]")

    print("\nname,us_per_call,derived")
    for line in summary:
        print(line)
    print(f"\noverall: "
          f"{'ALL PAPER CLAIMS REPRODUCED' if ok_all else 'SOME FAILED'}")
    return 0 if ok_all else 1


if __name__ == "__main__":
    sys.exit(main())
