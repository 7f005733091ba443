#!/usr/bin/env python3
"""The readings a cell's correctness limit is set from, taken by whole runs
of the cell in one process on the chip (the benchmark's own runs never run
this).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 [--seconds 3]

program   for each of ``--seeds``, a run of the cell as `run.py` makes it
          (set-up, a window of ``--seconds``, the check): its
          ``max_rel_err``, the lower reading
control   for each of ``--control-seeds`` and each precision of `CONTROLS`,
          the same run with the reference, computed with that precision's
          conv and dense inputs, put in the program's place by
          ``wrap_step``: its ``max_rel_err`` and ``correct`` as the
          harness's own check gives them.  Each must read ``correct``
          false; the smallest reading is the upper one.

Prints one JSON object with every reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402

CONTROLS = ("fp8", "int8")

_FORWARDS: dict = {}


def control_step(w: dict, seed: int, act: str):
    """A ``wrap_step`` that answers each request with the reference of the
    cell's configuration, its conv and dense inputs in ``act`` (see
    `reference/common.cast_act`), on weights it makes from ``seed``."""
    import jax
    cfg = w["config"]
    kp, _, kb = run.seed_keys(seed)
    ref, params = run.reference_weights(cfg, kp, kb)
    key = (json.dumps(cfg, sort_keys=True), act)
    if key not in _FORWARDS:
        _FORWARDS[key] = jax.jit(
            lambda p, x: ref.apply(p, x, cfg, act=act))
    fwd = _FORWARDS[key]
    return lambda step: (lambda x: fwd(params, x))


def reading(r: dict) -> dict:
    return {"max_rel_err": r["checks"]["max_rel_err"]["value"],
            "limit": r["checks"]["max_rel_err"]["limit"],
            "correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax
    from repro.runtime.compile_cache import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    w = run.resolve(run.load_spec(), args.workload)
    dev = run.check_device(w["cell"]["chips"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    out = {"workload": args.workload, "device": dev, "program": {},
           "control": {act: {} for act in CONTROLS}}
    for seed in seeds:
        r = run.run_cell(w, seed, args.seconds, False, dev["kind"],
                         t_start=time.perf_counter())
        out["program"][seed] = reading(r)
        print(f"program {seed}: {out['program'][seed]}", file=sys.stderr,
              flush=True)
    for seed in control_seeds:
        for act in CONTROLS:
            r = run.run_cell(w, seed, args.seconds, False, dev["kind"],
                             t_start=time.perf_counter(),
                             wrap_step=control_step(w, seed, act))
            out["control"][act][seed] = reading(r)
            print(f"control {act} {seed}: {out['control'][act][seed]}",
                  file=sys.stderr, flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
