#!/usr/bin/env python3
"""Device time by layer and the launch split of each frame, for one cell,
on the chip:

    python3 bench/layers.py --workload <cell> --seed <n> [--seconds <s>]
        [--max-requests <n>] [--fixture <dir>]

Sets the cell up as `run.py` does (weights from the seed, the AOT-compiled
forward, the mix's warm-up) and keeps the compiled program's text.  Then
runs the mix for ``--seconds`` with the profiler off (0 skips it), and for
the mix's ``trace_seconds`` (or ``--max-requests``) under the profiler
with `run.py`'s options and ``window`` span.  On stderr: the device time by
layer and role (`scopes.py`), and the offset bounds of the launches
(`launches.py`) beside `trace_reduce`'s clock shift.  On stdout, one JSON
line: the per-layer readings (``conv_glue``; in single-stream cells
``launch_ms``, ``device_wait_ms``, ``return_ms``), the checks on them, the
end-to-end numbers of both windows, and the seconds the program text and
the reductions took.  ``--fixture`` copies the traced window's
``.xplane.pb`` and the program text to ``<dir>/<cell>.layers.xplane.pb``
and ``<dir>/<cell>.hlo.txt.gz``.
"""

from __future__ import annotations

import argparse
import gc
import gzip
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import launches, loadgen, run, scopes, trace_reduce  # noqa: E402

NAMED_SHARE = 0.95   # least share of busy time in a named layer
FRAME_TOL = 0.02     # the five parts of a frame add up to it within this


def checks(lay: dict, lau: dict, single_stream: bool) -> dict:
    out = {"named_share": {"value": lay["named_s"] / lay["busy_s"],
                           "least": NAMED_SHARE},
           "paired": {"value": lau["paired"], "programs": lau["programs"]}}
    if single_stream:
        lo, hi = lau["offset_bounds_s"] or (None, None)
        out["offset_bounds"] = {"value": [lo, hi],
                                "ok": lo is not None and lo <= hi}
        worst = max((abs(sum(f[k] for k in launches.PARTS) / f["frame"] - 1)
                     for f in lau["frames"]), default=None)
        out["frame_sum"] = {"value": worst, "limit": FRAME_TOL,
                            "frames": len(lau["frames"])}
    out["ok"] = (out["named_share"]["value"] >= NAMED_SHARE
                 and lau["paired"] == lau["programs"]
                 and (not single_stream
                      or (out["offset_bounds"]["ok"]
                          and out["frame_sum"]["value"] is not None
                          and out["frame_sum"]["value"] <= FRAME_TOL)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--max-requests", type=int, default=None)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax
    import numpy as np
    from repro.runtime.compile_cache import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    w = run.resolve(run.load_spec(), args.workload)
    try:
        dev = run.check_device(w["cell"]["chips"])
    except run.NoChip as e:
        print(f"layers: {e}; this runs only on the chip", file=sys.stderr)
        return 2
    cfg, traffic, cell = w["config"], w["traffic"], w["cell"]
    single_stream = traffic["host_io"]
    kp, kx, kb = run.seed_keys(args.seed)
    qparams, apply = run.build_program(cfg, kp, kb)
    ring = loadgen.make_ring(kx, traffic, cfg["image_size"],
                             cfg["in_channels"])
    compiled = jax.jit(apply).lower(
        qparams, jax.ShapeDtypeStruct(ring[0].shape, np.float32)).compile()
    t0 = time.perf_counter()
    text = compiled.as_text()
    as_text_s = time.perf_counter() - t0

    def step(x):
        return compiled(qparams, x)

    loadgen.warm_up(step, ring, traffic)
    gc.collect()
    names = [m["name"] for m in w["end_to_end"] if m["name"] != "setup_s"]
    untraced = None
    if args.seconds > 0:
        untraced = run.end_to_end(names, loadgen.drive(step, ring, traffic,
                                                       args.seconds), 0.0)

    log_dir = os.path.join(run.TRACE_DIR, "layers-" + cell["name"])
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir, profiler_options=run.trace_options())
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            seconds = (float("inf") if args.max_requests
                       else traffic["trace_seconds"])
            record = loadgen.drive(step, ring, traffic, seconds,
                                   max_requests=args.max_requests)
    finally:
        jax.profiler.stop_trace()
    traced = run.end_to_end(names, record, 0.0)

    path = trace_reduce.find_xplane(log_dir)
    t0 = time.perf_counter()
    reduced = trace_reduce.reduce(trace_reduce.load(path), top=None)
    shift_s = reduced["clock_shift_s"]
    t1 = time.perf_counter()
    lay = scopes.reduce(text, reduced)
    t2 = time.perf_counter()
    lau = launches.reduce(launches.load(path))
    t3 = time.perf_counter()
    if args.fixture:
        os.makedirs(args.fixture, exist_ok=True)
        base = os.path.join(args.fixture, cell["name"])
        shutil.copyfile(path, base + ".layers.xplane.pb")
        with gzip.open(base + ".hlo.txt.gz", "wt") as f:
            f.write(text)
    shutil.rmtree(log_dir, ignore_errors=True)

    ctx = {"scopes": lay, "launches": lau}
    metrics = {"conv_glue": scopes.conv_glue(ctx)}
    if single_stream:
        metrics.update(launch_ms=launches.launch_ms(ctx),
                       device_wait_ms=launches.device_wait_ms(ctx),
                       return_ms=launches.return_ms(ctx))
    print(scopes.table(lay), file=sys.stderr)
    bounds = lau["offset_bounds_s"]
    print(f"launches: {lau['paired']} of {lau['programs']} programs paired; "
          f"offset bounds {bounds} s; trace_reduce clock_shift_s "
          f"{shift_s!r}", file=sys.stderr)
    line = {"workload": cell["name"], "device": dev, "metrics": metrics,
            "checks": checks(lay, lau, single_stream),
            "end_to_end": {"untraced": untraced, "traced": traced},
            "cost_s": {"as_text": as_text_s, "trace_reduce": t1 - t0,
                       "scopes": t2 - t1, "launches": t3 - t2},
            "layers": {"busy_s": lay["busy_s"], "named_s": lay["named_s"],
                       "conv_glue_s": lay["conv_glue_s"],
                       "top": lay["by_layer_role"][:15],
                       "unmapped": lay["unmapped"]},
            "launches": {"offset_bounds_s": bounds,
                         "clock_shift_s": shift_s,
                         "frames": len(lau["frames"]),
                         "median_ms": {k: 1e3 * statistics.median(
                             f[k] for f in lau["frames"])
                             for k in (*launches.PARTS, "frame")}
                         if lau["frames"] else None}}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
