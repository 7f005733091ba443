#!/usr/bin/env python3
"""Record the traces `test_bench_trace_reduce.py` reads, on the chip:

    python3 tests/bench/record_trace_fixtures.py

For each fixture cell: set up as `bench/run.py` does, then trace a few
requests of its traffic mix inside the harness's ``window`` span, with the
harness's profiler options, and copy the `.xplane.pb` to `data/<cell>.xplane.pb`.
"""

import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import loadgen, run, trace_reduce  # noqa: E402

# cell → requests traced
FIXTURES = {"mobilenet_v1-single_stream": 4, "resnet34-offline-b32": 3}


def main() -> int:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax
    import numpy as np
    from bench.reference.common import seed_key
    for cell, n in FIXTURES.items():
        w = run.resolve(run.load_spec(), cell)
        run.check_device(w["cell"]["chips"])
        cfg, traffic = w["config"], w["traffic"]
        kp, kx, kb = jax.random.split(seed_key(11), 3)
        qparams, apply = run.build_program(cfg, kp, kb)
        ring = loadgen.make_ring(kx, traffic, cfg["image_size"],
                                 cfg["in_channels"])
        compiled = jax.jit(apply).lower(qparams, jax.ShapeDtypeStruct(
            ring[0].shape, np.float32)).compile()

        def step(x):
            return compiled(qparams, x)

        loadgen.warm_up(step, ring, traffic)
        log_dir = os.path.join(run.TRACE_DIR, "fixture-" + cell)
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(log_dir,
                                 profiler_options=run.trace_options())
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            loadgen.drive(step, ring, traffic, float("inf"), max_requests=n)
        jax.profiler.stop_trace()
        dst = os.path.join(HERE, "data", cell + ".xplane.pb")
        shutil.copyfile(trace_reduce.find_xplane(log_dir), dst)
        shutil.rmtree(log_dir)
        print(cell, os.path.getsize(dst), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
