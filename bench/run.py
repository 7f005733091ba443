#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics come from
`BENCHMARK.json` and the files under `bench/` named there (see
`bench/__init__.py`).  One run:

  set-up   the weights from the seed, made on the device in one jitted call
           (the program's initialiser, `fill_biases` and
           `quantize_cnn_params`); the mix's inputs; the jitted forward
           compiled (or read from the compile cache in `.jax_cache/`); the
           mix's warm-up requests.  ``setup_s`` is the time from the start
           of this process to the start of the window.
  window   the mix's closed loop for ``--seconds`` (``trace_seconds`` with
           ``--trace 1``, under the profiler)
  check    every answer of the window against the plain float32 reference
           of `bench/reference/`, run after the program's state is freed,
           under the cell's limit in `limits/<cell>.json`

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit,
also the last lines of stderr.  Without a TPU whose `device_kind` has a row
in `peaks.py`, or with fewer chips than the cell asks for, it prints no
result and exits 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_traces")
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    """No TPU, an unknown TPU, or too few chips for the cell."""


# ---------------------------------------------------------------------------
# the registry: everything found by its name in BENCHMARK.json
# ---------------------------------------------------------------------------


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def metric_applies(metric: dict, cell: str, reported: set[str]) -> bool:
    """A metric with a ``workloads`` key is reported in those cells; one
    without, in every cell that reports the end-to-end metric it moves (or
    in every cell, for an end-to-end metric)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def resolve(spec: dict, workload: str, root: str = ROOT) -> dict:
    """The cell named ``workload`` with its configuration, traffic mix and
    metrics loaded from their files."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    e2e = [m for m in spec["end_to_end"]
           if metric_applies(m, workload, set())]
    names = {m["name"] for m in e2e}
    per_layer = [dict(m, file=os.path.join(root, "bench", "metrics",
                                           m["name"] + ".py"))
                 for m in spec["per_layer"]
                 if metric_applies(m, workload, names)]
    return {"cell": cell,
            "config": _load_json(os.path.join(root, cfg_entry["file"])),
            "traffic": _load_json(os.path.join(
                root, "bench", "traffic", cell["traffic"] + ".json")),
            "limits": _load_json(os.path.join(
                root, "bench", "limits", workload + ".json")),
            "end_to_end": e2e, "per_layer": per_layer}


def load_reader(path: str):
    """The ``read`` function of a per-layer metric's file."""
    name = "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------


def check_device(chips: int) -> dict:
    """The device the run reports; raises `NoChip` off-TPU."""
    import jax
    from bench.peaks import PEAKS
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev['platform']!r})")
    if dev["kind"] not in PEAKS:
        raise NoChip(f"no peaks for device_kind {dev['kind']!r}")
    if dev["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{dev['count']}")
    return dev


# ---------------------------------------------------------------------------
# the system under test, and the reference
# ---------------------------------------------------------------------------


def lookup_counts() -> dict:
    from repro.obs import metrics as obs_metrics
    return {r: obs_metrics.REGISTRY.counter(
        "autotune_lookup", op="conv2d", result=r).value
        for r in ("hit_warm", "hit_user", "miss")}


def build_program(cfg: dict, kp, kb):
    """The served weights, made on the device in one jitted call, and the
    forward users call (`make_cnn(conv_impl="auto")`'s apply)."""
    import jax
    from bench.reference.common import fill_biases
    from repro.core.logquant import LogQuantConfig
    from repro.models.cnn import make_cnn
    from repro.serving.quantize import quantize_cnn_params
    q = cfg["quant"]
    qcfg = LogQuantConfig(bits=q["bits"], frac_bits=q["frac_bits"],
                          per_channel=q["per_channel"])
    made = {}

    def make(kp, kb):
        params, apply = make_cnn(cfg["net"], kp, n_classes=cfg["n_classes"],
                                 cin=cfg["in_channels"],
                                 width_mult=cfg["width_mult"],
                                 qcfg=qcfg, conv_impl="auto")
        made["apply"] = apply
        return quantize_cnn_params(fill_biases(params, kb, cfg["bias_std"]),
                                   qcfg)

    qparams = jax.block_until_ready(jax.jit(make)(kp, kb))
    return qparams, made["apply"]


REF_BLOCK = 32  # images per reference call


def seed_keys(seed: int):
    """``(kp, kx, kb)``: the keys of the weights, the inputs and the
    biases, split alike by the program's run and the reference."""
    import jax
    from bench.reference.common import seed_key
    return jax.random.split(seed_key(seed), 3)


def reference_weights(cfg: dict, kp, kb):
    """The reference module of the configuration's net and the weights it
    makes itself from the same keys as the program's."""
    import jax
    from bench.reference import common
    ref = importlib.import_module(f"bench.reference.{cfg['net']}")
    q = cfg["quant"]

    def weights(kp, kb):
        p = common.fill_biases(ref.init(kp, cfg), kb, cfg["bias_std"])
        return common.quantize_convs(p, q["bits"], q["frac_bits"])

    return ref, jax.jit(weights)(kp, kb)


def reference_logits(cfg: dict, kp, kb, inputs: dict) -> dict:
    """``{slot: logits}`` of the plain float32 reference for each input
    batch, computed in blocks of `REF_BLOCK` images."""
    import jax
    import numpy as np
    ref, params = reference_weights(cfg, kp, kb)
    fwd = jax.jit(lambda p, x: ref.apply(p, x, cfg))
    slots = sorted(inputs)
    x = np.concatenate([np.asarray(inputs[s]) for s in slots])
    logits = np.concatenate([
        np.asarray(fwd(params, jax.device_put(x[i:i + REF_BLOCK])))
        for i in range(0, len(x), REF_BLOCK)])
    sizes = np.cumsum([len(inputs[s]) for s in slots])[:-1]
    return dict(zip(slots, np.split(logits, sizes)))


def compare(record: dict, ref: dict, limit: float) -> dict:
    """Each image's max |logit - reference| over the reference's largest
    |logit|, for every answer of the window."""
    import numpy as np
    errs = []
    for out, slot in zip(record["outputs"], record["slots"]):
        y = np.asarray(out, np.float64)
        r = np.asarray(ref[slot], np.float64)
        if y.shape != r.shape:
            errs.append(np.full(r.shape[0], np.inf))
            continue
        e = np.max(np.abs(y - r), axis=-1) / np.max(np.abs(r), axis=-1)
        errs.append(np.where(np.isfinite(e), e, np.inf))
    errs = np.concatenate(errs) if errs else np.zeros(0)
    worst = float(errs.max()) if errs.size else float("inf")
    return {"attempted": int(record["images"]),
            "failed": int(np.sum(~(errs <= limit))),
            "checks": {"max_rel_err": {"value": worst, "limit": limit}}}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def trace_options():
    """Profiler options of a traced window: the device planes and the
    harness's host spans; no Python tracer, no HLO protos."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def end_to_end(names: list[str], record: dict, setup_s: float) -> dict:
    import numpy as np
    lat_ms = np.asarray(record["latencies_s"]) * 1e3
    out = {}
    for name in names:
        if name == "setup_s":
            out[name] = {"value": setup_s, "unit": "s"}
        elif name == "images_per_s":
            out[name] = {"value": record["images"] / record["elapsed_s"],
                         "unit": "images/s"}
        elif name == "latency_p50_ms":
            out[name] = {"value": float(np.percentile(lat_ms, 50)),
                         "unit": "ms"}
        elif name == "latency_p95_ms":
            out[name] = {"value": float(np.percentile(lat_ms, 95)),
                         "unit": "ms"}
        else:
            raise KeyError(f"no arithmetic for end-to-end metric {name!r}")
    return out


def run_cell(w: dict, seed: int, seconds: float, trace: bool,
             device_kind: str | None, t_start: float = T_START,
             wrap_step=None) -> dict:
    """Set up, measure and check one cell (``w`` from `resolve`).

    ``wrap_step`` wraps the timed call: tests plant faults with it, and
    `calibrate.py` puts the lower-precision control in the program's
    place."""
    import jax
    import numpy as np
    from bench import counts, loadgen, trace_reduce
    cfg, traffic, cell = w["config"], w["traffic"], w["cell"]

    def note(phase):
        print(f"setup {phase}: {time.perf_counter() - t_start:.3f} s",
              file=sys.stderr, flush=True)

    note("imports")
    kp, kx, kb = seed_keys(seed)
    qparams, apply = build_program(cfg, kp, kb)
    note("weights")
    ring = loadgen.make_ring(kx, traffic, cfg["image_size"],
                             cfg["in_channels"])
    note("inputs")
    example = jax.ShapeDtypeStruct(ring[0].shape, np.float32)
    before = lookup_counts()
    lowered = jax.jit(apply).lower(qparams, example)
    lookups = {k: v - before[k] for k, v in lookup_counts().items()}
    note("trace and lower")
    compiled = lowered.compile()
    note("compile")

    def step(x):
        return compiled(qparams, x)

    if wrap_step is not None:
        step = wrap_step(step)
    loadgen.warm_up(step, ring, traffic)
    gc.collect()
    note("warm-up")
    setup_s = time.perf_counter() - t_start

    log_dir = os.path.join(TRACE_DIR, cell["name"])
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(log_dir, profiler_options=trace_options())
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                record = loadgen.drive(step, ring, traffic,
                                       min(seconds, traffic["trace_seconds"]))
        finally:
            jax.profiler.stop_trace()
    else:
        record = loadgen.drive(step, ring, traffic, seconds)

    stats = jax.devices()[0].memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")
    record["outputs"] = [np.asarray(y) for y in record["outputs"]]
    for leaf in jax.tree_util.tree_leaves(qparams):
        leaf.delete()
    del qparams, compiled, step
    gc.collect()

    ref = reference_logits(cfg, kp, kb,
                           {s: ring[s] for s in set(record["slots"])})
    result = compare(record, ref, w["limits"]["max_rel_err"])
    result["correct"] = (result["failed"] == 0 and result["attempted"] > 0)
    result["device"] = {"memory_peak_bytes": memory_peak}

    if not trace:
        result["metrics"] = end_to_end([m["name"] for m in w["end_to_end"]],
                                       record, setup_s)
        return result

    from bench.peaks import peaks
    reduced = trace_reduce.reduce(trace_reduce.load(
        trace_reduce.find_xplane(log_dir)))
    shutil.rmtree(log_dir, ignore_errors=True)
    peak = peaks(device_kind)
    fwd = counts.forward_counts(
        counts.program_conv_records(cfg, traffic["batch"]), cfg["head_in"],
        cfg["n_classes"], peak)
    ctx = {"images": record["images"], "forwards": record["requests"],
           "window_s": reduced["window_s"], "trace": reduced,
           "flops_per_image": fwd["flops"] / fwd["batch"],
           "conv_least_s": fwd["conv_least_s"], "peak": peak,
           "lookups": lookups}
    metrics = {}
    for m in w["per_layer"]:
        value = load_reader(m["file"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"].update(busy_s=reduced["busy_s"],
                            window_s=reduced["window_s"])
    result["breakdown"] = {"device_ops": reduced["top_ops"],
                           "idle_gaps": reduced["idle_gaps"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compile cache lives in the checkout, whatever the environment says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    from repro.runtime.compile_cache import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    w = resolve(load_spec(), args.workload)
    try:
        dev = check_device(w["cell"]["chips"])
    except NoChip as e:
        print(f"bench: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 2
    result = run_cell(w, args.seed, args.seconds, bool(args.trace),
                      dev["kind"])
    result["device"] = {**dev, **result["device"]}
    checks = result.pop("checks")
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
