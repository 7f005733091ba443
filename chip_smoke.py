#!/usr/bin/env python
"""On-chip smoke check: the paper's main path on one TPU, through the entry
points a user calls, at full width.

    python chip_smoke.py [--seed N]

Phases, all in this one process (a chip serves one process at a time):

  cnn    ResNet-34 and MobileNet v1 at their published size (224×224×3,
         width 1.0, 1000 classes, batch 8), random weights from ``--seed``:
         `make_cnn(conv_impl="auto")` → `quantize_cnn_params` (packed 6-bit
         log codes) → jitted apply → `ops.conv2d` → the fused Pallas conv.
         Checks that dispatch chose Pallas with interpret mode off, and
         that the logits agree with the float32 reference (the same packed
         params through ``conv_impl="ref"`` at highest matmul precision)
         to within `REL_TOL` of the largest logit.  Prints the autotune
         table lookups, compile seconds and a smoke reading of ms per
         batch (not a benchmark: one process, five calls).
  serve  The LM engine through `repro.launch.serve.main` on gemma3-1b at
         full width: 4 requests, 8 new tokens each, every one answered.

Exits non-zero, before printing any result, unless JAX's first device is a
TPU; any failed check or exception exits non-zero too.  On success the
last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

IMG, BATCH, N_CLASSES = 224, 8, 1000
CNN_NETS = ("resnet34", "mobilenet_v1")
SERVE_ARCH, SERVE_REQUESTS, SERVE_MAX_NEW = "gemma3-1b", 4, 8
# max |Δlogit| / max |logit| against the float32 reference: the kernel's
# f32 dots may run as single bf16 MXU passes (8-bit mantissa), and the
# error of ~36 stacked convs stays well under 1% of the logit range
REL_TOL = 1e-2
STEADY_CALLS = 5


def _lookup_counts() -> dict:
    from repro.obs import metrics as obs_metrics
    return {r: obs_metrics.REGISTRY.counter(
        "autotune_lookup", op="conv2d", result=r).value
        for r in ("hit_warm", "hit_user", "miss")}


def cnn_phase(name: str, seed: int) -> dict:
    from repro.kernels.ops import resolve_impl
    from repro.models.cnn import CNNS, make_cnn
    from repro.serving.quantize import quantize_cnn_params

    resolved = resolve_impl("conv2d")
    if resolved != ("pallas", False):
        raise RuntimeError(f"conv2d resolved to {resolved}, not the Pallas "
                           f"kernel with interpret off")
    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    params, apply_fn = make_cnn(name, kp, n_classes=N_CLASSES,
                                width_mult=1.0, conv_impl="auto")
    qparams = quantize_cnn_params(params)
    x = jax.random.normal(kx, (BATCH, IMG, IMG, 3), jnp.float32)

    before = _lookup_counts()
    t0 = time.perf_counter()
    fwd = jax.jit(apply_fn).lower(qparams, x).compile()
    compile_s = time.perf_counter() - t0
    lookups = {r: v - before[r] for r, v in _lookup_counts().items()}

    logits = jax.block_until_ready(fwd(qparams, x))          # warm-up
    t0 = time.perf_counter()
    for _ in range(STEADY_CALLS):
        out = fwd(qparams, x)
    jax.block_until_ready(out)
    ms_per_batch = (time.perf_counter() - t0) / STEADY_CALLS * 1e3

    ref_apply = functools.partial(CNNS[name][1], conv_impl="ref")
    with jax.default_matmul_precision("highest"):
        ref = jax.block_until_ready(jax.jit(ref_apply)(qparams, x))
    if logits.shape != (BATCH, N_CLASSES) or ref.shape != logits.shape:
        raise RuntimeError(f"{name}: logits {logits.shape}, reference "
                           f"{ref.shape}, want {(BATCH, N_CLASSES)}")
    if not bool(jnp.all(jnp.isfinite(logits))):
        raise RuntimeError(f"{name}: non-finite logits")
    rel_err = float(jnp.max(jnp.abs(logits - ref)) / jnp.max(jnp.abs(ref)))
    res = {"net": name, "conv2d": list(resolved), "lookups": lookups,
           "compile_s": compile_s, "smoke_ms_per_batch": ms_per_batch,
           "rel_err_vs_ref": rel_err, "tol": REL_TOL}
    print(f"[cnn] {json.dumps(res)}", flush=True)
    if not rel_err <= REL_TOL:
        raise RuntimeError(f"{name}: max|Δlogit|/max|logit| = {rel_err:.3e} "
                           f"exceeds {REL_TOL}")
    return res


def serve_phase(seed: int) -> dict:
    from repro.configs.registry import get_config
    from repro.launch import serve

    vocab = get_config(SERVE_ARCH).vocab
    t0 = time.perf_counter()
    done = serve.main(["--arch", SERVE_ARCH,
                       "--requests", str(SERVE_REQUESTS),
                       "--max-new", str(SERVE_MAX_NEW), "--seed", str(seed)])
    wall_s = time.perf_counter() - t0
    answered = [r for r in done if len(r.output) == SERVE_MAX_NEW
                and all(0 <= int(t) < vocab for t in r.output)]
    res = {"requests": SERVE_REQUESTS, "answered": len(answered),
           "wall_s_incl_compile": wall_s}
    print(f"[serve] {json.dumps(res)}", flush=True)
    if len(answered) != SERVE_REQUESTS:
        raise RuntimeError(f"served {len(answered)}/{SERVE_REQUESTS} "
                           f"requests with {SERVE_MAX_NEW} in-vocabulary "
                           f"tokens each")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"device: {dev}", flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; this check runs only on the chip",
              file=sys.stderr)
        return 1
    print(f"compile cache: {cache_dir}", flush=True)
    for name in CNN_NETS:
        cnn_phase(name, args.seed)
    serve_phase(args.seed)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
