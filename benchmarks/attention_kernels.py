"""GQA/MQA attention kernel bench: native kv-head-grid Pallas path vs the
legacy `jnp.repeat` expansion, across serving-shaped (decode / prefill)
cases.

Times the blockwise jnp path (what model lowering uses on CPU) against a
full-softmax reference on small shapes, and runs Pallas probes
(interpret mode off-TPU) — including a **traced-offset decode** probe
(q_offset as a jitted scalar operand, the case that used to fall back to
blockwise) — as correctness checks.  Emits ``BENCH_attention.json`` at the
repo root via `benchmarks/common.py`.

Timing hygiene: jitted entry points hoisted to module level, compile
reported separately from the steady-state mean.

Each row carries the analytic HBM traffic per path
(`kernels/flash_attention.attention_traffic_bytes`).  On CPU the timings
measure interpreter overhead, but the bytes-moved columns are
backend-independent and must show the native GQA path moving ≥2× fewer
bytes than the repeat path on every H/Hkv = 4 case with Tk ≥ 4096 — K/V
traffic scaling with kv heads, not query heads (the paper's broadcast
dataflow argument).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.autotune import default_attention_config
from repro.kernels.flash_attention import attention_traffic_bytes
from repro.kernels.ref import ref_attention

from .common import fmt_table, write_json

TRAFFIC_WIN_GQA4 = 2.0   # acceptance: native ≥2× fewer bytes at rep=4

# (case, B, Tq, Tk, H, Hkv, D) — decode/prefill shapes at serving ratios
CASES = [
    ("decode_gqa4",   1,   1, 4096,  8, 2, 64),
    ("decode_gqa4_8k", 1,  1, 8192,  8, 2, 64),
    ("decode_mqa",    1,   1, 4096,  8, 1, 64),
    ("prefill_gqa4",  1, 128, 4096,  8, 2, 64),
    ("decode_mha",    1,   1, 4096,  8, 8, 64),   # control: no GQA win
]


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def _attn(q, k, v, *, impl, interpret=None):
    return ops.attention(q, k, v, causal=True, impl=impl,
                         interpret=interpret)


@jax.jit
def _attn_decode_traced(q, k, v, q_offset):
    # q_offset is a traced scalar: exercises the scalar-prefetch decode
    # path of the Pallas kernel (previously a blockwise fallback).
    return ops.attention(q, k, v, causal=True, q_offset=q_offset,
                         impl="pallas", interpret=None)


def _bench(fn, *args, reps: int = 5, **kw):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args, **kw))
    compile_us = (time.perf_counter() - t0) * 1e6
    jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return compile_us, (time.perf_counter() - t0) / reps * 1e6


def run() -> dict:
    rng = np.random.default_rng(0)
    rows, ok = [], True
    for case, B, Tq, Tk, H, Hkv, D in CASES:
        q = jnp.asarray(rng.normal(size=(B, Tq, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, Tk, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, Tk, Hkv, D)), jnp.float32)
        bw_c, bw_us = _bench(_attn, q, k, v, impl="blockwise")

        blocks = default_attention_config(B, Tq, Tk, H, Hkv, D)
        traffic = {p: attention_traffic_bytes(p, B, Tq, Tk, H, Hkv, D,
                                              **blocks)
                   for p in ("pallas", "repeat", "blockwise")}
        # the claim under test is the K/V term: the repeat path moves K/V
        # proportional to H query heads, the native kernel to Hkv kv heads
        win = traffic["repeat"]["kv"] / traffic["pallas"]["kv"]
        rep = H // Hkv
        traffic_ok = (win >= TRAFFIC_WIN_GQA4) \
            if (rep >= 4 and Tk >= 4096) else True
        ok &= traffic_ok
        rows.append({
            "case": case, "shape": f"{B}x{Tq}/{Tk}x{H}.{Hkv}x{D}",
            "rep": rep,
            "blockwise_us": round(bw_us, 1),
            "blockwise_compile_us": round(bw_c, 1),
            "bytes_repeat": traffic["repeat"]["total"],
            "bytes_native": traffic["pallas"]["total"],
            "bytes_blockwise": traffic["blockwise"]["total"],
            "kv_bytes_repeat": traffic["repeat"]["kv"],
            "kv_bytes_native": traffic["pallas"]["kv"],
            "native_traffic_win_x": round(win, 2),
            "ok": traffic_ok,
        })

    # Pallas probes (correctness, not speed): native GQA kernel
    # ≡ blockwise ≡ ref on a small GQA shape, plus traced-offset decode.
    B, T, H, Hkv, D = 1, 64, 8, 2, 16
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    want = ref_attention(q, k, v, causal=True)
    c_us, s_us = _bench(_attn, q, k, v, impl="pallas", interpret=None,
                        reps=3)
    d_full = float(jnp.max(jnp.abs(
        _attn(q, k, v, impl="pallas", interpret=None) - want)))
    dec = _attn_decode_traced(q[:, -1:], k, v, jnp.asarray(T - 1, jnp.int32))
    d_dec = float(jnp.max(jnp.abs(dec[:, 0] - want[:, -1])))
    probes = {"pallas_gqa": {"compile_us": round(c_us, 1),
                             "steady_us": round(s_us, 1), "maxdiff": d_full},
              "pallas_decode_traced_offset": {"maxdiff": d_dec}}
    probes_ok = d_full < 1e-3 and d_dec < 1e-3
    ok &= probes_ok

    cols = ["case", "shape", "rep", "blockwise_us", "bytes_repeat",
            "bytes_native", "native_traffic_win_x", "ok"]
    print(fmt_table(rows, cols))
    for name, p in probes.items():
        print(f"{name} probe ({jax.default_backend()}): |Δ vs ref| = "
              f"{p['maxdiff']:.2e} "
              f"({'OK' if p['maxdiff'] < 1e-3 else 'FAIL'})")
    min_win = min(r["native_traffic_win_x"] for r in rows if r["rep"] >= 4)
    out = {"rows": rows, "probes": probes,
           "pallas_interpret_maxdiff": max(p["maxdiff"]
                                           for p in probes.values()),
           "min_gqa4_traffic_win_x": min_win, "ok": ok}
    path = write_json("BENCH_attention.json", out)
    print(f"wrote {path}")
    return out
