"""Kernel-dispatch profiling: per-op records behind `kernels/ops.py`.

Every dispatch through the unified ops surface (`conv2d` / `attention` /
`log_matmul` / `wkv6`) is recorded here when profiling is on: the op, the
resolved impl, the shape key (the same namespaced key the autotuner
uses), the **analytic bytes moved** (from `conv_traffic_bytes` /
`attention_traffic_bytes` — the paper's per-layer traffic accounting),
and wall time split into first-call (compile-inclusive) vs steady state,
measured around `jax.block_until_ready`.

Two dispatch regimes:

  eager    the op ran on concrete arrays — it is timed directly; the
           first call for a key is the compile-inclusive sample, later
           calls accumulate steady-state stats.
  traced   the op ran on tracers inside a `jax.jit` trace — there is no
           per-op wall clock (XLA fuses the program), so the record
           carries shape/bytes only and is tagged with the enclosing
           **program** (`time_program`, e.g. the serving engine's
           "prefill"/"decode" jit calls), whose own time the program
           record holds.  Its ``steady_us`` is None: the program's time is
           not one kernel's, and a kernel's device time comes from a
           profiler trace of the program.

Gating mirrors the tracer: ``REPRO_KERNEL_PROFILE=1`` or ``REPRO_TRACE=1``
(a trace without kernel rows is half a trace), or `set_enabled(True)`.
Disabled cost is one env check per op call; crucially, the
`block_until_ready` sync — which would break async dispatch pipelining —
only ever happens while profiling is on.
"""

from __future__ import annotations

import os
import threading
import time

import jax

from . import metrics as _metrics
from . import trace as _trace

_OFF = ("", "0", "false", "off")


def is_traced(*operands) -> bool:
    """True when any operand is a JAX tracer (op is being staged, not run)."""
    return any(isinstance(x, jax.core.Tracer) for x in operands)


def _new_entry(op, impl, key, bytes_moved):
    return {"op": op, "impl": impl, "key": key, "bytes": bytes_moved,
            "calls": 0, "traced_calls": 0, "first_us": None,
            "steady_n": 0, "steady_sum": 0.0, "steady_min": None,
            "program": None}


def _push_steady(ent, dt_us):
    ent["steady_n"] += 1
    ent["steady_sum"] += dt_us
    ent["steady_min"] = dt_us if ent["steady_min"] is None \
        else min(ent["steady_min"], dt_us)


class KernelProfiler:
    """Process-wide dispatch recorder used by `kernels/ops.py`."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[tuple, dict] = {}
        self._programs: dict[str, dict] = {}
        self._local = threading.local()
        self._override: bool | None = None

    # ------------------------------------------------------------- gating
    def enabled(self) -> bool:
        if self._override is not None:
            return self._override
        if os.environ.get("REPRO_KERNEL_PROFILE", "0").lower() not in _OFF:
            return True
        return _trace.TRACER.enabled()

    def set_enabled(self, flag: bool | None) -> None:
        """True/False force; None defers to the env gates."""
        self._override = flag

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._programs.clear()

    # ----------------------------------------------------------- programs
    def current_program(self) -> str | None:
        return getattr(self._local, "program", None)

    def time_program(self, name: str, fn):
        """Run `fn` (typically one jitted engine program) under a named
        program scope: traced kernel dispatches inside it are tagged with
        `name`, and the call is timed end-to-end via `block_until_ready`
        (first call = compile-inclusive, later calls = steady).  The timed
        call is a `jax.profiler.TraceAnnotation` named `name`, so under a
        profiler session it lands on the host plane beside the device's
        programs."""
        if not self.enabled():
            return fn()
        prev = getattr(self._local, "program", None)
        self._local.program = name
        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(name):
            try:
                out = fn()
            finally:
                self._local.program = prev
            jax.block_until_ready(out)
        dt_ns = time.perf_counter_ns() - t0
        dt_us = dt_ns / 1e3
        with self._lock:
            ent = self._programs.setdefault(
                name, {"calls": 0, "first_us": None, "steady_n": 0,
                       "steady_sum": 0.0, "steady_min": None})
            first = ent["calls"] == 0
            if first:
                ent["first_us"] = dt_us
            else:
                _push_steady(ent, dt_us)
            ent["calls"] += 1
        _trace.TRACER.add_complete(name, t0, dt_ns,
                                   phase="compile" if first else "steady")
        return out

    # ----------------------------------------------------------- dispatch
    def dispatch(self, op: str, impl: str, key: str, bytes_moved: dict,
                 fn, *, traced: bool):
        """The hook `kernels/ops.py` routes every kernel call through."""
        if not self.enabled():
            return fn()
        if traced:
            with self._lock:
                ent = self._entries.setdefault(
                    (op, impl, key), _new_entry(op, impl, key, bytes_moved))
                ent["traced_calls"] += 1
                prog = self.current_program()
                if prog is not None:
                    ent["program"] = prog
            return fn()
        t0 = time.perf_counter_ns()
        out = fn()
        jax.block_until_ready(out)
        dt_ns = time.perf_counter_ns() - t0
        dt_us = dt_ns / 1e3
        with self._lock:
            ent = self._entries.setdefault(
                (op, impl, key), _new_entry(op, impl, key, bytes_moved))
            first = ent["calls"] == 0
            if first:
                ent["first_us"] = dt_us
            else:
                _push_steady(ent, dt_us)
            ent["calls"] += 1
        phase = "compile" if first else "steady"
        _trace.TRACER.add_complete(f"{op}[{impl}]", t0, dt_ns,
                                   key=key, phase=phase)
        _metrics.REGISTRY.histogram("kernel_dispatch_us",
                                    bounds=_metrics.US_BUCKETS,
                                    op=op, impl=impl,
                                    phase=phase).record(dt_us)
        return out

    # ------------------------------------------------------------ readout
    def snapshot(self) -> dict:
        """{"records": [per-(op, impl, key) rows], "programs": {...},
        "device": {"platform", "kind", "count"}} — the device names the
        chip the times were taken on (the report looks its peaks up).

        Eagerly-timed rows carry their own steady mean (`steady_us`,
        `steady_source: "self"`); rows staged inside a jit have no clock
        of their own and carry None."""
        with self._lock:
            entries = [dict(e) for e in self._entries.values()]
            programs = {n: dict(p) for n, p in self._programs.items()}
        for p in programs.values():
            p["steady_us"] = (p["steady_sum"] / p["steady_n"]
                              if p["steady_n"] else None)
            del p["steady_sum"]
        records = []
        for e in entries:
            r = {k: e[k] for k in ("op", "impl", "key", "bytes", "calls",
                                   "traced_calls", "first_us", "program")}
            steady = e["steady_n"] > 0
            r["steady_us"] = (e["steady_sum"] / e["steady_n"] if steady
                              else None)
            r["steady_us_min"] = e["steady_min"]
            r["steady_source"] = "self" if steady else None
            records.append(r)
        dev = jax.devices()[0]
        return {"records": records, "programs": programs,
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind,
                           "count": len(jax.devices())}}


PROFILER = KernelProfiler()

dispatch = PROFILER.dispatch
time_program = PROFILER.time_program
snapshot = PROFILER.snapshot
set_enabled = PROFILER.set_enabled
enabled = PROFILER.enabled
clear = PROFILER.clear
