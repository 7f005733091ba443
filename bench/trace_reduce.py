"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time and idle share, device time per op, the conv
kernels' time, and the idle gaps named by what the host was doing.

Device ops are the events of the ``XLA Ops`` line of each ``/device:TPU:n``
plane; each event's name is its HLO instruction
(``%name = type opcode(operands), attrs``).  Host spans are the harness's
own `jax.profiler.TraceAnnotation`s on the host plane.  The measured window
is the harness's ``window`` span.

The profiler maps device time onto the host's clock, and on a TPU v5e host
that mapping was found about a millisecond early: device programs appeared
to start before the host dispatched them.  `reduce` therefore
moves each device's events later by the least shift that starts every
program (an ``XLA Modules`` event) no earlier than the ``dispatch`` span
that sent it, the k-th program pairing with the k-th dispatch.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

WINDOW_SPAN = "window"
HOST_SPANS = ("h2d", "dispatch", "fetch", "drain")
UNNAMED = "host_other"  # idle time outside every named host span

_OPCODE = re.compile(r"\s([a-z][\w\-.]*)\(")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def op_name(hlo: str) -> str:
    """``%log_conv2d_fused_pallas.30 = ...`` → ``log_conv2d_fused_pallas.30``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def opcode(hlo: str) -> str:
    rhs = hlo.split(" = ", 1)[1] if " = " in hlo else ""
    m = _OPCODE.search(rhs)
    return m.group(1) if m else ""


def is_conv(hlo: str) -> bool:
    """A conv kernel event: a Mosaic custom call, an XLA convolution, or a
    fusion built around one."""
    op = opcode(hlo)
    if op == "custom-call":
        return 'custom_call_target="tpu_custom_call"' in hlo
    if op == "convolution":
        return True
    return op == "fusion" and "convolution" in hlo.split("calls=", 1)[-1]


def load(path: str) -> dict:
    """``{"devices": [[(start_ns, end_ns, hlo), ...] per device],
    "programs": [[start_ns, ...] per device],
    "spans": {name: [(start_ns, end_ns), ...]}}``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, programs, spans = [], [], collections.defaultdict(list)
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, starts = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events)
                elif line.name == "XLA Modules":
                    starts.extend(e.start_ns for e in line.events)
            devices.append(sorted(ops))
            programs.append(sorted(starts))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans[e.name].append((e.start_ns,
                                              e.start_ns + e.duration_ns))
    return {"devices": devices, "programs": programs,
            "spans": {k: sorted(v) for k, v in spans.items()}}


def clock_shift(programs: list, dispatches: list) -> float:
    """The least shift (ns, ≥ 0) that starts the k-th program no earlier
    than the k-th dispatch span; 0 where the counts differ and the pairing
    is unknown."""
    if len(programs) != len(dispatches):
        return 0.0
    return max([0.0] + [d[0] - p for p, d in zip(programs, dispatches)])


def union(intervals) -> list[tuple[float, float]]:
    """Merge intervals into disjoint, sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def gaps(busy, t0: float, t1: float) -> list[tuple[float, float]]:
    """The parts of [t0, t1] that no interval of ``busy`` (disjoint,
    sorted) covers."""
    out, cur = [], t0
    for s, e in busy:
        if e <= t0 or s >= t1:
            continue
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def attribute(gap_list, spans: dict) -> dict[str, float]:
    """Nanoseconds of the gaps that each host span covers; what no span
    covers goes to `UNNAMED`."""
    labelled = sorted((s, e, name) for name in HOST_SPANS
                      for s, e in spans.get(name, ()))
    starts = [s for s, _, _ in labelled]
    out = collections.Counter()
    for g0, g1 in gap_list:
        covered = 0.0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(labelled) and labelled[i][0] < g1:
            s, e, name = labelled[i]
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                out[name] += ov
                covered += ov
            i += 1
        if g1 - g0 - covered > 0:
            out[UNNAMED] += g1 - g0 - covered
    return dict(out)


def reduce(trace: dict, window: tuple[float, float] | None = None,
           top: int = 10) -> dict:
    """Numbers of one traced window, averaged over the device planes.

    ``window`` defaults to the first ``window`` host span.  Device events
    are clipped to it."""
    if window is None:
        found = trace["spans"].get(WINDOW_SPAN)
        if not found:
            raise ValueError("no window span in the trace")
        window = found[0]
    t0, t1 = window
    if not trace["devices"]:
        raise ValueError("no TPU device plane in the trace")
    busy_ns, conv_ns, n_conv, shift_ns = 0.0, 0.0, 0, 0.0
    op_ns = collections.Counter()
    idle = collections.Counter()
    for ops, programs in zip(trace["devices"], trace["programs"]):
        shift = clock_shift(programs, trace["spans"].get("dispatch", []))
        shift_ns += shift
        clipped = [(max(s + shift, t0), min(e + shift, t1), h)
                   for s, e, h in ops if e + shift > t0 and s + shift < t1]
        busy = union((s, e) for s, e, _ in clipped)
        busy_ns += sum(e - s for s, e in busy)
        for s, e, h in clipped:
            op_ns[op_name(h)] += e - s
            if is_conv(h):
                conv_ns += e - s
                n_conv += 1
        idle.update(attribute(gaps(busy, t0, t1), trace["spans"]))
    n = len(trace["devices"])
    window_s = (t1 - t0) / 1e9
    busy_s = busy_ns / n / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "conv_s": conv_ns / n / 1e9,
        "clock_shift_s": shift_ns / n / 1e9,
        "n_conv_events": n_conv / n,
        "top_ops": [[k, v / n / 1e9] for k, v in op_ns.most_common(top)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in idle.most_common(top)],
    }
