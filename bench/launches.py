"""Pair each program the device ran with the runtime's own launch events on
the host, and split each single-stream frame on the host's clock.

The profiler's trace holds, besides the harness's spans:

  XLA Modules       on each ``/device:TPU:n`` plane, one event per program
                    run, with its ``run_id``
  DoEnqueueProgram  on a host thread, the runtime handing that program to
                    the device queue, with the same ``run_id`` (and
                    ``device_ordinal``)
  tpu::System::Execute=>Done
                    on a host thread, the host learning that a program is
                    done; it carries no id, and programs finish in the order
                    they were queued, so each enqueue takes the first Done
                    after it that no earlier enqueue took

A program cannot start before its enqueue ends, nor end after the host sees
it done.  Over the pairs, that bounds the offset between the device's and
the host's clocks to ``[lo, hi]`` (ns to add to device times), with no
heuristic.  A single-stream frame (spans ``h2d``, ``dispatch``, ``fetch``)
splits into five parts that need no offset at all:

  h2d          the ``h2d`` span
  launch       ``dispatch`` start to the end of the frame's DoEnqueueProgram
  device       the program's duration on the device
  device_wait  DoEnqueueProgram end to Execute=>Done start, less ``device``
  return       Execute=>Done start to the end of the ``fetch`` span

which add up to the frame (``h2d`` start to ``fetch`` end) less the gap
between the ``h2d`` and ``dispatch`` spans.
"""

from __future__ import annotations

import bisect
import statistics

from bench import trace_reduce

MODULES = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
DONE = "tpu::System::Execute=>Done"
PARTS = ("h2d", "launch", "device", "device_wait", "return")


def load(path: str) -> dict:
    """``{"programs": [(start, end, device, run_id)], "enqueues": [(start,
    end, device, run_id)], "dones": [(start, end)], "spans": ...}`` of an
    ``.xplane.pb``, times in ns; ``spans`` is `trace_reduce.load`'s."""
    from jax.profiler import ProfileData
    programs, enqueues, dones = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            device = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == MODULES:
                    programs.extend(
                        (e.start_ns, e.start_ns + e.duration_ns, device,
                         dict(e.stats).get("run_id")) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ENQUEUE:
                        st = dict(e.stats)
                        enqueues.append((e.start_ns,
                                         e.start_ns + e.duration_ns,
                                         st.get("device_ordinal", 0),
                                         st.get("run_id")))
                    elif e.name == DONE:
                        dones.append((e.start_ns,
                                      e.start_ns + e.duration_ns))
    return {"programs": sorted(programs), "enqueues": sorted(enqueues),
            "dones": sorted(dones),
            "spans": trace_reduce.load(path)["spans"]}


def pair(events: dict) -> list[dict]:
    """One entry per program with an enqueue of the same device and
    ``run_id``: ``{"run_id", "program": (s, e), "enqueue": (s, e),
    "done": (s, e) or None}``, in enqueue order."""
    by_id = {(d, r): (s, e) for s, e, d, r in events["programs"]
             if r is not None}
    launches = []
    for s, e, d, r in sorted(events["enqueues"], key=lambda x: x[1]):
        if (d, r) in by_id:
            launches.append({"run_id": r, "program": by_id[(d, r)],
                             "enqueue": (s, e), "done": None})
    dones = events["dones"]
    starts = [s for s, _ in dones]
    i = 0
    for launch in launches:
        i = max(i, bisect.bisect_left(starts, launch["enqueue"][1]))
        if i < len(dones):
            launch["done"] = dones[i]
            i += 1
    return launches


def offset_bounds(launches: list[dict]) -> tuple[float, float] | None:
    """``(lo, hi)`` ns to add to device times so that every program starts
    after its enqueue ends and ends before the host sees it done."""
    done = [x for x in launches if x["done"] is not None]
    if not done:
        return None
    lo = max(x["enqueue"][1] - x["program"][0] for x in done)
    hi = min(x["done"][0] - x["program"][1] for x in done)
    return lo, hi


def frames(launches: list[dict], spans: dict) -> list[dict]:
    """The five parts of each single-stream frame, in ns, and the frame
    itself (``frame``); frames without a paired, completed launch are left
    out.  Empty where the trace has no ``h2d``/``fetch`` spans."""
    h2d, dispatch, fetch = (spans.get(k, []) for k in
                            ("h2d", "dispatch", "fetch"))
    if not h2d or not (len(h2d) == len(dispatch) == len(fetch)):
        return []
    ends = [x["enqueue"][1] for x in launches]    # sorted, as `pair` is
    out = []
    for (h0, h1), (d0, _), (_, f1) in zip(h2d, dispatch, fetch):
        mine = [x for x in launches[bisect.bisect_left(ends, d0):
                                    bisect.bisect_right(ends, f1)]
                if x["done"] is not None and x["done"][0] <= f1]
        if len(mine) != 1:
            continue
        x = mine[0]
        device = x["program"][1] - x["program"][0]
        out.append({"h2d": h1 - h0,
                    "launch": x["enqueue"][1] - d0,
                    "device": device,
                    "device_wait": x["done"][0] - x["enqueue"][1] - device,
                    "return": f1 - x["done"][0],
                    "frame": f1 - h0})
    return out


def reduce(events: dict) -> dict:
    """The launches of a traced window: how many programs ran and were
    paired, the offset bounds in s, and each frame's parts in s."""
    launches = pair(events)
    bounds = offset_bounds(launches)
    return {"programs": len(events["programs"]), "paired": len(launches),
            "offset_bounds_s": (None if bounds is None
                                else [b / 1e9 for b in bounds]),
            "frames": [{k: v / 1e9 for k, v in f.items()}
                       for f in frames(launches, events["spans"])]}


def _median_ms(ctx: dict, part: str) -> float | None:
    got = ctx.get("launches")
    if not got or not got["frames"]:
        return None
    return 1e3 * statistics.median(f[part] for f in got["frames"])


def launch_ms(ctx: dict) -> float | None:
    """Median per frame of ``dispatch`` start to its enqueue's end, in ms
    (``ctx["launches"]``: `reduce`)."""
    return _median_ms(ctx, "launch")


def device_wait_ms(ctx: dict) -> float | None:
    """Median per frame of enqueue end to Execute=>Done, less the
    program's device time, in ms."""
    return _median_ms(ctx, "device_wait")


def return_ms(ctx: dict) -> float | None:
    """Median per frame of Execute=>Done to the end of ``fetch``, in ms."""
    return _median_ms(ctx, "return")
