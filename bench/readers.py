"""The arithmetic of the per-layer metrics.  Each `metrics/<name>.py` binds
one of these as its ``read``; a reader that finds nothing to read returns
None and the harness leaves the metric out.

``ctx`` (built by `run.py` after a traced window):

  images, forwards   images and forward calls completed in the window
  window_s           the traced window's length
  trace              `trace_reduce.reduce` of the window
  flops_per_image    conv and head flops of one image (`counts.py`)
  conv_least_s       Σ over one forward's convs of their roofline time
  peak               the chip's row of `peaks.PEAKS`
  lookups            autotune table lookups while the forward was traced:
                     {"hit_warm": n, "hit_user": n, "miss": n}
"""

from __future__ import annotations


def mfu(ctx: dict) -> float | None:
    """Conv and head flops completed per second over the bf16 peak, in %."""
    if not ctx["images"] or ctx["window_s"] <= 0:
        return None
    rate = ctx["images"] * ctx["flops_per_image"] / ctx["window_s"]
    return 100.0 * rate / ctx["peak"]["bf16_flops"]


def conv_roofline(ctx: dict) -> float | None:
    """The convs' roofline time over their kernels' device time, in %."""
    conv_s = ctx["trace"]["conv_s"]
    if not conv_s or not ctx["forwards"]:
        return None
    return 100.0 * ctx["forwards"] * ctx["conv_least_s"] / conv_s


def device_idle(ctx: dict) -> float | None:
    """Share of the window in which no op ran on the device, in %."""
    return 100.0 * ctx["trace"]["idle_share"]


def autotune_miss(ctx: dict) -> float | None:
    """Conv table lookups that missed both tiers, in %."""
    total = sum(ctx["lookups"].values())
    if not total:
        return None
    return 100.0 * ctx["lookups"]["miss"] / total
