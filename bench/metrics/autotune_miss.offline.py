"""Dispatch and tiling: conv autotune lookups that missed, in an offline
cell."""
from bench.readers import autotune_miss as read  # noqa: F401
