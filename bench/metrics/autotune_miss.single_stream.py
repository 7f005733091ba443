"""Dispatch and tiling: conv autotune lookups that missed, in a
single_stream cell."""
from bench.readers import autotune_miss as read  # noqa: F401
