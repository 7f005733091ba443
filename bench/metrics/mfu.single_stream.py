"""Whole forward: conv and head flops per second over the chip's bf16
peak, over the traced window of a single_stream cell."""
from bench.readers import mfu as read  # noqa: F401
