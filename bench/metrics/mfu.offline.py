"""Whole forward: conv and head flops per second over the chip's bf16
peak, over the traced window of an offline cell."""
from bench.readers import mfu as read  # noqa: F401
