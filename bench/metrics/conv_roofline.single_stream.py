"""Conv kernels: their roofline time over their device time, in a
single_stream cell."""
from bench.readers import conv_roofline as read  # noqa: F401
