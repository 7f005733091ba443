"""Conv kernels: their roofline time over their device time, in an offline
cell."""
from bench.readers import conv_roofline as read  # noqa: F401
