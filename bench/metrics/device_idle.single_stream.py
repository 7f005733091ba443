"""Device: share of the traced window with no op on the chip, in a
single_stream cell."""
from bench.readers import device_idle as read  # noqa: F401
