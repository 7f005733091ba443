"""Published peaks of one chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s per chip.  A chip missing here has no
roofline: the benchmark refuses to run on it rather than borrow another
chip's numbers.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip; raises for a `device_kind` not in `PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
