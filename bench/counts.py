"""Operations and least HBM bytes of a forward pass, from its conv shapes.

The work is counted from shapes alone, so it reads the same whatever
implements a conv:

  flops  2 · B · Ho · Wo · Cout · K² · Cin/groups  (a multiply and an add)
  bytes  the input and the output once each in float32, the 1-byte weight
         codes and the float32 per-output-channel scales once each

That is the least a conv can move if it reads its input from HBM and writes
its output back.  A program that fuses two layers moves less, and needs
this count redone.  The dense head adds 2 · B · Cin · n_classes flops.
"""

from __future__ import annotations

ACT_BYTES = 4    # float32 activations
CODE_BYTES = 1   # packed log code per weight
SCALE_BYTES = 4  # float32 scale per output channel


def out_size(size: int, k: int, stride: int, padding: str) -> int:
    if padding == "SAME":
        return -(-size // stride)
    if padding == "VALID":
        return (size - k) // stride + 1
    raise ValueError(f"padding {padding!r} is not counted")


def conv_counts(rec: dict) -> dict:
    """``rec``: one launch record of `trace_conv_shapes` (B, H, W, C, K,
    Cout, stride, padding, groups) → ``{"flops", "bytes"}`` of that call."""
    B, H, W, C, K, Cout = (rec[k] for k in ("B", "H", "W", "C", "K", "Cout"))
    s, pad, g = rec["stride"], rec["padding"], rec["groups"]
    Ho, Wo = out_size(H, K, s, pad), out_size(W, K, s, pad)
    flops = 2 * B * Ho * Wo * Cout * K * K * (C // g)
    nbytes = (ACT_BYTES * (B * H * W * C + B * Ho * Wo * Cout)
              + CODE_BYTES * K * K * (C // g) * Cout + SCALE_BYTES * Cout)
    return {"flops": flops, "bytes": nbytes}


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def forward_counts(records: list[dict], head_in: int, n_classes: int,
                   peak: dict | None = None) -> dict:
    """Totals for one forward call at the records' batch.

    ``conv_least_s`` (with ``peak``) sums each conv's own roofline time."""
    batch = records[0]["B"]
    per = [conv_counts(r) for r in records]
    out = {"batch": batch, "n_convs": len(per),
           "conv_flops": sum(c["flops"] for c in per),
           "conv_bytes": sum(c["bytes"] for c in per),
           "head_flops": 2 * batch * head_in * n_classes}
    out["flops"] = out["conv_flops"] + out["head_flops"]
    if peak is not None:
        out["conv_least_s"] = sum(least_seconds(c["flops"], c["bytes"], peak)
                                  for c in per)
    return out


def program_conv_records(cfg: dict, batch: int) -> list[dict]:
    """The conv launches of the program's forward for a configuration, as
    the program's own shape walker reports them."""
    from repro.models.cnn import trace_conv_shapes
    return trace_conv_shapes(cfg["net"], batch=batch, img=cfg["image_size"],
                             n_classes=cfg["n_classes"],
                             cin=cfg["in_channels"],
                             width_mult=cfg["width_mult"])
