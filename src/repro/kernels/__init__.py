"""Pallas TPU kernels for the perf-critical hot spots + pure-jnp oracles.

log_matmul       decode 6-bit log codes in VMEM → MXU dot (NeuroMAX PE path)
log_conv2d       NHWC conv against packed HWIO log codes: fused
                 implicit-im2col kernel (VMEM patch extraction, grouped-conv
                 grid, in-kernel lane packing), blockwise and ref fallbacks
autotune         per-layer block-size search + op-keyed on-disk tuning
                 table (conv2d and attention namespaces)
flash_attention  blockwise online-softmax attention, GQA-native (kv-head
                 grid dimension, causal / window, traced decode offsets)
wkv6             chunked RWKV6 WKV scan with data-dependent decay

Every op is exposed through `ops` with the unified dispatch surface —
``impl="pallas|blockwise|ref|auto"``, ``config=`` per-op spec
dataclasses (`AttentionConfig`, `ConvConfig`, `WkvConfig`) and
``interpret=``; `ops.resolve_impl` documents the precedence order.
"""
from . import ops, ref
from .ops import (AttentionConfig, ConvConfig, WkvConfig, attention, conv2d,
                  log_matmul, resolve_impl, wkv6)
