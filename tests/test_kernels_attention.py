"""GQA-native flash_attention Pallas kernel + blockwise jnp vs full-softmax
oracle, and the redesigned `ops.attention` call surface (config=, legacy
kwarg deprecation, shape validation, traced decode offsets)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import AttentionConfig, attention, resolve_impl
from repro.kernels.ref import ref_attention


def _mk(b, tq, tk, h, hkv, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, tq, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, tk, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, tk, hkv, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,hkv,d,window", [
    (1, 128, 4, 4, 64, None),     # MHA causal
    (2, 256, 8, 2, 64, None),     # GQA
    (1, 256, 4, 1, 64, 64),       # MQA + sliding window (gemma3 local)
    (1, 130, 4, 2, 64, None),     # ragged T
])
def test_pallas_attention_matches_ref(b, t, h, hkv, d, window, dtype):
    q, k, v = _mk(b, t, t, h, hkv, d, dtype)
    got = attention(q, k, v, causal=True, window=window, impl="pallas",
                    interpret=True)
    want = ref_attention(q, k, v, causal=True, window=window)
    rtol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=rtol)


# --------------------------------------------------------------------------
# GQA/MQA sweep: every impl agrees, static and dynamic (traced) offsets
# --------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 16])
@pytest.mark.parametrize("hkv", [1, 2, 8])   # MQA, H/4 GQA, MHA (H = 8)
def test_gqa_pallas_blockwise_ref_agree(hkv, window):
    h, t, d = 8, 48, 16
    q, k, v = _mk(1, t, t, h, hkv, d, jnp.float32, seed=7)
    cfg = AttentionConfig(block_q=16, block_k=16)
    want = ref_attention(q, k, v, causal=True, window=window)
    got_p = attention(q, k, v, causal=True, window=window, impl="pallas",
                      config=cfg, interpret=True)
    got_b = attention(q, k, v, causal=True, window=window, impl="blockwise",
                      config=cfg)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_b), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("hkv", [1, 2, 8])
def test_gqa_dynamic_decode_offset_on_pallas(hkv):
    """Traced q_offset (decode at a dynamic cache index) runs on the Pallas
    impl — no blockwise fallback — and matches the full-prefill row."""
    h, t, d = 8, 64, 16
    q, k, v = _mk(1, t, t, h, hkv, d, jnp.float32, seed=8)
    full = ref_attention(q, k, v, causal=True)

    @jax.jit
    def decode(q1, k, v, off):
        return attention(q1, k, v, causal=True, q_offset=off, impl="pallas",
                         interpret=True,
                         config=AttentionConfig(block_q=8, block_k=16))

    got = decode(q[:, -1:], k, v, jnp.asarray(t - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_ring_k_offset_pallas_matches_blockwise():
    """Ring-buffer decode: traced k_offset masks never-written slots
    (absolute position < 0) identically on pallas and blockwise."""
    h, hkv, s, d = 4, 2, 32, 16
    q, k, v = _mk(1, 1, s, h, hkv, d, jnp.float32, seed=9)

    @jax.jit
    def ring(q, k, v, q_off, k_off):
        kw = dict(causal=True, window=8, q_offset=q_off, k_offset=k_off)
        a = attention(q, k, v, impl="pallas", interpret=True,
                      config=AttentionConfig(block_q=8, block_k=8), **kw)
        b = attention(q, k, v, impl="blockwise",
                      config=AttentionConfig(block_k=8), **kw)
        return a, b

    # k[0] sits at absolute position -9: the first 9 slots are unwritten
    a, b = ring(q, k, v, jnp.asarray(22, jnp.int32),
                jnp.asarray(-9, jnp.int32))
    want = ref_attention(q, k, v, causal=True, window=8, q_offset=22,
                         k_offset=-9)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(b), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# call-surface redesign: validation, deprecation, impl resolution
# --------------------------------------------------------------------------


def test_head_mismatch_raises_clear_valueerror():
    q, k, v = _mk(1, 8, 8, 6, 4, 16, jnp.float32)
    with pytest.raises(ValueError, match="H=6 query heads vs Hkv=4"):
        attention(q, k, v)
    with pytest.raises(ValueError, match="inconsistent attention operands"):
        attention(q, k[:, :4], v)


def test_legacy_kwargs_deprecated_but_equivalent():
    q, k, v = _mk(1, 32, 32, 4, 2, 16, jnp.float32, seed=3)
    with pytest.warns(DeprecationWarning, match="AttentionConfig"):
        old = attention(q, k, v, impl="blockwise", block_k=8,
                        gqa_broadcast=True)
    new = attention(q, k, v, impl="blockwise",
                    config=AttentionConfig(block_k=8, gqa_broadcast=True))
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
    with pytest.raises(ValueError, match="not both"):
        attention(q, k, v, impl="blockwise", block_k=8,
                  config=AttentionConfig(block_k=8))


def test_resolve_impl_precedence():
    # off-TPU (CI): auto → blockwise, interpret default → True
    assert resolve_impl("attention") == ("blockwise", True)
    assert resolve_impl("attention", "pallas") == ("pallas", True)
    assert resolve_impl("attention", "pallas", False) == ("pallas", False)
    for op in ("log_matmul", "conv2d", "attention", "wkv6"):
        with pytest.raises(ValueError, match="unknown"):
            resolve_impl(op, "nope")
    # no op takes an explicit-im2col impl
    for op in ("conv2d", "attention"):
        with pytest.raises(ValueError, match="unknown"):
            resolve_impl(op, "pallas_im2col")


# --------------------------------------------------------------------------
# legacy blockwise coverage (unchanged semantics)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 32])
def test_blockwise_attention_matches_ref(window):
    q, k, v = _mk(2, 96, 96, 4, 2, 32, jnp.float32, seed=2)
    got = attention(q, k, v, causal=True, window=window, impl="blockwise",
                    config=AttentionConfig(block_k=32))
    want = ref_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_decode_q_offset():
    """Single-token decode: q at position Tk-1 must equal full-prefill row."""
    b, t, h, d = 1, 64, 4, 32
    q, k, v = _mk(b, t, t, h, h, d, jnp.float32, seed=3)
    full = ref_attention(q, k, v, causal=True)
    last = attention(q[:, -1:], k, v, causal=True, q_offset=t - 1,
                     impl="blockwise", config=AttentionConfig(block_k=16))
    np.testing.assert_allclose(np.asarray(last[:, 0]), np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-4)
    last_p = attention(q[:, -1:], k, v, causal=True, q_offset=t - 1,
                       impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(last_p[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)


def test_window_equals_full_when_large():
    q, k, v = _mk(1, 64, 64, 2, 2, 32, jnp.float32, seed=4)
    a = attention(q, k, v, causal=True, window=4096, impl="blockwise")
    b_ = attention(q, k, v, causal=True, window=None, impl="blockwise")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-5)
