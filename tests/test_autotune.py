"""The op-keyed block-size autotuner: layered table resolution (user
tier over the packaged warm-start tier), persistence (including the
concurrent-writer merge), keying (conv2d and attention namespaces),
invalidation, candidate filtering, and numerics of tuned configs."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops
from repro.core.logquant import LogQuantConfig, quantize_tensor
from repro.obs import metrics as obs_metrics

SHAPE = dict(B=1, H=8, W=8, C=5, K=3, Cout=7)
ARGS = (1, 8, 8, 5, 3, 7)

REAL_PACKAGED_DIR = autotune.PACKAGED_DIR  # before the fixture repoints it


@pytest.fixture(autouse=True)
def _isolated_table(tmp_path, monkeypatch):
    """Every test gets its own on-disk user table AND an empty packaged
    tier; caches are reset so nothing leaks between tests (or into the
    user's real cache dir / the checked-in warm-start tables)."""
    monkeypatch.setenv("REPRO_AUTOTUNE_PATH", str(tmp_path / "table.json"))
    monkeypatch.setattr(autotune, "PACKAGED_DIR",
                        str(tmp_path / "packaged"))
    autotune.reset_cache()
    yield
    autotune.reset_cache()


def _write_packaged(backend: str, entries: dict) -> str:
    os.makedirs(autotune.PACKAGED_DIR, exist_ok=True)
    path = autotune.packaged_table_path(backend)
    with open(path, "w") as f:
        json.dump({"version": autotune.SCHEMA_VERSION, "entries": entries},
                  f)
    return path


def _lookup_counts(op="conv2d") -> dict:
    out = {"hit_user": 0, "hit_warm": 0, "miss": 0}
    for r in out:
        out[r] = obs_metrics.REGISTRY.counter("autotune_lookup", op=op,
                                              result=r).value
    return out


def test_key_carries_shape_stride_groups_backend():
    k1 = autotune.conv_key(*ARGS, backend="cpu")
    assert autotune.conv_key(*ARGS, backend="cpu") == k1  # deterministic
    for other in (autotune.conv_key(1, 8, 8, 5, 3, 9, backend="cpu"),
                  autotune.conv_key(*ARGS, stride=2, backend="cpu"),
                  autotune.conv_key(*ARGS, padding="VALID", backend="cpu"),
                  autotune.conv_key(*ARGS, backend="tpu"),
                  autotune.conv_key(*ARGS, cfg=LogQuantConfig(bits=4),
                                    backend="cpu")):
        assert other != k1


def test_record_lookup_roundtrip_persists():
    key = autotune.conv_key(*ARGS, backend="cpu")
    cfg = dict(block_cin=4, block_cout=8, rows_per_tile=4, batch_per_tile=1)
    autotune.record(key, cfg, 12.5)
    assert autotune.lookup(key) == cfg
    autotune.reset_cache()          # force re-read from disk
    assert autotune.lookup(key) == cfg
    table = json.load(open(autotune.table_path()))
    assert table["version"] == autotune.SCHEMA_VERSION
    assert table["entries"][key]["us"] == 12.5


def test_stale_schema_version_invalidates_table():
    key = autotune.conv_key(*ARGS, backend="cpu")
    autotune.record(key, dict(block_cin=4), 1.0)
    autotune.reset_cache()
    path = autotune.table_path()
    table = json.load(open(path))
    table["version"] = autotune.SCHEMA_VERSION - 1
    json.dump(table, open(path, "w"))
    assert autotune.lookup(key) is None  # stale entries are not served


def test_corrupt_table_is_ignored():
    with open(autotune.table_path(), "w") as f:
        f.write("{not json")
    assert autotune.lookup("anything") is None
    autotune.record("k", dict(block_cin=4), 1.0)  # and is recoverable
    autotune.reset_cache()
    assert autotune.lookup("k") == dict(block_cin=4)


# ------------------------------------------------- layered warm-start tier


def test_layered_lookup_precedence_and_counter_labels():
    """User tier (env path / user cache) shadows the packaged tier; each
    resolution increments its own `autotune_lookup` result label."""
    key = autotune.conv_key(*ARGS, backend="cpu")
    c0 = _lookup_counts()
    assert autotune.lookup(key) is None                    # nothing anywhere
    _write_packaged("cpu", {key: {"config": dict(block_cin=8), "us": 1.0}})
    autotune.reset_cache()
    assert autotune.lookup(key) == dict(block_cin=8)       # packaged tier
    autotune.record(key, dict(block_cin=4), 2.0)
    assert autotune.lookup(key) == dict(block_cin=4)       # user tier wins
    c1 = _lookup_counts()
    assert {r: c1[r] - c0[r] for r in c1} == \
        {"miss": 1, "hit_warm": 1, "hit_user": 1}


def test_packaged_tier_keyed_per_backend():
    key_cpu = autotune.conv_key(*ARGS, backend="cpu")
    key_tpu = autotune.conv_key(*ARGS, backend="tpu")
    _write_packaged("cpu", {key_cpu: {"config": dict(block_cin=8),
                                      "us": 1.0}})
    assert autotune.lookup(key_cpu) == dict(block_cin=8)
    assert autotune.lookup(key_tpu) is None  # no tpu.json → miss, no error


def test_env_path_overrides_user_cache(tmp_path, monkeypatch):
    """$REPRO_AUTOTUNE_PATH beats ~/.cache/repro/… — both are the user
    tier, the env var just repoints it."""
    key = autotune.conv_key(*ARGS, backend="cpu")
    monkeypatch.delenv("REPRO_AUTOTUNE_PATH")
    monkeypatch.setenv("HOME", str(tmp_path / "home"))
    autotune.reset_cache()
    assert autotune.table_path() == str(
        tmp_path / "home" / ".cache" / "repro" / "kernel_autotune.json")
    autotune.record(key, dict(block_cin=2), 1.0)           # lands in ~/.cache
    autotune.reset_cache()
    assert autotune.lookup(key) == dict(block_cin=2)
    monkeypatch.setenv("REPRO_AUTOTUNE_PATH",
                       str(tmp_path / "env_table.json"))
    autotune.reset_cache()
    assert autotune.lookup(key) is None                    # env tier shadows
    autotune.record(key, dict(block_cin=16), 1.0)
    autotune.reset_cache()
    assert autotune.lookup(key) == dict(block_cin=16)


def test_record_never_writes_packaged_tier():
    key = autotune.conv_key(*ARGS, backend="cpu")
    path = _write_packaged("cpu", {key: {"config": dict(block_cin=8),
                                         "us": 1.0}})
    before = open(path).read()
    autotune.record(key, dict(block_cin=4), 2.0)
    assert open(path).read() == before                 # packaged: read-only
    user = json.load(open(autotune.table_path()))
    assert user["entries"][key]["config"] == dict(block_cin=4)


def test_stale_packaged_schema_is_ignored():
    key = autotune.conv_key(*ARGS, backend="cpu")
    os.makedirs(autotune.PACKAGED_DIR, exist_ok=True)
    with open(autotune.packaged_table_path("cpu"), "w") as f:
        json.dump({"version": autotune.SCHEMA_VERSION - 1,
                   "entries": {key: {"config": dict(block_cin=8)}}}, f)
    assert autotune.lookup(key) is None


def test_checked_in_tables_cover_the_zoo(monkeypatch):
    """The real packaged tier resolves every conv dispatch of the four
    paper CNNs (the cold-start acceptance, on one network for speed)."""
    from repro.models.cnn import trace_conv_shapes
    monkeypatch.setattr(autotune, "PACKAGED_DIR", REAL_PACKAGED_DIR)
    autotune.reset_cache()
    shapes = trace_conv_shapes("mobilenet_v1")             # has dw + pw
    assert len(shapes) == 27
    entries = autotune._load_packaged("interpret")
    assert entries, "packaged interpret.json missing or stale schema"
    for s in shapes:
        key = autotune.conv_key(s["B"], s["H"], s["W"], s["C"], s["K"],
                                s["Cout"], stride=s["stride"],
                                padding=s["padding"], groups=s["groups"],
                                backend="interpret")
        assert key in entries, f"warm tier misses {key}"
        assert autotune.lookup(key) == entries[key]["config"]


# --------------------------------------------------- concurrent-writer merge


def test_record_merges_concurrent_writers():
    """Two processes tuning different layers interleave: A and B both
    snapshot an empty table; A lands its entry; B's record() must re-read
    and merge, not clobber A's entry with its own stale snapshot."""
    key_a = autotune.conv_key(*ARGS, backend="cpu")
    key_b = autotune.attention_key(1, 1, 4096, 8, 2, 64, backend="cpu")
    autotune._load()              # process B's in-memory snapshot: empty
    # process A (simulated externally) lands its entry on disk
    with open(autotune.table_path(), "w") as f:
        json.dump({"version": autotune.SCHEMA_VERSION,
                   "entries": {key_a: {"config": dict(block_cin=8),
                                       "us": 5.0}}}, f)
    autotune.record(key_b, dict(block_q=8, block_k=256), 7.0)  # process B
    disk = json.load(open(autotune.table_path()))
    assert disk["entries"][key_a]["config"] == dict(block_cin=8)  # survived
    assert disk["entries"][key_b]["config"] == dict(block_q=8, block_k=256)
    # and the reverse conflict: B's own fresh measurement wins its key
    autotune.record(key_a, dict(block_cin=4), 1.0)
    disk = json.load(open(autotune.table_path()))
    assert disk["entries"][key_a]["config"] == dict(block_cin=4)
    assert disk["entries"][key_b]["config"] == dict(block_q=8, block_k=256)


# ------------------------------------------------------------ reps validation


def test_autotune_reps_zero_raises():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 4, 4, 2)).astype(np.float32))
    qt = quantize_tensor(jnp.asarray(
        rng.normal(size=(3, 3, 2, 4)).astype(np.float32)))
    with pytest.raises(ValueError, match="reps >= 1"):
        autotune.autotune_conv2d(x, qt.packed, qt.scale, qt.cfg,
                                 interpret=True, reps=0)
    q = jnp.asarray(rng.normal(size=(1, 4, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 4, 2, 8)), jnp.float32)
    with pytest.raises(ValueError, match="reps >= 1"):
        autotune.autotune_attention(q, k, k, interpret=True, reps=-1)


# ------------------------------------------------- partial-config dispatch


def test_partial_conv_config_fills_from_table(monkeypatch):
    """`ops.conv2d` with only some `ConvConfig` fields set fills the rest
    per-field from the table — the documented contract a partial config
    used to silently bypass."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, 8, 8, 5)).astype(np.float32))
    qt = quantize_tensor(jnp.asarray(
        rng.normal(size=(3, 3, 5, 7)).astype(np.float32)))
    key = autotune.conv_key(*ARGS, cfg=qt.cfg, backend="interpret")
    autotune.record(key, dict(block_cin=4, block_cout=8, rows_per_tile=2,
                              batch_per_tile=1, lane_pack=1), 9.0)
    seen = {}
    real = ops.log_conv2d_fused_pallas

    def spy(*a, **kw):
        seen.update(kw)
        return real(*a, **kw)

    monkeypatch.setattr(ops, "log_conv2d_fused_pallas", spy)
    y = ops.conv2d(x, qt, impl="pallas", interpret=True,
                   config=ops.ConvConfig(rows_per_tile=4))
    assert seen["rows_per_tile"] == 4          # explicit field kept
    assert seen["block_cin"] == 4              # … the rest from the table
    assert seen["block_cout"] == 8
    assert seen["batch_per_tile"] == 1
    y_ref = ops.conv2d(x, qt, impl="ref")
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref),
        atol=1e-4 * float(jnp.max(jnp.abs(y_ref)) + 1))
    # a fully-pinned config consults no table at all
    c0 = _lookup_counts()
    seen.clear()
    ops.conv2d(x, qt, impl="pallas", interpret=True,
               config=dict(block_cin=8, block_cout=8, rows_per_tile=4,
                           batch_per_tile=1, lane_pack=1))
    assert _lookup_counts() == c0
    assert seen["block_cin"] == 8


def test_candidates_fit_vmem_budget_and_dedupe():
    cands = autotune.candidate_configs(*ARGS)
    assert cands, "no candidates for a tiny layer"
    seen = set()
    for c in cands:
        assert autotune.estimate_vmem_bytes(
            *ARGS, **c) <= autotune.VMEM_BUDGET_BYTES
        sig = tuple(sorted(c.items(), key=str))
        assert sig not in seen
        seen.add(sig)


def test_key_namespaces_distinct_per_op():
    ck = autotune.conv_key(*ARGS, backend="cpu")
    ak = autotune.attention_key(1, 8, 8, 5, 1, 7, backend="cpu")
    assert ck.startswith("conv2d|") and ak.startswith("attention|")
    assert ck != ak


def test_attention_key_carries_shape_mask_backend():
    args = (2, 16, 128, 8, 2, 64)
    k1 = autotune.attention_key(*args, backend="cpu")
    assert autotune.attention_key(*args, backend="cpu") == k1
    for other in (autotune.attention_key(2, 16, 128, 8, 4, 64,
                                         backend="cpu"),
                  autotune.attention_key(2, 16, 256, 8, 2, 64,
                                         backend="cpu"),
                  autotune.attention_key(*args, causal=False, backend="cpu"),
                  autotune.attention_key(*args, window=64, backend="cpu"),
                  autotune.attention_key(*args, backend="tpu")):
        assert other != k1


def test_attention_record_lookup_roundtrip_persists():
    key = autotune.attention_key(1, 1, 4096, 8, 2, 64, backend="interpret")
    cfg = dict(block_q=8, block_k=256)
    autotune.record(key, cfg, 42.0)
    assert autotune.lookup(key) == cfg
    autotune.reset_cache()          # force re-read from disk
    assert autotune.lookup(key) == cfg
    table = json.load(open(autotune.table_path()))
    assert table["version"] == autotune.SCHEMA_VERSION
    # conv entries coexist in the same table file
    ck = autotune.conv_key(*ARGS, backend="cpu")
    autotune.record(ck, dict(block_cin=4), 1.0)
    assert autotune.lookup(key) == cfg and autotune.lookup(ck) is not None


def test_attention_candidates_fit_vmem_budget_and_dedupe():
    args = (1, 1, 4096, 8, 2, 64)
    cands = autotune.attention_candidate_configs(*args)
    assert cands
    seen = set()
    for c in cands:
        assert autotune.estimate_attention_vmem_bytes(
            *args, **c) <= autotune.VMEM_BUDGET_BYTES
        sig = (c["block_q"], c["block_k"])
        assert sig not in seen
        seen.add(sig)
    # decode shape: folded rep·Tq rows keep block_q small
    assert all(c["block_q"] <= 8 for c in cands)


def test_autotune_attention_persists_winner_and_is_picked_up():
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(1, 16, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 16, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 16, 2, 16)), jnp.float32)
    winner = autotune.autotune_attention(q, k, v, interpret=True, reps=1,
                                         max_candidates=2)
    key = autotune.attention_key(1, 16, 16, 4, 2, 16, backend="interpret")
    assert autotune.lookup(key) == winner
    # subsequent plain pallas calls pick the persisted winner up
    y = ops.attention(q, k, v, impl="pallas", interpret=True)
    from repro.kernels.ref import ref_attention
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref_attention(q, k, v)),
                               rtol=2e-4, atol=2e-4)


def test_autotune_persists_winner_and_matches_ref():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1, 8, 8, 5)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 5, 7)).astype(np.float32))
    qt = quantize_tensor(w)
    winner = autotune.autotune_conv2d(x, qt.packed, qt.scale, qt.cfg,
                                      interpret=True, reps=1)
    key = autotune.conv_key(*ARGS, cfg=qt.cfg, backend="interpret")
    assert autotune.lookup(key) == winner
    # subsequent plain calls pick the persisted winner up: a user-tier
    # hit, the same launch as the winner's config pinned, and the same
    # conv as the oracle
    hits = obs_metrics.REGISTRY.counter("autotune_lookup", op="conv2d",
                                        result="hit_user")
    before = hits.value
    y = ops.conv2d(x, qt, impl="pallas", interpret=True)
    assert hits.value == before + 1
    y_pinned = ops.conv2d(x, qt, impl="pallas", interpret=True,
                          config=winner)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_pinned))
    y_ref = ops.conv2d(x, qt, impl="ref")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4 * float(jnp.max(jnp.abs(y_ref)) + 1))
