"""Observability stack: span tracer, metrics registry, kernel-dispatch
profiler, and the end-to-end serving/training telemetry acceptance paths."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig, ShardedLoader
from repro.kernels import autotune, ops
from repro.models import transformer
from repro.obs import kernel_profile as kprof
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.runtime.monitor import HeartbeatMonitor
from repro.serving.engine import EngineConfig, Request, ServeEngine
from repro.training.optimizer import OptimizerConfig
from repro.training.train_loop import TrainConfig, train


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Each test starts with env gates unset, empty buffers, no overrides."""
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_PROFILE", raising=False)
    obs_trace.set_enabled(None)
    kprof.set_enabled(None)
    obs_trace.clear()
    kprof.clear()
    yield
    obs_trace.set_enabled(None)
    kprof.set_enabled(None)
    obs_trace.clear()
    kprof.clear()


def _small_model():
    cfg = get_config("gemma-2b").reduced(n_layers=2, vocab=64, d_model=16,
                                         d_ff=32, head_dim=8, n_heads=2)
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(0))


# ------------------------------------------------------------------- tracer


def test_tracer_disabled_is_shared_noop():
    assert not obs_trace.enabled()
    s1, s2 = obs_trace.span("a"), obs_trace.span("b", x=1)
    assert s1 is s2                       # one shared null span, no allocs
    with s1:
        pass
    obs_trace.instant("marker")
    obs_trace.add_complete("ext", 0, 100)
    assert obs_trace.events() == []


def test_tracer_env_gate_and_override(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "1")
    assert obs_trace.enabled()
    obs_trace.set_enabled(False)          # override beats env
    assert not obs_trace.enabled()
    obs_trace.set_enabled(None)           # defer back to env
    assert obs_trace.enabled()
    monkeypatch.setenv("REPRO_TRACE", "off")
    assert not obs_trace.enabled()


def test_tracer_ring_buffer_bounded():
    t = obs_trace.Tracer(capacity=4)
    t.set_enabled(True)
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    evs = t.events()
    assert len(evs) == 4
    assert [e[1] for e in evs] == ["s6", "s7", "s8", "s9"]  # keeps latest


def test_tracer_chrome_export_loadable(tmp_path):
    obs_trace.set_enabled(True)
    with obs_trace.span("work", uid=7) as sp:
        sp.set(tokens=3)
    obs_trace.instant("mark", note="x")
    path = tmp_path / "sub" / "trace.json"   # exercises makedirs
    obs_trace.export_chrome_trace(str(path))
    payload = json.loads(path.read_text())
    assert payload["displayTimeUnit"] == "ms"
    evs = payload["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    work = by_name["work"]
    assert work["ph"] == "X" and work["dur"] >= 0
    assert work["args"] == {"uid": 7, "tokens": 3}
    assert by_name["mark"]["ph"] == "i" and by_name["mark"]["s"] == "t"
    for e in evs:
        assert {"ts", "pid", "tid", "cat"} <= set(e)


def test_traced_decorator():
    calls = []

    @obs_trace.traced("fancy", kind="unit")
    def fn(x):
        calls.append(x)
        return x * 2

    assert fn(3) == 6                     # disabled: plain passthrough
    assert obs_trace.events() == []
    obs_trace.set_enabled(True)
    assert fn(4) == 8
    (ev,) = obs_trace.events()
    assert ev[1] == "fancy" and ev[5] == {"kind": "unit"}
    assert calls == [3, 4]


def test_tracer_span_lands_on_profiler_host_plane(tmp_path):
    """Under a JAX profiler session a span is also a `TraceAnnotation`, and
    so is a `time_program` call (the engine's ``decode``): both show on the
    xplane's host plane, the span with its args, and the ring buffer still
    records them."""
    from jax.profiler import ProfileData
    obs_trace.set_enabled(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("prefill", uid=3):
            jnp.ones(4).block_until_ready()
        kprof.time_program("decode", lambda: jnp.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    found = [(e.name, {k: v for k, v in e.stats})
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in ("prefill", "decode")]
    assert sorted(found) == [("decode", {}), ("prefill", {"uid": 3})]
    assert [e[1] for e in obs_trace.events()] == ["prefill", "decode"]


# ------------------------------------------------------------------ metrics


def test_log_bucket_bounds():
    b = obs_metrics.log_bucket_bounds(1e-3, 1.0, per_decade=3)
    assert b[0] == pytest.approx(1e-3)
    assert b[-1] >= 1.0
    assert all(x < y for x, y in zip(b, b[1:]))
    # constant ratio (geometric spacing)
    ratios = [y / x for x, y in zip(b, b[1:])]
    assert max(ratios) == pytest.approx(min(ratios))
    with pytest.raises(ValueError):
        obs_metrics.log_bucket_bounds(1.0, 0.5)


def test_counter_gauge():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("reqs", route="a")
    c.inc()
    c.inc(3)
    assert c.value == 4
    assert reg.counter("reqs", route="a") is c       # get-or-create
    assert reg.counter("reqs", route="b") is not c   # distinct labels
    g = reg.gauge("depth")
    g.set(5)
    g.inc(-2)
    assert g.value == 3


def test_histogram_percentiles_and_snapshot():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("lat_s")
    for v in (0.001, 0.002, 0.002, 0.003, 0.5):
        h.record(v)
    assert h.count == 5
    assert h.sum == pytest.approx(0.508)
    # bucket-resolution estimates stay clamped to observed min/max and
    # ordered across percentiles
    p50, p99 = h.percentile(50), h.percentile(99)
    assert 0.001 <= p50 <= 0.5
    assert p50 <= p99 <= 0.5
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["min"] == 0.001 and snap["max"] == 0.5
    assert snap["mean"] == pytest.approx(0.508 / 5)
    assert snap["buckets"][-1][0] == "+Inf"
    assert sum(c for _, c in snap["buckets"]) == 5
    assert snap["p50"] == pytest.approx(p50)
    # empty histogram is well-defined
    assert reg.histogram("empty").percentile(50) == 0.0


def test_registry_kind_collision():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_registry_snapshot_and_prometheus():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("hits", op="conv").inc(2)
    reg.gauge("depth").set(1.5)
    h = reg.histogram("lat", bounds=(0.1, 1.0))
    h.record(0.05)
    h.record(0.5)
    h.record(7.0)

    snap = reg.snapshot()
    assert snap["counters"] == {'hits{op="conv"}': 2}
    assert snap["gauges"] == {"depth": 1.5}
    assert snap["histograms"]["lat"]["count"] == 3

    text = reg.to_prometheus()
    assert "# TYPE hits counter" in text
    assert 'hits{op="conv"} 2' in text
    assert "# TYPE lat histogram" in text
    # cumulative buckets: ≤0.1 → 1, ≤1.0 → 2, +Inf → 3
    assert 'lat_bucket{le="0.1"} 1' in text
    assert 'lat_bucket{le="1.0"} 2' in text
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_sum 7.55" in text and "lat_count 3" in text


def test_registry_dump_json(tmp_path):
    reg = obs_metrics.MetricsRegistry()
    reg.counter("n").inc()
    path = tmp_path / "m.json"
    reg.dump_json(str(path))
    assert json.loads(path.read_text())["counters"]["n"] == 1


# ----------------------------------------------------------- kernel profiler


def test_profiler_disabled_passthrough():
    p = kprof.KernelProfiler()
    assert p.dispatch("op", "ref", "k", {}, lambda: 42, traced=False) == 42
    assert p.time_program("prog", lambda: jnp.ones(2)).shape == (2,)
    snap = p.snapshot()
    assert snap["records"] == [] and snap["programs"] == {}


def test_profiler_eager_first_vs_steady():
    p = kprof.KernelProfiler()
    p.set_enabled(True)
    fn = lambda: jnp.ones(4)
    for _ in range(3):
        p.dispatch("attention", "ref", "k1", {"total": 64}, fn, traced=False)
    (rec,) = p.snapshot()["records"]
    assert rec["calls"] == 3 and rec["traced_calls"] == 0
    assert rec["first_us"] is not None
    assert rec["steady_us"] is not None and rec["steady_source"] == "self"
    assert rec["steady_us_min"] <= rec["steady_us"]
    assert rec["bytes"]["total"] == 64


def test_profiler_traced_dispatch_inherits_program_time():
    kprof.set_enabled(True)
    obs_trace.set_enabled(True)
    q = jnp.ones((1, 8, 2, 4))
    kv = jnp.ones((1, 8, 2, 4))
    f = jax.jit(lambda q, k, v: ops.attention(q, k, v, impl="blockwise"))
    for _ in range(3):                    # 1 compile + 2 steady
        kprof.time_program("myprog", lambda: f(q, kv, kv))
    snap = kprof.snapshot()
    recs = [r for r in snap["records"] if r["op"] == "attention"]
    assert recs, "jit-traced attention dispatch must be recorded"
    rec = recs[0]
    assert rec["traced_calls"] >= 1       # staged once, cached afterwards
    assert rec["program"] == "myprog"
    # the program's time is not the kernel's: a traced row has no clock
    assert rec["steady_us"] is None and rec["steady_source"] is None
    assert rec["bytes"]["total"] > 0
    assert not any(e[1].startswith("trace:") for e in obs_trace.events())
    prog = snap["programs"]["myprog"]
    assert prog["calls"] == 3 and prog["first_us"] is not None
    assert prog["steady_us"] is not None


def test_profiler_eager_ops_dispatch_records():
    kprof.set_enabled(True)
    q = jnp.ones((1, 8, 2, 4))
    kv = jnp.ones((1, 8, 2, 4))
    for _ in range(2):
        ops.attention(q, kv, kv, impl="blockwise")
    recs = [r for r in kprof.snapshot()["records"]
            if r["op"] == "attention" and r["calls"] == 2]
    assert recs
    rec = recs[0]
    assert rec["impl"] == "blockwise"
    assert rec["key"].startswith("attention|")
    assert rec["bytes"]["total"] > 0
    assert rec["steady_source"] == "self"
    # dispatch also feeds the process-wide latency histogram
    h = obs_metrics.REGISTRY.histogram(
        "kernel_dispatch_us", bounds=obs_metrics.US_BUCKETS,
        op="attention", impl="blockwise", phase="steady")
    assert h.count >= 1


def test_autotune_lookup_hit_miss_counters(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_PATH", str(tmp_path / "tune.json"))
    monkeypatch.setattr(autotune, "PACKAGED_DIR", str(tmp_path / "pkg"))
    autotune.reset_cache()
    try:
        hit = obs_metrics.REGISTRY.counter("autotune_lookup",
                                           op="attention", result="hit_user")
        miss = obs_metrics.REGISTRY.counter("autotune_lookup",
                                            op="attention", result="miss")
        h0, m0 = hit.value, miss.value
        key = autotune.attention_key(1, 8, 8, 2, 2, 4, backend="interpret")
        assert autotune.lookup(key) is None
        assert (hit.value, miss.value) == (h0, m0 + 1)
        autotune.record(key, {"block_q": 8, "block_k": 8}, 1.0)
        assert autotune.lookup(key) == {"block_q": 8, "block_k": 8}
        assert (hit.value, miss.value) == (h0 + 1, m0 + 1)
    finally:
        autotune.reset_cache()            # drop the tmp table from cache


# ------------------------------------------------------- training telemetry


def test_train_step_histogram_feeds_monitor():
    cfg, params = _small_model()
    loss_fn = lambda p, b: transformer.lm_loss(p, b, cfg, xent_chunk=8)
    tcfg = TrainConfig(opt=OptimizerConfig(lr=1e-2, warmup_steps=0,
                                           schedule="constant",
                                           total_steps=10), log_every=2)
    ld = ShardedLoader(DataConfig(seq_len=8, global_batch=2, vocab=64,
                                  seed=0))
    reg = obs_metrics.MetricsRegistry()
    mon = HeartbeatMonitor(["host0"])
    train(loss_fn, params, ld, tcfg, num_steps=4,
          metrics=reg, monitor=mon, host="host0")
    hist = reg.snapshot()["histograms"]["train_step_s"]
    assert hist["count"] == 4 and hist["min"] > 0
    # monitor heartbeats come from the same per-step event stream
    rep = mon.report(step=3)
    assert not rep.missing
    assert mon._last_seen["host0"][1] == 3     # last recorded step
    # same event also lands in the tracer when it is on (train() donates
    # its state buffers, so the second run needs fresh params)
    obs_trace.set_enabled(True)
    params2 = transformer.init_params(cfg, jax.random.PRNGKey(0))
    train(loss_fn, params2, ld, tcfg, num_steps=2, metrics=reg, monitor=mon)
    steps = [e for e in obs_trace.events() if e[1] == "train_step"]
    assert len(steps) == 2


# -------------------------------------------- serving acceptance (ISSUE 8)


def test_engine_trace_acceptance(tmp_path, monkeypatch):
    """REPRO_TRACE=1 + a run over 8 mixed-length requests must yield a
    loadable Chrome trace with prefill/decode spans and a metrics snapshot
    with TTFT/tokens-per-s histograms plus per-op kernel records carrying
    impl, analytic bytes moved, and a steady-µs attribution."""
    monkeypatch.setenv("REPRO_TRACE", "1")
    cfg, params = _small_model()
    eng = ServeEngine(cfg, params, EngineConfig(max_batch=4, max_prompt=16,
                                                max_len=64))
    rng = np.random.default_rng(0)
    for uid in range(8):
        T = int(rng.integers(2, 13))
        eng.submit(Request(uid=uid,
                           prompt=rng.integers(1, cfg.vocab, size=T)
                           .astype(np.int32),
                           max_new_tokens=3 + uid % 4))
    done = eng.run()
    assert len(done) == 8

    # ---- Chrome trace: loadable, with the serving lifecycle spans
    path = tmp_path / "trace.json"
    obs_trace.export_chrome_trace(str(path))
    payload = json.loads(path.read_text())
    names = {e["name"] for e in payload["traceEvents"]}
    assert {"enqueue", "prefill", "decode", "retire"} <= names
    for e in payload["traceEvents"]:
        if e["ph"] == "X":
            assert e["dur"] >= 0

    # ---- request timelines are causally ordered
    for r in done:
        tl = r.timeline
        assert tl["enqueue"] <= tl["prefill_start"] <= tl["first_token"] \
            <= tl["retire"]

    # ---- engine metrics: one TTFT and one tokens/s sample per request
    snap = eng.metrics_snapshot()
    hists = snap["engine"]["histograms"]
    assert hists["serve_ttft_s"]["count"] == 8
    assert hists["serve_tokens_per_s"]["count"] == 8
    assert hists["serve_prefill_s"]["count"] == 8
    assert snap["engine"]["counters"]["serve_requests_retired"] == 8
    assert snap["stats"]["prefill_calls"] == 8

    # ---- kernel records: every dispatched op carries impl/bytes; only a
    # self-timed row has a steady time, a row staged in a jit names its
    # program instead
    recs = snap["kernels"]["records"]
    assert recs, "engine run must record kernel dispatches"
    for r in recs:
        assert r["impl"]
        assert r["bytes"]["total"] > 0
        if r["steady_source"] == "self":
            assert r["steady_us"] is not None, r
        else:
            assert r["steady_source"] is None and r["steady_us"] is None, r
            assert r["traced_calls"] >= 1 and r["program"], r
    progs = snap["kernels"]["programs"]
    assert {"prefill", "decode"} <= set(progs)
    assert progs["decode"]["steady_us"] is not None


def test_engine_telemetry_off_records_nothing():
    obs_trace.set_enabled(True)           # tracer on, engine forced off
    cfg, params = _small_model()
    eng = ServeEngine(cfg, params, EngineConfig(max_batch=2, max_prompt=16,
                                                max_len=32, telemetry="off"))
    eng.submit(Request(uid=0, prompt=np.array([1, 2, 3], np.int32),
                       max_new_tokens=3))
    done = eng.run()
    assert done[0].timeline == {}
    snap = eng.metrics_snapshot()
    assert snap["engine"]["histograms"]["serve_ttft_s"]["count"] == 0
    assert {e[1] for e in obs_trace.events()}.isdisjoint(
        {"enqueue", "prefill", "retire"})
    assert eng.stats["prefill_calls"] == 1    # compat counters always on


def test_engine_rejects_bad_telemetry_mode():
    cfg, params = _small_model()
    with pytest.raises(ValueError, match="telemetry"):
        ServeEngine(cfg, params, EngineConfig(telemetry="sometimes"))
