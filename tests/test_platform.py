"""What the program does about the device it runs on: dispatch that never
hides a backend failure behind a CPU fallback, peak rates that exist only
for chips in the table, and a compile cache that can be placed from
outside."""

import os
import subprocess
import sys

import jax
import pytest

from repro.analysis import report, roofline
from repro.kernels import ops
from repro.runtime import compile_cache

SRC = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    compile_cache.__file__))))


def test_resolve_impl_raises_on_backend_error(monkeypatch):
    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="initialize backend"):
        ops.resolve_impl("conv2d")
    with pytest.raises(RuntimeError, match="initialize backend"):
        ops.resolve_impl("attention", "pallas")   # interpret=None asks too
    # an explicit impl and interpret bool ask nothing of the backend
    assert ops.resolve_impl("conv2d", "pallas", False) == ("pallas", False)


def test_device_peaks_table():
    v5e = roofline.device_peaks("TPU v5 lite")
    assert v5e == {"bf16_flops": 197e12, "int8_ops": 393e12,
                   "hbm_bytes_per_s": 819e9}
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError, match="no published peaks"):
            roofline.device_peaks(kind)


def _snap(platform, kind, steady_us=10.0):
    rec = {"op": "conv2d", "impl": "pallas", "key": "k", "calls": 2,
           "bytes": {"total": 819_000}, "steady_us": steady_us}
    return {"kernels": {"records": [rec], "programs": {},
                        "device": {"platform": platform, "kind": kind,
                                   "count": 1}}}


def test_report_roofline_follows_snapshot_device():
    # 819 kB in 10 µs = 81.9 GB/s = 10% of a v5e's HBM bandwidth
    assert "| 10.00% |" in report.per_op_utilization_table(
        _snap("tpu", "TPU v5 lite"))
    assert "— (cpu)" in report.per_op_utilization_table(_snap("cpu", "cpu"))
    with pytest.raises(KeyError, match="no published peaks"):
        report.per_op_utilization_table(_snap("tpu", "TPU v9 imaginary"))


def test_profiler_snapshot_names_its_device():
    from repro.obs import kernel_profile
    dev = kernel_profile.snapshot()["device"]
    assert dev == {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}


def test_roofline_needs_a_device_kind():
    rec = {"arch": "a", "shape": "s", "mesh": "single", "chips": 256,
           "cost_true": {"flops": 197e12, "bytes": 819e9,
                         "collective_bytes": 0.0},
           "model_flops": 1.0, "memory": {"temp_bytes": 0,
                                          "argument_bytes": 0}}
    with pytest.raises(KeyError):
        roofline.from_record(rec)
    r = roofline.from_record(dict(rec, device_kind="TPU v5 lite"))
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(1.0)


_PROBE = """
from repro.runtime.compile_cache import setup_compile_cache
import jax, jax.numpy as jnp
print(setup_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) @ x.T).lower(jnp.ones((64, 64))).compile()
"""


def _probe(env_extra: dict) -> list[str]:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0", **env_extra)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return out.stdout.split()


def test_compile_cache_dir_from_env_lands_files_there(tmp_path):
    where = str(tmp_path / "cache")
    helper_dir, config_dir = _probe({"JAX_COMPILATION_CACHE_DIR": where})
    assert helper_dir == config_dir == where
    assert os.listdir(where), "no cache entry was written"


def test_compile_cache_defaults_to_checkout():
    checkout = os.path.dirname(SRC)
    assert compile_cache.DEFAULT_DIR == os.path.join(checkout, ".jax_cache")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.runtime.compile_cache import setup_compile_cache\n"
         "import jax\nsetup_compile_cache()\n"
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    assert out.stdout.split() == [compile_cache.DEFAULT_DIR]
