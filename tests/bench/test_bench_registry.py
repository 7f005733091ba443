"""The benchmark is driven by data: every name in `BENCHMARK.json` resolves
to its file, and a cell, configuration, traffic mix or per-layer metric is
added with new files and entries alone."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

SPEC = run.load_spec()
E2E = {m["name"]: m for m in SPEC["end_to_end"]}


def _reports(cell: str) -> set[str]:
    return {m["name"] for m in SPEC["end_to_end"]
            if run.metric_applies(m, cell, set())}


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_to_its_files(cell):
    w = run.resolve(SPEC, cell)
    assert w["config"]["name"] == w["cell"]["config"]
    assert w["traffic"]["name"] == w["cell"]["traffic"]
    assert os.path.exists(os.path.join(ROOT, "bench", "reference",
                                       w["config"]["net"] + ".py"))
    names = {m["name"] for m in w["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert w["per_layer"], "every cell reports a per-layer metric"
    assert 0 < w["limits"]["max_rel_err"] < 1


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_resolves(metric):
    m = {x["name"]: x for x in SPEC["per_layer"]}[metric]
    path = os.path.join(ROOT, "bench", "metrics", metric + ".py")
    assert callable(run.load_reader(path))
    assert m["moves"] in E2E
    for cell in m["workloads"]:
        assert m["moves"] in _reports(cell), (metric, cell)


def test_configs_match_their_entries():
    for c in SPEC["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]


def test_new_cell_config_mix_and_metric_need_only_new_files(tmp_path):
    """Copy the benchmark, add one of each by files and entries alone, and
    resolve and read the new metric through the unchanged harness."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    spec = json.loads(json.dumps(SPEC))
    with open(root / "bench" / "configs" / "resnet34-224.json") as f:
        cfg = json.load(f)
    cfg["name"] = "resnet34-160"
    cfg["image_size"] = 160
    (root / "bench" / "configs" / "resnet34-160.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "offline-b8.json").write_text(json.dumps(
        {"name": "offline-b8", "batch": 8, "ring": 4, "host_io": False,
         "in_flight": 2, "warmup": 4, "trace_seconds": 1.0}))
    (root / "bench" / "limits" / "resnet34-160-offline-b8.json").write_text(
        json.dumps({"max_rel_err": 0.01}))
    (root / "bench" / "metrics" / "images_per_forward.py").write_text(
        "def read(ctx):\n    return ctx['images'] / ctx['forwards']\n")
    spec["configs"].append({"name": "resnet34-160",
                            "source": cfg["source"],
                            "file": "bench/configs/resnet34-160.json",
                            "reduced": ["image_size"], "why": "x"})
    spec["workloads"].append({"name": "resnet34-160-offline-b8",
                              "config": "resnet34-160",
                              "traffic": "offline-b8", "chips": 1,
                              "why": "x"})
    E = [m for m in spec["end_to_end"] if m["name"] == "images_per_s"][0]
    E["workloads"].append("resnet34-160-offline-b8")
    spec["per_layer"].append({"name": "images_per_forward", "unit": "images",
                              "better": "higher", "source": "host_clock",
                              "layer": "model step", "moves": "images_per_s",
                              "workloads": ["resnet34-160-offline-b8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    w = run.resolve(run.load_spec(str(root)), "resnet34-160-offline-b8",
                    root=str(root))
    assert w["config"]["image_size"] == 160
    assert w["traffic"]["batch"] == 8
    assert w["limits"] == {"max_rel_err": 0.01}
    assert {m["name"] for m in w["end_to_end"]} == {"images_per_s",
                                                     "setup_s"}
    [m] = [m for m in w["per_layer"] if m["name"] == "images_per_forward"]
    assert run.load_reader(m["file"])({"images": 24, "forwards": 3}) == 8
