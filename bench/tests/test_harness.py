"""The harness as data: BENCHMARK.json names files that exist, every
per-layer metric has a reader and moves a metric its cells report, and the
command refuses to run without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_files(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in spec["configs"]]
    for c in spec["configs"]:
        assert NAME.match(c["name"])
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        assert conf["reduced"] == c["reduced"]
    cells = [w["name"] for w in spec["workloads"]]
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names
        assert len(w["why"]) <= 200
        for part in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.isfile(os.path.join(BENCH, *part) + ".json")
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert len(set(cells)) == len(cells)
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])


def test_every_cell_reports_setup_another_metric_and_a_layer(spec):
    for w in spec["workloads"]:
        mine = [m for m in spec["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        names = {m["name"] for m in mine}
        assert "setup_s" in names and len(names) >= 2
        layer = [m for m in spec["per_layer"] if w["name"] in m["workloads"]]
        assert layer
        assert all(m["moves"] in names for m in layer)


def test_every_per_layer_metric_has_a_reader(spec):
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]


def test_bounds_and_run_length(spec):
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    rs = spec["run_seconds"]
    assert 1 <= rs <= 51
    assert 2 + 14 * 24 * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "pruned-clip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
