"""``BENCHMARK.json`` against its own rules, and a cell, configuration,
traffic mix and metric added as new files and entries only."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((bench.ROOT / "BENCHMARK.json").read_text())


def test_names_units_and_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        cfg = json.loads((bench.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in names
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_piece_of_a_cell_is_found(cell):
    c = bench.load_cell(cell)
    assert c.bench_path("drivers", f"{c.config['driver']}.py").exists()
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        reader = bench.load_module(c.bench_path("metrics",
                                                f"{m['name']}.py"))
        assert callable(reader.read)


def test_no_chip_no_result():
    """On the CPU the command exits non-zero and prints no result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload",
         SPEC["workloads"][0]["name"], "--seed", str(2 ** 33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=bench.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


EXTRA_METRIC = '''
def read(run):
    return len(run.requests) / run.window_s if run.requests else None
'''


def test_a_cell_added_by_new_files_only(tmp_path):
    """A new configuration, traffic mix, cell and metric come as new files
    and new entries, and run through the unchanged harness."""
    root = tmp_path
    bench_dir = root / bench.BENCH_DIR
    shutil.copytree(bench.HERE, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    cfg = json.loads((bench.ROOT / spec["configs"][0]["file"]).read_text())
    cfg.update(name="ring4_bbs",
               fabric={**cfg["fabric"], "constructor": "ring", "args": [4]})
    (bench_dir / "configs" / "ring4_bbs.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "ring4_root3_64k.json").write_text(json.dumps(
        {"message_bytes": 1 << 16, "root": 3, "loop": "closed",
         "outstanding": 1, "fresh_payload": True, "check_sample": 2}))
    (bench_dir / "metrics" / "requests_per_s.py").write_text(EXTRA_METRIC)
    spec["configs"].append({"name": "ring4_bbs", "source": "test",
                            "file": f"{bench.BENCH_DIR}/configs/ring4_bbs.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "ring4_root3", "config": "ring4_bbs",
                              "traffic": "ring4_root3_64k", "chips": 4,
                              "why": "test"})
    spec["end_to_end"].append({"name": "requests_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["ring4_root3"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    res = bench.run_cell("ring4_root3", 7, 0.3, False, root=root,
                         require_tpu=False)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"requests_per_s", "setup_s"}
    assert res["metrics"]["requests_per_s"]["value"] > 0
