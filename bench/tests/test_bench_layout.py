"""``BENCHMARK.json`` and the files it names: found by name, no code edit;
and ``bench/run.py`` refuses to run without a TPU or without the program."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_entry_has_its_file_and_a_valid_name():
    b = _bench()
    configs = {c["name"] for c in b["configs"]}
    metrics = b["end_to_end"] + b["per_layer"]
    for entry in b["configs"] + b["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for c in b["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert all(k in cfg for k in c["reduced"])
    for w in b["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for m in b["per_layer"]:
        assert callable(run.reader(m["name"]))
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    for m in metrics:
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in b["workloads"]}
    assert {"setup_s"} <= {e["name"] for e in b["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_each_cell_reports_what_its_per_layer_metrics_move(cell):
    spec = run.cell_spec(cell)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and spec["per_layer"]
    assert all(m["moves"] in e2e for m in spec["per_layer"])


def test_a_new_cell_traffic_and_metric_need_only_new_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = _bench()
    b["workloads"].append({"name": "lubm100-adhash.tiny-open",
                           "config": "tiny", "traffic": "tiny-open",
                           "chips": 1, "why": "test"})
    b["configs"].append({"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"})
    b["per_layer"].append({"name": "answered_count", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "front end", "moves": "p95_ms",
                           "workloads": ["lubm100-adhash.tiny-open"]})
    for m in b["end_to_end"]:
        if "workloads" in m and m["name"] != "qps":
            m["workloads"].append("lubm100-adhash.tiny-open")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cfg = json.loads((ROOT / "bench/configs/lubm100-adhash.json").read_text())
    cfg.update(name="tiny", n_universities=2)
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    traffic = {"loop": "open", "rate_qps": 5.0, "queries": "lubm",
               "mix": {"q1": 1.0}, "block": 1, "serve": {}}
    (tmp_path / "bench/traffic/tiny-open.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/metrics/answered_count.py").write_text(
        "def read(record):\n"
        "    return sum(r.status == 'answered' "
        "for r in record['requests'])\n")
    spec = run.cell_spec("lubm100-adhash.tiny-open", root=tmp_path)
    assert spec["config"]["n_universities"] == 2
    assert spec["traffic"]["mix"] == {"q1": 1.0}
    assert [m["name"] for m in spec["per_layer"]] == ["ingest_s",
                                                      "answered_count"]
    read = run.reader("answered_count", tmp_path)

    class R:
        status = "answered"

    assert read({"requests": [R(), R()]}) == 2


def _run(cwd, *args, env=None):
    env = dict(os.environ if env is None else env)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         _bench()["workloads"][0]["name"], "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = _run(tmp_path, env=env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
