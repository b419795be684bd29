"""BENCHMARK.json against the contract it is written to, and the command's
behaviour where it must not run."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import arrivals, corpus, run
from bench.conftest import BENCH

ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command(bench_json):
    b = bench_json
    assert set(b) == KEYS
    assert b["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(b["paths"]) <= 16
    for p in b["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    # the full check of 24 cells fits in its 43,200 s
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len(json.dumps(b)) <= 64 * 1024


def test_configs_files_and_names(bench_json):
    used = {w["config"] for w in bench_json["workloads"]}
    names = [c["name"] for c in bench_json["configs"]]
    assert len(names) == len(set(names)) and set(names) == used
    for c in bench_json["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and len(c["reduced"]) <= 16
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["index"]["dim"] == cfg["dim"]
        assert cfg["search"]["k"] == cfg["k"]
        assert set(cfg["limits"]) == {"recall", "dist_gap"}
        assert cfg["n"] % cfg["generator"]["components"] == 0


@pytest.mark.parametrize("path", sorted((BENCH / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_config_files_state_dtype_and_metric(path):
    """Every configuration states a dtype and a metric the harness takes,
    and quantizes its generator exactly when the dtype is an integer."""
    cfg = json.loads(path.read_text())
    assert cfg["dtype"] in corpus.RANGES and cfg["metric"] in corpus.METRICS
    integer = corpus.RANGES[cfg["dtype"]] is not None
    assert ("quantize" in cfg["generator"]) == integer
    assert run.load_config(path.stem)["dtype"] == cfg["dtype"]


def test_workloads(bench_json):
    pairs = set()
    for w in bench_json["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert one_line(w["why"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        arrivals.load_mix(BENCH / "traffic" / f"{w['traffic']}.json")
    names = [w["name"] for w in bench_json["workloads"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    four = sum(w["chips"] == 4 for w in bench_json["workloads"])
    assert four <= max(1, len(names) // 2)


def reported(metric, cells):
    return set(metric.get("workloads", cells))


def test_metrics(bench_json):
    cells = [w["name"] for w in bench_json["workloads"]]
    e2e = {m["name"]: m for m in bench_json["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = list(e2e) + [m["name"] for m in bench_json["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench_json["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench_json["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert one_line(m["layer"]) and m["moves"] in e2e
        # every cell the metric is read in reports the metric it moves
        assert reported(m, cells) <= reported(e2e[m["moves"]], cells)
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
        run.load_reader(m["name"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in cells:
        mine = [m for m in bench_json["end_to_end"]
                if c in reported(m, cells)]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        assert any(c in reported(m, cells) for m in bench_json["per_layer"])


def command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift1m.batch",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def no_result(out: str) -> bool:
    return not any(line.lstrip().startswith("{")
                   for line in out.splitlines())


def test_run_exits_non_zero_without_a_tpu():
    p = command(ROOT)
    assert p.returncode == 1, p.stderr[-2000:]
    assert "needs 1 TPU chip" in p.stderr
    assert no_result(p.stdout)


def test_run_exits_non_zero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = command(tmp_path)
    assert p.returncode != 0
    assert "repro" in p.stderr
    assert no_result(p.stdout)


def test_run_rejects_an_unknown_workload():
    with pytest.raises(SystemExit):
        run.main(["--workload", "x", "--seed", "1"])
    assert run.main(["--workload", "nope.batch", "--seed", "1",
                     "--seconds", "1"]) == 2


def test_the_yardstick_imports_nothing_of_the_old_measurement():
    """Data, reference, metric arithmetic and trace reduction are the
    benchmark's own: nothing from repro.data, repro.launch or benchmarks/."""
    banned = re.compile(r"^\s*(from|import)\s+(repro\.data|repro\.launch|"
                        r"benchmarks)\b|from\s+repro\s+import\s+.*\b(data|"
                        r"launch)\b", re.M)
    for path in BENCH.rglob("*.py"):
        assert not banned.search(path.read_text()), path
