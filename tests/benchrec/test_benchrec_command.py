"""The command itself: it refuses a CPU and prints no result line; and a
later PR adds a configuration, a traffic mix, a driver and a metric as
new files plus manifest entries, editing nothing that is there."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from benchrec_util import REPO

ARGS = ["--workload", "chain_64v.lite_certify", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def run_command(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONHASHSEED", None)
    env.update(extra_env or {})
    return subprocess.run([sys.executable, "-m", "benchmark.run"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=240)


def result_lines(stdout):
    out = []
    for ln in stdout.splitlines():
        try:
            doc = json.loads(ln)
        except ValueError:
            continue
        if isinstance(doc, dict) and "metrics" in doc:
            out.append(doc)
    return out


def test_the_command_refuses_a_cpu_and_prints_no_result():
    r = run_command(REPO)
    assert r.returncode not in (0, None)
    assert result_lines(r.stdout) == []
    assert "PYTHONHASHSEED=0" in r.stdout.splitlines()[0]
    assert "TPU" in r.stderr and "Nothing was measured" in r.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run_command(str(tmp_path), {"PYTHONPATH": ""})
    assert r.returncode not in (0, None)
    assert result_lines(r.stdout) == []


def _digest(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for name in files:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


DUMMY_DRIVER = '''
from benchmark.harness import Outcome
from benchmark.passes import Pass, run_passes


def run(h):
    h.settle()
    h.open_window()
    passes = run_passes(lambda _: Pass(0.0, 0.25, int(h.params["widgets"])),
                        h.seconds)
    h.close_window()
    h.check("dummy_wrong_answers", 0, 0)
    return Outcome(attempted=sum(p.work for p in passes), failed=0,
                   passes=passes, counters={"dummy.polished": 3.0})
'''

DUMMY_METRIC = '''
LAYER = "dummy layer"
MOVES = "commits_per_s"


def read(r):
    return r.counters.get("dummy.polished")
'''


def test_a_later_pr_adds_files_and_entries_and_edits_nothing(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(root / "benchmark")
    bench = root / "benchmark"
    (bench / "configs" / "dummy_shop.json").write_text(json.dumps(
        {"name": "dummy_shop", "source": "a test", "widgets": 40,
         "guarantees": ["none"], "rehearsal": {"widgets": 4}}))
    (bench / "traffic" / "dummy_shop.polish.json").write_text(json.dumps(
        {"driver": "dummy", "params": {}}))
    (bench / "drivers" / "dummy.py").write_text(DUMMY_DRIVER)
    (bench / "metrics" / "widgets_polished.py").write_text(DUMMY_METRIC)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "dummy_shop", "source": "a test",
                           "file": "benchmark/configs/dummy_shop.json",
                           "reduced": [], "why": "a test"})
    doc["workloads"].append({"name": "dummy_shop.polish",
                             "config": "dummy_shop", "traffic": "polish",
                             "chips": 1, "why": "a test"})
    for m in doc["end_to_end"]:
        if m["name"] == "commits_per_s":
            m["workloads"].append("dummy_shop.polish")
    doc["per_layer"].append({"name": "widgets_polished", "unit": "count",
                             "better": "higher", "source": "program_counter",
                             "layer": "dummy layer", "moves": "commits_per_s",
                             "workloads": ["dummy_shop.polish"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    script = (
        "import json, os\n"
        "from benchmark.harness import run_cell\n"
        "for trace in (False, True):\n"
        "    print(json.dumps(run_cell(os.getcwd(), 'dummy_shop.polish', 1,"
        " 0.0, trace, rehearsal=True)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), REPO]))
    r = subprocess.run([sys.executable, "-c", script], cwd=str(root),
                       env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    e2e, layer = result_lines(r.stdout)
    assert e2e["correct"] is True and e2e["attempted"] == 8
    assert e2e["metrics"]["commits_per_s"]["value"] == 16.0   # 4 / 0.25 s
    assert set(e2e["metrics"]) == {"commits_per_s", "setup_s"}
    assert layer["metrics"]["widgets_polished"] == {"value": 3.0,
                                                    "unit": "count"}
    after = _digest(root / "benchmark")
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 4
