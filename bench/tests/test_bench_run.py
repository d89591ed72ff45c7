"""The command and its data files: a CPU backend exits non-zero without a
result line, and every name in BENCHMARK.json finds its file."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import run

SPEC = run.load_json(run.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_cpu_backend_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(run.BENCH, "run.py"), "--workload",
         "olmo1b-train", "--seed", str(2**33 + 1), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=run.ROOT, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "nothing was run" in proc.stderr


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_workload_finds_its_files(w):
    spec = run.load_spec(w["name"])
    assert spec["cell"]["kind"] in ("train", "serve")
    assert spec["cfg"]["name"] == w["config"]
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in names
        assert callable(run.load_reader(m["name"]))


def test_names_and_keys():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for c in SPEC["configs"]:
        with open(os.path.join(run.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["name"] == c["name"]
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] == 1
