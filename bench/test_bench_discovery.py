"""Configurations, traffic mixes, metric readers, references, cell limits
and device peaks are files found by name; the command refuses to measure
without a TPU, and without the program next to it."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

REPO = harness.REPO


def test_files_in_a_new_root_are_found_by_name(tmp_path):
    for kind in ("configs", "traffic", "metrics", "references", "cells"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "m-1.json").write_text('{"hidden_size": 8}')
    (tmp_path / "traffic" / "bursty.json").write_text('{"concurrency": 3}')
    (tmp_path / "cells" / "m-1.bursty.json").write_text(
        '{"widest_logit_gap": 0.5}')
    (tmp_path / "metrics" / "my_metric.py").write_text(
        "def read(w):\n    return 42.0\n")
    (tmp_path / "references" / "tiny.py").write_text("NAME = 'tiny'\n")
    (tmp_path / "devices.json").write_text(
        '{"Chip X": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 2.0}}')
    assert harness.load_json("configs", "m-1", tmp_path)["hidden_size"] == 8
    assert harness.load_json("traffic", "bursty", tmp_path)["concurrency"] == 3
    assert harness.load_json("cells", "m-1.bursty", tmp_path)[
        "widest_logit_gap"] == 0.5
    assert harness.load_module("metrics", "my_metric", tmp_path).read(None) \
        == 42.0
    assert harness.load_module("references", "tiny", tmp_path).NAME == "tiny"
    assert harness.peaks_for("Chip X", tmp_path)["hbm_bytes_per_s"] == 2.0
    with pytest.raises(FileNotFoundError):
        harness.load_json("traffic", "absent", tmp_path)


def test_every_cell_resolves():
    bm = harness.benchmark()
    for wl in bm["workloads"]:
        _, cfg, mix = harness.cell(bm, wl["name"])
        assert harness.load_json("cells", wl["name"])["widest_logit_gap"] > 0
        harness.load_module("references", cfg["reference"])
        for m in harness.per_layer_for(bm, wl["name"]):
            assert callable(harness.load_module("metrics", m["name"]).read)
        assert mix["concurrency"] > 0


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    r = _run(REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not a TPU" in r.stderr


def test_command_fails_with_the_benchmark_files_alone(tmp_path):
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in bm["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
