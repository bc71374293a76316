"""A later PR adds a cell, a configuration and a per-layer metric as new
files and entries; no file that is there is edited."""

import hashlib
import json
import os

import tiny
from benchmarks.harness.spec import Bench

READER = '''"""Orchestration: rounds the window completed (a count)."""


def read(r):
    return float(r.rounds) if r.rounds else None
'''


def digests(root: str) -> dict:
    out = {}
    for folder, _, files in os.walk(os.path.join(root, "benchmarks")):
        if "__pycache__" in folder:
            continue
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[path] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_new_cell_config_and_metric_are_data(tmp_path):
    root = tiny.make_root(str(tmp_path))
    before = digests(root)
    bench_dir = os.path.join(root, "benchmarks")

    # A configuration: its file of sizes (another width of a family whose
    # reference is there).
    with open(os.path.join(bench_dir, "configs", "cifar10_cnn.json")) as f:
        config = json.load(f)
    config["name"] = "cifar10_cnn_w16"
    config["experiment"]["model"]["width"] = 16
    with open(os.path.join(bench_dir, "configs", "cifar10_cnn_w16.json"),
              "w") as f:
        json.dump(config, f)
    # A traffic mix: a file of parameters.
    with open(os.path.join(bench_dir, "traffic", "cohort2_eval2.json"),
              "w") as f:
        json.dump({"cohort": 2, "eval_every": 2, "holdout": 64,
                   "local_steps": 2}, f)
    # A per-layer metric: a reader of its own.
    with open(os.path.join(bench_dir, "layer_metrics", "rounds_completed.py"),
              "w") as f:
        f.write(READER)
    # And the entries.
    tiny.edit_json(os.path.join(root, "BENCHMARK.json"), lambda doc: (
        doc["configs"].append({
            "name": "cifar10_cnn_w16", "source": config["source"],
            "file": "benchmarks/configs/cifar10_cnn_w16.json",
            "reduced": [], "why": "test"}),
        doc["workloads"].append({
            "name": "cnn_w16_small_cohort", "config": "cifar10_cnn_w16",
            "traffic": "cohort2_eval2", "chips": 1, "why": "test"}),
        doc["per_layer"].append({
            "name": "rounds_completed", "unit": "rounds", "better": "higher",
            "source": "program_counter", "layer": "orchestration",
            "moves": "client_samples_per_s_per_chip",
            "workloads": ["cnn_w16_small_cohort"]})))

    process = tiny.run(root, "cnn_w16_small_cohort", 1, seconds=0.5, trace=1)
    assert process.returncode == 0, process.stderr[-2000:]
    out = tiny.lines(process)
    window = next(e for e in out if e.get("event") == "window")
    assert window["samples_per_round"] == 2 * 2 * 32
    assert out[-1]["metrics"]["rounds_completed"] == {
        "value": float(window["rounds"]), "unit": "rounds"}
    # The new metric is this cell's alone; the others keep theirs.
    assert "rounds_completed" not in {
        m["name"] for m in Bench(root).metrics("per_layer", "cnn_device_bound")}
    after = digests(root)
    assert {p: d for p, d in after.items() if p in before} == before
    assert len(after) == len(before) + 3
