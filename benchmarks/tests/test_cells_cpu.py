"""Each cell's path end to end on the CPU, at a tiny size."""

import json
import os
import re
import subprocess
import sys

import pytest

import tiny
from benchmarks.harness.spec import Bench

CELLS = [("cnn_device_bound", 1), ("cnn_mesh4", 4), ("bert_memory_bound", 1)]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# What the CPU cannot give: it has no device plane in its trace and no
# memory_stats().
NOT_ON_CPU = {"round_device_ms", "allreduce_ms_per_round",
              "device_idle_share", "hbm_peak_reserved_gb"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def committed_chips():
    bench = Bench(tiny.REPO)
    return {w["name"]: w["chips"] for w in bench.doc["workloads"]}


def test_cells_are_the_committed_ones():
    assert committed_chips() == dict(CELLS)
    assert sum(c == 4 for c in committed_chips().values()) == 1


@pytest.mark.parametrize("workload,chips", CELLS)
def test_cell_runs_end_to_end(root, workload, chips):
    # 16 rounds are what train_loss_r8_15 needs; the bert cell's are fast.
    seconds = 2.0 if workload == "bert_memory_bound" else 0.5
    process = tiny.run(root, workload, chips, seconds=seconds)
    assert process.returncode == 0, process.stderr[-2000:]
    out = tiny.lines(process)
    result, window = out[-1], next(e for e in out if e.get("event") == "window")
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, out
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    # Nothing compiles inside the window, by jax's count and the program's.
    assert window["compiles"] == 0 and window["round_compiles"] == 1
    # Whole chunks only.
    chunk = tiny.TINY_TRAFFIC[Bench(root).workload(workload)["traffic"]][
        "eval_every"]
    assert window["chunks"] >= 1
    assert window["rounds"] == window["chunks"] * chunk
    assert result["attempted"] == window["rounds"] and result["failed"] == 0
    assert [e[0] for e in window["eval"]] == [
        i * chunk for i in range(window["chunks"] + 1)]
    # The cell's end-to-end metrics and no others.
    want = {m["name"] for m in Bench(root).metrics("end_to_end", workload)}
    if window["rounds"] < 15:
        want.discard("train_loss_r8_15")
    assert set(result["metrics"]) == want
    assert "setup_s" in want and len(want) >= 2
    for value in result["metrics"].values():
        assert value["value"] > 0 and value["unit"]
    samples = window["samples_per_round"] * window["rounds"]
    assert result["metrics"]["client_samples_per_s_per_chip"]["value"] == (
        pytest.approx(samples / window["elapsed_s"] / chips))


@pytest.mark.parametrize("workload,chips", [("cnn_mesh4", 4),
                                            ("bert_memory_bound", 1)])
def test_traced_run_reports_per_layer_metrics(root, workload, chips):
    process = tiny.run(root, workload, chips, seconds=0.5, trace=1)
    assert process.returncode == 0, process.stderr[-2000:]
    result = tiny.lines(process)[-1]
    assert set(result) - {"breakdown"} == RESULT_KEYS
    assert {"busy_s", "window_s"} <= set(result["device"])
    names = {m["name"] for m in Bench(root).metrics("per_layer", workload)}
    # Readers that find nothing to read: their metrics are left out, and
    # a traced run in which no operation ran on a device is not correct.
    assert set(result["metrics"]) == names - NOT_ON_CPU
    assert {"enqueue_ms_p50", "eval_share", "mfu"} <= set(result["metrics"])
    assert result["device"]["busy_s"] == 0 and result["correct"] is False


def test_run_py_refuses_a_cpu(root):
    """``run.py`` has no way to be told that a CPU will do."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=tiny.REPO)
    process = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", "cnn_device_bound", "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert process.returncode != 0
    assert "correct" not in process.stdout
    assert "cell needs 1 x tpu" in process.stderr


def test_run_py_fails_without_the_program(root):
    """A directory with BENCHMARK.json and ``benchmarks/`` only."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    process = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", "cnn_device_bound", "--seed", "0", "--seconds", "1",
         "--trace", "0"],
        env=env, cwd=root, capture_output=True, text=True, timeout=300)
    assert process.returncode != 0 and "correct" not in process.stdout


def test_unknown_names_list_what_exists(root):
    bench = Bench(root)
    with pytest.raises(KeyError, match="cnn_device_bound"):
        bench.workload("nope")
    with pytest.raises(KeyError, match="cohort128_eval10"):
        bench.traffic("nope")
    with pytest.raises(KeyError, match="enqueue_ms_p50"):
        bench.module("layer_metrics", "nope")
    with pytest.raises(KeyError, match="agnews_bert_base"):
        bench.config("nope")


def test_benchmark_json_is_within_the_contract():
    doc = Bench(tiny.REPO).doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["benchmarks"]
    assert os.path.getsize(os.path.join(tiny.REPO, "BENCHMARK.json")) < 65536
    assert all(len(w["why"]) <= 200 for w in doc["workloads"] + doc["configs"])
    assert any(m["name"] == "setup_s" for m in doc["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in doc["end_to_end"])
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in doc["end_to_end"])
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert all(m["moves"] in e2e for m in doc["per_layer"])
    # The driver refuses a name or a layer outside these before any run.
    names = [entry["name"] for key in ("configs", "workloads", "end_to_end",
                                       "per_layer") for entry in doc[key]]
    assert len(set(names)) == len(names)
    assert all(re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}", n)
               for n in names)
    assert all(re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", m["layer"])
               for m in doc["per_layer"])
    for config in doc["configs"]:
        with open(os.path.join(tiny.REPO, config["file"])) as f:
            assert json.load(f)["reduced"] == config["reduced"]
