"""A copy of the benchmark in a temporary directory, cut to a size the CPU
runs in seconds.  The cut is made here, in the test's own copy of the
data files; ``run.py`` has no option for it."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_MODEL = {
    "cifar10_cnn": {"width": 8},
    "agnews_bert_base": {"width": 32, "depth": 2, "num_heads": 2,
                         "seq_len": 16, "vocab_size": 1400},
}
TINY_DATASET = {
    "cifar10_cnn": {"n_train": 1024},
    "agnews_bert_base": {"n_train": 400, "input_shape": [16],
                         "vocab_size": 1400},
}
TINY_DATA = {
    "cifar10_cnn": {"num_clients": 16, "max_examples_per_client": 32},
    "agnews_bert_base": {"num_clients": 10},
}
TINY_TRAFFIC = {
    "cohort128_eval10": {"cohort": 4, "eval_every": 3, "holdout": 128},
    "cohort512_eval10": {"cohort": 8, "eval_every": 3, "holdout": 128},
    "cohort9_eval10": {"cohort": 3, "eval_every": 3, "holdout": 64},
}


def edit_json(path: str, edit) -> None:
    with open(path) as f:
        doc = json.load(f)
    edit(doc)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def make_root(tmp: str) -> str:
    """``tmp/root`` with BENCHMARK.json and ``benchmarks/`` as committed,
    then the tiny sizes written over the configuration and traffic
    files."""
    root = os.path.join(tmp, "root")
    shutil.copytree(
        os.path.join(REPO, "benchmarks"), os.path.join(root, "benchmarks"),
        ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for name in TINY_MODEL:
        def shrink(doc, name=name):
            doc["experiment"]["model"].update(TINY_MODEL[name])
            doc["experiment"]["data"].update(TINY_DATA[name])
            doc["dataset"].update(TINY_DATASET[name])
        edit_json(os.path.join(root, "benchmarks", "configs", name + ".json"),
                  shrink)
    for name, values in TINY_TRAFFIC.items():
        edit_json(os.path.join(root, "benchmarks", "traffic", name + ".json"),
                  lambda doc, values=values: doc.update(values))
    # The CPU has no published peak; the rehearsal gives it a made-up one
    # so that the mfu reader's arithmetic runs.
    edit_json(os.path.join(root, "benchmarks", "harness", "peaks.json"),
              lambda doc: doc.update(cpu={"bf16_flops_per_s": 1e12}))
    return root


def lines(process: subprocess.CompletedProcess) -> list[dict]:
    return [json.loads(line) for line in process.stdout.splitlines()]


def run(root: str, workload: str, chips: int, seed: int = 0,
        seconds: float = 1.0,
        trace: int = 0) -> subprocess.CompletedProcess:
    """One run in a child with ``chips`` virtual CPU devices."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "tests", "drive.py"),
         root, workload, str(seed), str(seconds), str(trace)],
        env=env, capture_output=True, text=True, timeout=600)
