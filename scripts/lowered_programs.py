"""Writes the lowered round and evaluation programs (StableHLO text, no
source locations) of every configuration in a checkout's BENCHMARK.json,
at the configurations' ``tiny`` sizes, on the CPU:

    python scripts/lowered_programs.py <checkout> <outdir> <devices>

Run it on a parent checkout and on a change (1 and 4 virtual devices) and
``cmp`` the texts of the configurations both have: a refactor, or a PR
that adds a model beside the others, leaves them equal byte for byte
(PERF.md section 6, PRs 31 and 33).
"""

import os
import sys

checkout, outdir, devices = sys.argv[1], sys.argv[2], int(sys.argv[3])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={devices}")
sys.path.insert(0, checkout)
sys.path.insert(0, os.path.join(checkout, "benchmarks", "tests"))

import jax.numpy as jnp  # noqa: E402

import tiny  # noqa: E402
from benchmarks.harness.spec import Bench  # noqa: E402
from benchmarks.traffic import generate  # noqa: E402
from colearn_federated_learning_tpu.fed.engine import (  # noqa: E402
    FederatedLearner,
)

bench = Bench(checkout)
os.makedirs(outdir, exist_ok=True)
for entry in bench.doc["configs"]:
    name = entry["name"]
    doc = bench.config(name)
    tiny.shrink_config(doc)
    cell = next(w for w in bench.doc["workloads"] if w["config"] == name)
    traffic = bench.traffic(cell["traffic"])
    tiny.shrink_traffic(traffic)
    if devices > 1:
        traffic["cohort"] = max(traffic["cohort"], devices) // devices * devices
    learner = FederatedLearner.from_config(
        generate.experiment_config(doc, traffic, 0),
        dataset=generate.dataset(bench, doc, traffic, 0))
    round_args = (learner.server_state, learner.base_key,
                  jnp.asarray(0, jnp.int32), *learner._device_data, None,
                  None, learner._dp_clip)
    for part, text in (
            ("round", learner._round_fn.lower(*round_args).as_text()),
            ("eval", learner._eval_fn.lower(
                learner.server_state.params).as_text())):
        with open(os.path.join(outdir, f"{name}_{part}_{devices}.txt"),
                  "w") as f:
            f.write(text)
    print(name, devices, "ok", flush=True)
