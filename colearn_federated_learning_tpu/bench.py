"""Headline benchmark: FedAvg rounds/sec on the accelerator.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", ...}.

The measured workload is BASELINE.json's headline metric ("FedAvg rounds/sec
and client-samples/sec/chip; CIFAR-10 acc@round"): a federated round —
cohort of clients, each running jit-compiled local SGD on-device, FedAvg
aggregation in-XLA (psum over a mesh when >1 device) — at config #2's
shape (CIFAR-10 CNN, bf16, width 64), on the devices jax gives this
process.  Without an accelerator the bench fails: a CPU timing says how
fast XLA:CPU is, not how fast this system is.

``vs_baseline`` compares against a faithful reference-style implementation
run in-process (SURVEY.md §3a: sequential per-client PyTorch-CPU local
training + host-side state_dict weighted averaging — the reference's
PySyft-worker architecture minus the network, which only makes the baseline
FASTER than the real thing).  There are no published reference numbers
(BASELINE.json "published" is {}), so this measured stand-in is the baseline.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


# Scaled CIFAR-10 CNN FedAvg (BASELINE config #2).
TPU_WORKLOAD = dict(model="cnn", dataset="cifar10", cohort=16, local_steps=8,
                    batch=32, width=64, num_clients=64,
                    examples_per_client=256, dtype="bfloat16")


def _make_config(w: dict):
    from colearn_federated_learning_tpu.utils.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig, RunConfig,
    )

    return ExperimentConfig(
        data=DataConfig(dataset=w["dataset"], num_clients=w["num_clients"],
                        partition="dirichlet", dirichlet_alpha=0.5,
                        max_examples_per_client=w["examples_per_client"]),
        model=ModelConfig(name="cnn", num_classes=10, width=w["width"],
                          dtype=w["dtype"]),
        fed=FedConfig(strategy="fedavg", cohort_size=w["cohort"],
                      local_steps=w["local_steps"], batch_size=w["batch"],
                      lr=0.05, momentum=0.9),
        run=RunConfig(name="bench", backend="auto"),
    )


def run_tpu_native(rounds: int, warmup: int) -> dict:
    """Time ``rounds`` federated rounds of ``TPU_WORKLOAD`` after ``warmup``
    untimed ones (the first of which compiles)."""
    import jax

    from colearn_federated_learning_tpu.data import registry as data_registry
    from colearn_federated_learning_tpu.fed.engine import FederatedLearner

    w = TPU_WORKLOAD
    config = _make_config(w)
    dataset = data_registry.get_dataset(
        w["dataset"], seed=0,
        max_train=w["num_clients"] * w["examples_per_client"], max_test=512,
    )
    learner = FederatedLearner.from_config(config, dataset=dataset)
    devices = learner.devices
    n_devices = len(devices)
    # Actual per-round work (cohort may be adjusted to the mesh size).
    samples_per_round = learner.cohort_size * learner.num_steps * w["batch"]

    for _ in range(warmup):
        learner.run_round()
    learner.finalize_history()

    # sync=False: rounds are enqueued back to back (dispatch is
    # asynchronous); block_until_ready on the last round's params is the
    # barrier that closes the timed window.
    t0 = time.perf_counter()
    for _ in range(rounds):
        learner.run_round(sync=False)
    jax.block_until_ready(learner.server_state.params)
    dt = time.perf_counter() - t0
    learner.finalize_history()

    rps = rounds / dt
    return {
        "rounds_per_sec": rps,
        "client_samples_per_sec_per_chip": rps * samples_per_round / n_devices,
        "n_devices": n_devices,
        "rounds_timed": rounds,
        "seconds_timed": round(dt, 3),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
    }


def run_reference_style(rounds: int) -> dict:
    """Reference architecture stand-in: sequential per-client torch-CPU SGD +
    host-side numpy weighted averaging of state_dicts (SURVEY.md §3a/§3c),
    at ``TPU_WORKLOAD``'s shapes so ``vs_baseline`` is like for like."""
    import numpy as np
    import torch
    import torch.nn as tnn

    w = TPU_WORKLOAD
    cohort, local_steps = w["cohort"], w["local_steps"]
    batch, width = w["batch"], w["width"]
    torch.manual_seed(0)

    class TorchModel(tnn.Module):
        # Same op graph as colearn_federated_learning_tpu/models/cnn.py.
        def __init__(self, width=width, num_classes=10):
            super().__init__()
            layers, in_ch = [], 3
            for mult in (1, 2, 4):
                ch = width * mult
                layers += [
                    tnn.Conv2d(in_ch, ch, 3, padding=1),
                    tnn.GroupNorm(min(32, ch), ch), tnn.ReLU(),
                    tnn.Conv2d(ch, ch, 3, padding=1),
                    tnn.GroupNorm(min(32, ch), ch), tnn.ReLU(),
                    tnn.MaxPool2d(2),
                ]
                in_ch = ch
            self.features = tnn.Sequential(*layers)
            self.head = tnn.Linear(in_ch, num_classes)

        def forward(self, x):
            h = self.features(x)
            return self.head(h.mean(dim=(2, 3)))

    rng = np.random.default_rng(0)
    data = [
        (torch.randn(local_steps, batch, 3, 32, 32),
         torch.from_numpy(rng.integers(0, 10, (local_steps, batch))).long())
        for _ in range(cohort)
    ]
    global_model = TorchModel()
    global_sd = {k: v.clone() for k, v in global_model.state_dict().items()}
    loss_fn = tnn.CrossEntropyLoss()

    t0 = time.perf_counter()
    for _ in range(rounds):
        updates, weights = [], []
        for cx, cy in data:  # sequential workers, as in the reference
            model = TorchModel()
            model.load_state_dict(global_sd)  # "broadcast"
            opt = torch.optim.SGD(model.parameters(), lr=0.05, momentum=0.9)
            for s in range(local_steps):
                opt.zero_grad()
                loss_fn(model(cx[s]), cy[s]).backward()
                opt.step()
            # "websocket return": state_dict to host numpy
            updates.append({k: v.detach().numpy() for k, v in model.state_dict().items()})
            weights.append(local_steps * batch)
        # host-side fed_avg(weights, sizes)
        total = float(sum(weights))
        global_sd = {
            k: torch.from_numpy(
                sum(w * u[k] for w, u in zip(weights, updates)) / total
            )
            for k in updates[0]
        }
    dt = time.perf_counter() - t0
    return {"rounds_per_sec": rounds / dt}


def main(argv: list[str] | None = None) -> int:
    """``argv=None`` parses ``sys.argv``; pass an explicit list when calling
    from another CLI (``colearn bench`` passes its own arguments).

    Returns 0 after printing the ONE JSON line; returns 1, with no JSON
    on stdout, when jax offers no accelerator.  A run that raises is
    left to raise."""
    from colearn_federated_learning_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    p = argparse.ArgumentParser(prog="colearn bench")
    p.add_argument("--rounds", type=int, default=20)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--baseline-rounds", type=int, default=1)
    p.add_argument("--skip-baseline", action="store_true")
    args = p.parse_args(argv)

    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        print(f"[bench] no accelerator: jax.devices() = {devices}",
              file=sys.stderr)
        return 1

    ours = run_tpu_native(args.rounds, args.warmup)
    print(f"[bench] tpu-native: {ours}", file=sys.stderr)
    vs = 0.0
    if not args.skip_baseline:
        base = run_reference_style(args.baseline_rounds)
        print(f"[bench] reference-style torch-cpu: {base}", file=sys.stderr)
        vs = ours["rounds_per_sec"] / base["rounds_per_sec"]
    w = TPU_WORKLOAD
    print(json.dumps({
        "metric": f"fedavg_{w['dataset']}_{w['model']}_rounds_per_sec",
        "value": round(ours["rounds_per_sec"], 4),
        "unit": "rounds/sec",
        "vs_baseline": round(vs, 4),
        "platform": ours["platform"],
        "device_kind": ours["device_kind"],
        "n_devices": ours["n_devices"],
        "rounds_timed": ours["rounds_timed"],
        "seconds_timed": ours["seconds_timed"],
        "client_samples_per_sec_per_chip": round(
            ours["client_samples_per_sec_per_chip"], 1),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
