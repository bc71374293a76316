"""North-star-shaped perf run (BASELINE.json: 1000-client FedAvg CIFAR-10).

Runs the real engine on whatever accelerator is present: 1000 clients,
cohort >= 64, width-64 bf16 CNN, jit-compiled local SGD, FedAvg in-XLA.
Reports rounds/sec, client-samples/sec/chip, HBM usage, and an MFU estimate
from XLA's own cost analysis of the compiled round program.  Run with
--profile-dir to also capture a jax.profiler trace.

Every run writes a RAW record file ``results/perf_<shape>.jsonl`` (override
with --out): one ``meta`` line (device kind, shape, cost_analysis FLOPs,
compile/build timings, HBM), one line per timed round (dispatch timestamps
in the pipelined mode; true per-round latencies with --sync-per-round), and
a closing ``summary`` line.  PERF.md table rows cite these files — every
number must be traceable to a committed record.

    python scripts/perf_north_star.py [--rounds 20] [--cohort 64]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Per-chip bf16 peaks, keyed by ``device_kind`` (v5e: Google Cloud "TPU
# v5e" documentation); see PERF.md for the derivation of the MFU estimate.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12, "TPU v4": 275e12,
                   "TPU v5p": 459e12, "TPU v6 lite": 918e12}


def peak_bf16_flops(device_kind: str) -> float:
    """A device that is not in the table is an error, not "MFU 0.0"."""
    if device_kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no bf16 peak for device_kind {device_kind!r}; "
            f"known: {sorted(PEAK_BF16_FLOPS)}")
    return PEAK_BF16_FLOPS[device_kind]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--num-clients", type=int, default=1000)
    p.add_argument("--cohort", type=int, default=64)
    p.add_argument("--local-steps", type=int, default=8)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--width", type=int, default=64)
    p.add_argument("--examples-per-client", type=int, default=64)
    p.add_argument("--rounds", type=int, default=20)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--tp-size", type=int, default=1,
                   help="model-axis size: shard the global model (and the "
                        "server plane) over a (clients, model) mesh")
    p.add_argument("--stem", default="conv",
                   choices=["conv", "space_to_depth"],
                   help="CNN stem MFU lever (models/cnn.py)")
    p.add_argument("--norm", default="group", choices=["group", "none"],
                   help="CNN norm MFU lever")
    p.add_argument("--profile-dir", default=None)
    p.add_argument("--sync-per-round", action="store_true",
                   help="block on every round for TRUE per-round "
                        "latencies (disables the on-device pipelining "
                        "the headline number uses)")
    p.add_argument("--out", default=None,
                   help="raw JSONL record path (default: "
                        "results/perf_c<cohort>_w<width>_n<clients>.jsonl)")
    args = p.parse_args()

    import jax

    from colearn_federated_learning_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    from colearn_federated_learning_tpu.data import registry as data_registry
    from colearn_federated_learning_tpu.fed.engine import FederatedLearner
    from colearn_federated_learning_tpu.utils.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig, RunConfig,
    )

    dev = jax.devices()[0]
    print(f"[perf] device: {dev.device_kind} ({dev.platform}) "
          f"x{len(jax.devices())}", file=sys.stderr)
    peak = peak_bf16_flops(dev.device_kind)    # before any time is spent

    config = ExperimentConfig(
        data=DataConfig(dataset="cifar10", num_clients=args.num_clients,
                        partition="dirichlet", dirichlet_alpha=0.5,
                        max_examples_per_client=args.examples_per_client),
        model=ModelConfig(name="cnn", num_classes=10, width=args.width,
                          dtype="bfloat16", stem=args.stem, norm=args.norm),
        fed=FedConfig(strategy="fedavg", cohort_size=args.cohort,
                      local_steps=args.local_steps, batch_size=args.batch,
                      lr=0.05, momentum=0.9),
        run=RunConfig(name="north_star", backend="auto",
                      tp_size=args.tp_size, profile_dir=args.profile_dir),
    )
    dataset = data_registry.get_dataset(
        "cifar10", seed=0,
        max_train=args.num_clients * args.examples_per_client, max_test=512,
    )
    t0 = time.perf_counter()
    learner = FederatedLearner.from_config(config, dataset=dataset)
    build_s = time.perf_counter() - t0

    # XLA's own FLOP count for one compiled round (forward+backward+opt),
    # via the engine's introspection path (telemetry/runtime.py) — same
    # operands run_round passes, scan body scaled by local steps.
    cost = learner.round_cost_analysis()
    compile_s = float(cost.get("compile_s", 0.0))
    flops_per_round = float(cost.get("flops_per_round", 0.0))

    if args.profile_dir:
        learner.fit(rounds=3)                       # traces rounds 1..2
    for _ in range(args.warmup):
        learner.run_round()
    learner.finalize_history()                      # true device sync

    from colearn_federated_learning_tpu.parallel import partition

    # Report memory across the LEARNER'S MESH, not jax.devices()[0]: the
    # round program runs (and with --tp-size, the model lives sharded)
    # over every mesh chip, so chip 0 alone under-reports a multi-chip
    # run exactly when the numbers matter most.
    mesh_devices = (list(learner.mesh.devices.flat)
                    if learner.mesh is not None else [dev])
    stats = [d.memory_stats() or {} for d in mesh_devices]
    mem = {
        "bytes_in_use": max((s.get("bytes_in_use", 0) for s in stats),
                            default=0),
        "peak_bytes_in_use": max(
            (s.get("peak_bytes_in_use", s.get("bytes_in_use", 0))
             for s in stats), default=0),
        "bytes_limit": max((s.get("bytes_limit", 0) for s in stats),
                           default=0),
    }
    # Measured per-chip server-state bytes (per-shard accounting) — the
    # deterministic stand-in where memory_stats() is empty (CPU backends).
    server_bytes_per_chip = partition.bytes_per_chip(learner.server_state)
    gather_avoided = partition.tree_gather_avoided(
        learner.server_state.params)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tag = (f"perf_c{learner.cohort_size}_w{args.width}_n{args.num_clients}"
           f"_k{learner.num_steps}_b{args.batch}_e{args.examples_per_client}"
           f"{'_s2d' if args.stem == 'space_to_depth' else ''}"
           f"{'_nonorm' if args.norm == 'none' else ''}"
           f"{f'_tp{args.tp_size}' if args.tp_size > 1 else ''}"
           f"{'_sync' if args.sync_per_round else ''}")
    out_path = args.out or os.path.join(repo, "results", f"{tag}.jsonl")
    out_dir = os.path.dirname(out_path)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    rec_f = open(out_path, "w")

    def rec(obj):
        rec_f.write(json.dumps(obj) + "\n")

    rec({
        "kind": "meta",
        "recorded_unix": int(time.time()),
        "device": dev.device_kind,
        "platform": dev.platform,
        "n_devices": len(jax.devices()),
        "mesh_devices": len(mesh_devices),
        "tp_size": learner.tp_size,
        "num_clients": args.num_clients,
        "cohort": learner.cohort_size,
        "local_steps": learner.num_steps,
        "batch": args.batch,
        "width": args.width,
        "stem": args.stem,
        "norm": args.norm,
        "examples_per_client": args.examples_per_client,
        "build_s": round(build_s, 2),
        "compile_s": round(compile_s, 2),
        "cost_analysis_flops_per_round": flops_per_round,
        "hbm_used_gb": round(mem["bytes_in_use"] / 2**30, 3),
        "hbm_peak_per_chip_gb": round(mem["peak_bytes_in_use"] / 2**30, 3),
        "hbm_limit_gb": round(mem["bytes_limit"] / 2**30, 3),
        "server_bytes_per_chip": int(server_bytes_per_chip),
        "gather_bytes_avoided": int(gather_avoided),
        "timing_mode": ("sync_per_round" if args.sync_per_round
                        else "pipelined"),
    })

    # Pipelined (default): rounds queue on-device and block_until_ready
    # on the last round's params closes the timed window — per-round
    # stamps are DISPATCH times, only the total is a latency.
    # --sync-per-round instead blocks each round for true per-round
    # latencies.
    t0 = time.perf_counter()
    for i in range(args.rounds):
        r0 = time.perf_counter()
        learner.run_round(sync=args.sync_per_round)
        rec({"kind": "round", "round": i,
             ("round_s" if args.sync_per_round else "dispatch_s"):
             round(time.perf_counter() - r0, 6)})
    jax.block_until_ready(learner.server_state.params)
    dt = time.perf_counter() - t0
    learner.finalize_history()
    rps = args.rounds / dt

    samples_per_round = learner.cohort_size * learner.num_steps * args.batch
    mfu = flops_per_round * rps / peak

    out = {
        "kind": "summary",
        "device": dev.device_kind,
        "platform": dev.platform,
        "num_clients": args.num_clients,
        "cohort": learner.cohort_size,
        "local_steps": learner.num_steps,
        "batch": args.batch,
        "width": args.width,
        "tp_size": learner.tp_size,
        "rounds_timed": args.rounds,
        "total_s": round(dt, 4),
        "rounds_per_sec": round(rps, 4),
        "server_bytes_per_chip": int(server_bytes_per_chip),
        "gather_bytes_avoided": int(gather_avoided),
        "client_samples_per_sec_per_chip": round(rps * samples_per_round, 1),
        "flops_per_round": flops_per_round,
        "model_flops_utilization": round(mfu, 4),
    }
    rec(out)
    rec_f.close()
    print(f"[perf] raw record -> {out_path}", file=sys.stderr)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
