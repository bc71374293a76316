#!/usr/bin/env python
"""Mesh smoke: sharded vs replicated server on a forced 8-device CPU host.

The multichip proof for the PR 9 sharded server, runnable anywhere CI
runs: force 8 host CPU devices, build the BERT-config global model, and
measure the server plane both ways —

- ``replicated``: every chip holds the full params/opt state (the pre-PR
  layout);
- ``sharded``: params, optimizer state, and the fold live partitioned
  over a ``(model,)`` mesh (parallel/partition.ServerPlacement).

Self-checking: the sharded StreamingFolder fold must be BITWISE identical
to the replicated fold, the sharded DownlinkEncoder frame byte-identical
to the gathered frame, and per-chip server bytes strictly lower sharded
than replicated.  One JSON row per mode plus a ``compare`` row with
``hbm_ratio_sharded_over_replicated`` is written to
``results/mesh_bench.jsonl`` — the sentinel rules in pyproject.toml pin
the ratio < 1 and gather-bytes-avoided > 0, so a regression that quietly
re-replicates the server fails `colearn slo`.

Usage (a CPU tool: the eight devices are virtual):
    JAX_PLATFORMS=cpu python scripts/mesh_smoke.py [--tp-size 4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Must land before jax initializes (same trick as tests/conftest.py).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bert_config(tp_size: int):
    from colearn_federated_learning_tpu.utils.config import (
        DataConfig, ExperimentConfig, FedConfig, ModelConfig, RunConfig,
    )

    return ExperimentConfig(
        data=DataConfig(dataset="agnews_tiny", num_clients=8,
                        partition="iid", max_examples_per_client=8),
        model=ModelConfig(name="bert", num_classes=4, width=32, depth=2,
                          num_heads=4, seq_len=64, vocab_size=2000),
        fed=FedConfig(strategy="fedavg", rounds=1, cohort_size=0,
                      local_steps=1, batch_size=4, lr=0.05, momentum=0.9),
        run=RunConfig(name="mesh_smoke", backend="cpu", seed=0,
                      tp_size=tp_size),
    )


def run_smoke(tp_size: int, out_path: str) -> int:
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from colearn_federated_learning_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    from colearn_federated_learning_tpu.comm.aggregation import (
        StreamingFolder,
    )
    from colearn_federated_learning_tpu.comm.downlink import DownlinkEncoder
    from colearn_federated_learning_tpu.fed import setup as setup_lib
    from colearn_federated_learning_tpu.fed import strategies
    from colearn_federated_learning_tpu.parallel import partition

    devices = jax.devices()
    if len(devices) < tp_size:
        print(f"FAIL: need {tp_size} devices, have {len(devices)}",
              file=sys.stderr)
        return 1

    config = bert_config(tp_size)
    params = setup_lib.init_global_params(config)
    placement = partition.make_server_placement(
        params, tp_size, config.run.tp_axis, config.model.name,
        devices=devices)
    if placement is None:
        print("FAIL: make_server_placement fell back to replicated",
              file=sys.stderr)
        return 1

    rows = []

    # Replicated layout: full server state on every chip of the SAME mesh.
    rep_specs = jax.tree.map(lambda _: P(), params)
    replicated = partition.shard_tree(params, rep_specs, placement.mesh)
    rep_state = strategies.init_server_state(replicated, config.fed)
    rep_bytes = partition.bytes_per_chip(rep_state)
    rows.append({
        "bench": "mesh_smoke", "mode": "replicated", "model": "bert",
        "tp_size": 1, "n_devices": len(devices),
        "server_bytes_per_chip": int(rep_bytes),
        "gather_bytes_avoided": 0, "sharded_fraction": 0.0,
    })

    sharded = placement.shard(params)
    shd_state = strategies.init_server_state(sharded, config.fed)
    shd_bytes = partition.bytes_per_chip(shd_state)
    avoided = partition.tree_gather_avoided(sharded)
    rows.append({
        "bench": "mesh_smoke", "mode": "sharded", "model": "bert",
        "tp_size": tp_size, "n_devices": len(devices),
        "server_bytes_per_chip": int(shd_bytes),
        "gather_bytes_avoided": int(avoided),
        "sharded_fraction": round(placement.sharded_fraction(), 4),
    })

    # Self-check 1: sharded fold == replicated fold, bitwise.
    shapes = placement.shapes_tree()
    order = [str(i) for i in range(4)]
    rep_fold = StreamingFolder(shapes, order=order)
    shd_fold = StreamingFolder(shapes, order=order, placement=placement)
    for i in order:
        rng = np.random.default_rng(40 + int(i))
        delta = jax.tree.map(
            lambda w: rng.standard_normal(np.shape(w)).astype(w.dtype),
            shapes)
        meta = {"client_id": i, "weight": 1.0 + 0.5 * int(i),
                "mean_loss": 0.1}
        rep_fold.add(dict(meta), delta)
        shd_fold.add(dict(meta), delta)
    m_rep, w_rep, _ = rep_fold.mean()
    m_shd, w_shd, _ = shd_fold.mean()
    host_shd = partition.host_tree(m_shd)
    fold_ok = w_rep == w_shd and all(
        np.asarray(a).tobytes() == np.asarray(b).tobytes()
        for a, b in zip(jax.tree.leaves(m_rep), jax.tree.leaves(host_shd)))

    # Self-check 2: sharded downlink frame == gathered frame, bytewise.
    host = partition.host_tree(sharded)
    body_rep, _, _ = DownlinkEncoder("none").encode_round(1, host)
    body_shd, _, _ = DownlinkEncoder("none").encode_round(1, sharded)
    frame_ok = bytes(body_rep) == bytes(body_shd)

    ratio = shd_bytes / max(rep_bytes, 1)
    rows.append({
        "bench": "mesh_smoke", "mode": "compare", "model": "bert",
        "tp_size": tp_size, "n_devices": len(devices),
        "hbm_ratio_sharded_over_replicated": round(ratio, 4),
        "gather_bytes_avoided": int(avoided),
        "fold_bitwise_ok": bool(fold_ok),
        "frame_bytes_ok": bool(frame_ok),
    })

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        for row in rows:
            print(json.dumps(row))
            f.write(json.dumps(row) + "\n")
    print(f"wrote {len(rows)} rows to {out_path}")

    if not fold_ok:
        print("FAIL: sharded fold is not bitwise identical to replicated",
              file=sys.stderr)
        return 1
    if not frame_ok:
        print("FAIL: sharded downlink frame differs from gathered frame",
              file=sys.stderr)
        return 1
    if not shd_bytes < rep_bytes:
        print(f"FAIL: sharded per-chip bytes {shd_bytes} not below "
              f"replicated {rep_bytes}", file=sys.stderr)
        return 1
    if avoided <= 0:
        print("FAIL: sharded layout avoided no gather bytes",
              file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp-size", type=int, default=4)
    ap.add_argument("--out", default=os.path.join(
        _REPO, "results", "mesh_bench.jsonl"))
    args = ap.parse_args(argv)
    return run_smoke(args.tp_size, args.out)


if __name__ == "__main__":
    raise SystemExit(main())
