"""Cross-silo file federation (fed/offline.py) and the `colearn` CLI."""

import dataclasses
import json

import numpy as np
import pytest

from colearn_federated_learning_tpu import cli
from colearn_federated_learning_tpu.utils import serialization
from tests.test_engine import tiny_config


def test_pytree_npz_roundtrip(tmp_path):
    tree = {"a": {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
                  "b": np.ones(4, np.int32)},
            "c": np.float32(2.5)}
    path = str(tmp_path / "t.npz")
    serialization.save_pytree_npz(path, tree, meta={"round": 3})
    got, meta = serialization.load_pytree_npz(path)
    assert meta["round"] == 3
    np.testing.assert_array_equal(got["a"]["w"], tree["a"]["w"])
    np.testing.assert_array_equal(got["a"]["b"], tree["a"]["b"])
    np.testing.assert_array_equal(got["c"], tree["c"])
    # bytes plane matches the file plane
    data = serialization.pytree_to_bytes(tree, {"round": 3})
    got2, meta2 = serialization.bytes_to_pytree(data)
    assert meta2 == meta
    np.testing.assert_array_equal(got2["a"]["w"], tree["a"]["w"])


def test_offline_round_improves_and_matches_roles(tmp_path):
    """init → N client updates → aggregate → eval: the full cross-silo flow
    through the CLI entrypoints (`colearn train --role client`,
    `colearn aggregate`, BASELINE.json north_star)."""
    from colearn_federated_learning_tpu.fed import offline

    cfg = tiny_config(rounds=1)
    g0 = str(tmp_path / "g0.npz")
    offline.init_global_model(cfg, g0)

    base = offline.evaluate_global(cfg, g0)

    updates = []
    for cid in range(4):
        out = str(tmp_path / f"u{cid}.npz")
        stats = offline.client_update(cfg, cid, g0, out)
        assert np.isfinite(stats["mean_loss"])
        updates.append(out)

    g1 = str(tmp_path / "g1.npz")
    agg = offline.aggregate_updates(cfg, g0, updates, g1)
    assert agg["round"] == 1 and agg["num_updates"] == 4

    after = offline.evaluate_global(cfg, g1)
    assert after["eval_acc"] >= base["eval_acc"]  # one round of 4/10 silos


def test_cli_configs_and_train(tmp_path, capsys):
    assert cli.main(["configs"]) == 0
    out = capsys.readouterr().out
    assert "mnist_mlp_fedavg" in out and "femnist_vit_cross_silo" in out

    log = str(tmp_path / "log.jsonl")
    rc = cli.main([
        "train", "--config", "mnist_mlp_fedavg", "--dataset", "mnist_tiny",
        "--rounds", "2", "--backend", "cpu", "--log-file", log,
    ])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["rounds"] == 2 and "rounds_per_sec" in summary
    assert len(open(log).readlines()) == 2


def test_cli_cross_silo_flow(tmp_path, capsys):
    g0 = str(tmp_path / "g.npz")
    args = ["--config", "mnist_mlp_fedavg", "--dataset", "mnist_tiny"]
    assert cli.main(["init", *args, "--out", g0]) == 0
    u0 = str(tmp_path / "u0.npz")
    assert cli.main(["train", *args, "--role", "client", "--client-id", "0",
                     "--global-model", g0, "--out", u0]) == 0
    g1 = str(tmp_path / "g1.npz")
    assert cli.main(["aggregate", *args, "--global-model", g0,
                     "--updates", u0, "--out", g1]) == 0
    assert cli.main(["eval", *args, "--global-model", g1]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["round"] == 1 and 0.0 <= rec["eval_acc"] <= 1.0


def test_cli_missing_client_args_errors():
    rc = cli.main(["train", "--role", "client"])
    assert rc == 2


def test_aggregate_rejects_stale_update(tmp_path):
    from colearn_federated_learning_tpu.fed import offline

    cfg = tiny_config()
    g0 = str(tmp_path / "g0.npz")
    offline.init_global_model(cfg, g0)
    u0 = str(tmp_path / "u0.npz")
    offline.client_update(cfg, 0, g0, u0)
    g1 = str(tmp_path / "g1.npz")
    offline.aggregate_updates(cfg, g0, [u0], g1)
    # u0 was computed against round 0; folding it into the round-1 model
    # must fail loudly, not corrupt the model.
    with pytest.raises(ValueError, match="stale update"):
        offline.aggregate_updates(cfg, g1, [u0], str(tmp_path / "g2.npz"))


def test_serialization_rejects_list_nodes(tmp_path):
    with pytest.raises(TypeError, match="list"):
        serialization.save_pytree_npz(
            str(tmp_path / "x.npz"), {"layers": [np.zeros(3), np.zeros(3)]}
        )
