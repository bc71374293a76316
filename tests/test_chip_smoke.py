"""chip_smoke.py rehearsed on the CPU at a tiny size, so the script cannot
rot between chip runs: the same phase functions, the same ``cli.main``
path, a 2-layer BERT and the flash kernels in interpret mode."""

import dataclasses

import pytest

import chip_smoke
from colearn_federated_learning_tpu.utils import config as config_lib

TINY = ["--backend", "cpu", "--num-clients", "8", "--cohort-size", "4",
        "--local-steps", "2", "--batch-size", "4"]


@pytest.fixture(scope="module")
def bert_runs():
    """One dense and one flash run of a 2-layer BERT through the phase
    functions (module-scoped: each is a full trace and compile)."""
    base = config_lib.CONFIGS["agnews_bert_fedavg"]
    tiny = ["--config", "smoke_tiny_bert", *TINY]
    config_lib.CONFIGS["smoke_tiny_bert"] = base.replace(
        data=dataclasses.replace(base.data, dataset="agnews_tiny"),
        model=dataclasses.replace(base.model, width=32, depth=2, num_heads=2,
                                  seq_len=64, vocab_size=2000),
    )
    try:
        dense = chip_smoke.run_train([*tiny, "--rounds", "2"])
        with pytest.warns(UserWarning, match="dense attention is measured"):
            flash = chip_smoke.run_train(
                [*tiny, "--rounds", "1", "--attn-impl", "flash"])
    finally:
        del config_lib.CONFIGS["smoke_tiny_bert"]
    return dense, flash


def test_cnn_phase_on_cpu():
    run = chip_smoke.run_train(
        ["--config", "cifar10_cnn_fedavg", "--dataset", "cifar10_tiny",
         "--width", "8", "--rounds", "1", *TINY])
    rep = chip_smoke.check_phase("cnn", run, "cpu")
    assert rep["platform"] == "cpu" and rep["n_chips"] >= 1
    # A run that landed on another device than the one asked for fails.
    with pytest.raises(chip_smoke.SmokeFailure, match="ran on 'cpu'"):
        chip_smoke.check_phase("cnn", run, "tpu")


def test_bert_dense_and_flash_phases_on_cpu(bert_runs):
    dense, flash = bert_runs
    rep = chip_smoke.check_phase("bert_dense", dense, "cpu")
    assert rep["rounds"] == 2 and rep["steady_rounds_per_sec"] > 0
    chip_smoke.check_phase("bert_flash", flash, "cpu")
    assert flash["flash_interpret"] > 0 and flash["flash_mosaic"] == 0
    gap = chip_smoke.check_flash(dense, flash, interpret=True)
    assert gap <= chip_smoke.FLASH_VS_DENSE_RTOL
    # On the chip the same counters must read the other way round.
    with pytest.raises(chip_smoke.SmokeFailure, match="interpreted"):
        chip_smoke.check_flash(dense, flash, interpret=False)


def test_check_phase_rejects_bad_runs(bert_runs):
    run = bert_runs[0]

    def broken(**rec_update):
        recs = [dict(r) for r in run["records"]]
        recs[-1].update(rec_update)
        return {**run, "records": recs}

    for bad, why in [
        (broken(train_loss=float("nan")), "train_loss"),
        (broken(recompiles=1), "recompiled"),
        (broken(eval_loss=float("inf")), "eval_loss"),
        ({**run, "round_compiles": 2}, "signatures"),
    ]:
        with pytest.raises(chip_smoke.SmokeFailure, match=why):
            chip_smoke.check_phase("bad", bad, "cpu")
    # hbm_used_gb is only owed by a device that reports memory.
    with pytest.raises(chip_smoke.SmokeFailure, match="hbm_used_gb"):
        chip_smoke.check_phase(
            "bad", {**run, "summary": {**run["summary"], "platform": "tpu"}},
            "tpu")


def test_phases_are_the_full_size_commands(monkeypatch):
    """What the script sends to the chip: each phase's arguments go
    through the real parser to the registered full-size configuration."""
    from colearn_federated_learning_tpu import cli
    from colearn_federated_learning_tpu.fed import engine

    seen = {}

    class Captured(Exception):
        pass

    def capture(config, dataset=None):
        seen["config"] = config
        raise Captured

    monkeypatch.setattr(engine.FederatedLearner, "from_config", capture)
    want = {"cnn": ("cnn", 64, 20, "dense"),
            "bert_dense": ("bert", 768, 9, "dense"),
            "bert_flash": ("bert", 768, 9, "flash")}
    for name, argv in chip_smoke.PHASES:
        with pytest.raises(Captured):
            cli.main(["train", *argv, "--backend", "tpu"])
        c = seen["config"]
        assert (c.model.name, c.model.width, c.fed.cohort_size,
                c.model.attn_impl) == want[name]
        assert c.run.backend == "tpu"
    assert seen["config"].model.depth == 12


def test_script_has_no_cpu_mode(capsys):
    # This process is held to the CPU, as `JAX_PLATFORMS=cpu python
    # chip_smoke.py` is: non-zero, and no result line on stdout.
    assert chip_smoke.main() == 1
    assert capsys.readouterr().out == ""
