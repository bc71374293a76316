"""Config-validation guards (utils/config.validate_experiment).

Pins VERDICT r4 weak #5: ``attn_impl="flash"`` below the measured
dense/flash crossover (~L=1k, PERF.md §5) is a user footgun — dense is
faster there — so construction warns.  The warning must fire exactly for
the below-crossover case and stay silent for dense and for long sequences,
and it must be a WARNING, not an error: the combination executes correctly
(a kernel benchmark needs to be able to run it).
"""

import dataclasses
import warnings

import pytest

from colearn_federated_learning_tpu.utils.config import (
    FLASH_SEQ_CROSSOVER,
    ModelConfig,
    get_config,
    validate_experiment,
)


def _bert_cfg(**model_kw):
    cfg = get_config("agnews_bert_fedavg")
    return cfg.replace(model=dataclasses.replace(cfg.model, **model_kw))


def test_flash_below_crossover_warns():
    cfg = _bert_cfg(attn_impl="flash", seq_len=128)
    with pytest.warns(UserWarning, match="dense attention is measured FASTER"):
        validate_experiment(cfg)


def test_flash_at_or_above_crossover_silent():
    cfg = _bert_cfg(attn_impl="flash", seq_len=FLASH_SEQ_CROSSOVER)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        validate_experiment(cfg)


def test_dense_short_seq_silent():
    cfg = _bert_cfg(attn_impl="dense", seq_len=128)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        validate_experiment(cfg)


def test_engine_init_routes_through_validation():
    # The guard must fire on the real construction path, not only when
    # called directly — a tiny MLP run with a flash-flagged model config.
    from colearn_federated_learning_tpu.fed.engine import FederatedLearner

    cfg = get_config("mnist_mlp_fedavg")
    cfg = cfg.replace(
        model=ModelConfig(name="mlp", num_classes=10, hidden_dim=8, depth=1,
                          attn_impl="flash", seq_len=128),
        data=dataclasses.replace(cfg.data, num_clients=2,
                                 max_examples_per_client=16),
    )
    with pytest.warns(UserWarning, match="dense attention is measured FASTER"):
        FederatedLearner.from_config(cfg)
