"""The parity probe on the CPU at small widths: both references agree
with the program, and a program with one term removed does not."""

import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from benchmarks.harness import probe
from benchmarks.harness.spec import Bench
from benchmarks.traffic import generate
from colearn_federated_learning_tpu.models import registry
from colearn_federated_learning_tpu.models.attention import MultiHeadAttention
from colearn_federated_learning_tpu.utils.config import ModelConfig

SMALL = {
    # Two channels to a GroupNorm group, as at width 64.
    "cifar10_cnn": ({"width": 64}, {}),
    "agnews_bert_base": ({"width": 64, "depth": 3, "num_heads": 4,
                          "seq_len": 32, "vocab_size": 1400},
                         {"input_shape": [32], "vocab_size": 1400}),
}


class Intercepted:
    """The program's model with one flax module's call rewritten."""

    def __init__(self, model, interceptor):
        self.model, self.interceptor = model, interceptor

    def apply(self, *args, **kwargs):
        with nn.intercept_methods(self.interceptor):
            return self.model.apply(*args, **kwargs)


def drop_group_norm(next_fun, args, kwargs, context):
    if (isinstance(context.module, nn.GroupNorm)
            and context.module.name == "GroupNorm_3"
            and context.method_name == "__call__"):
        return args[0]
    return next_fun(*args, **kwargs)


def drop_residual(next_fun, args, kwargs, context):
    """x + attention(x) becomes attention(x) in the second block."""
    if (isinstance(context.module, MultiHeadAttention)
            and "TransformerBlock_1" in context.module.path
            and context.method_name == "__call__"):
        return next_fun(*args, **kwargs) - args[0]
    return next_fun(*args, **kwargs)


def stand_in(name: str, interceptor=None):
    """What ``probe.parity`` reads of a learner: its model, its seeded
    weights and its data."""
    bench = Bench(tiny.REPO)
    doc = bench.config(name)
    doc["experiment"]["model"].update(SMALL[name][0])
    doc["dataset"].update(SMALL[name][1], n_train=64)
    data = generate.dataset(doc, {"cohort": 1, "eval_every": 1, "holdout": 8},
                            seed=3)
    model = registry.build_model(ModelConfig(**doc["experiment"]["model"]))
    params = registry.init_params(
        model, jnp.asarray(data.x_train[:4]), jax.random.PRNGKey(3))
    if interceptor is not None:
        model = Intercepted(model, interceptor)
    learner = types.SimpleNamespace(model=model, params=params, dataset=data)
    return learner, bench.module("reference", doc["family"]), doc


@pytest.mark.parametrize("name", sorted(SMALL))
def test_reference_agrees_with_the_program(name):
    got = probe.parity(*stand_in(name))
    assert got["ok"], got
    assert got["loss_rel_gap"] < 2e-3


@pytest.mark.parametrize("name,interceptor", [
    ("cifar10_cnn", drop_group_norm), ("agnews_bert_base", drop_residual)])
def test_a_removed_term_fails(name, interceptor):
    got = probe.parity(*stand_in(name, interceptor))
    assert not got["ok"], got


def test_float32_program_is_close_to_the_reference():
    """In float32 the two sides differ by rounding alone, far inside the
    tolerance: what the tolerance allows for is bf16, not the reference."""
    learner, reference, doc = stand_in("agnews_bert_base")
    doc["experiment"]["model"]["dtype"] = "float32"
    learner.model = registry.build_model(
        ModelConfig(**doc["experiment"]["model"]))
    got = probe.parity(learner, reference, doc)
    assert got["loss_rel_gap"] < 1e-5 and got["grad_rel_gap_max"] < 1e-4


def test_a_nan_gradient_is_not_ok():
    tolerance = {"loss": 1e-2, "grad_leaf": 1e-1, "grad_floor": 1e-2}
    ref_sq = {"a": np.float32(3.0), "b": np.float32(3.0)}
    got = probe.compare(1.0, 1.0, {"a": np.float32(0.0), "b": np.float32(0.0)},
                        ref_sq, tolerance)
    assert got["ok"] and got["grad_rel_gap_max"] == 0.0
    got = probe.compare(1.0, 1.0, {"a": np.float32(0.0),
                                   "b": np.float32(np.nan)},
                        ref_sq, tolerance)
    assert not got["ok"] and got["grad_worst_leaf"] == "['b']"
