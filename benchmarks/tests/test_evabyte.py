"""The ``evabyte`` family's own files: its dataset kind, its operation
counts, its plain reference against the program at a small size, and the
control.  The cell's rehearsal end to end is ``test_cells_cpu.py``'s, which
finds every cell of ``BENCHMARK.json`` by name."""

import hashlib
import json
import math
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import controls
import tiny
from benchmarks.flops import evabyte as flops
from benchmarks.harness import probe
from benchmarks.harness.spec import Bench
from benchmarks.layer_metrics import _eva
from benchmarks.traffic import generate
from colearn_federated_learning_tpu.models import registry
from colearn_federated_learning_tpu.utils.config import ModelConfig

# What the CPU cannot give (``tiny.NOT_ON_CPU``): these two read the device
# plane of a trace.  ``test_cells_cpu.py`` takes the set from ``tiny`` when
# its tests run, after every test module has been imported.
tiny.NOT_ON_CPU |= {"eva_attention_ms_per_round", "eva_attention_roofline"}

BENCH = Bench(tiny.REPO)
CONFIG = "evabyte_4of32"
with open(os.path.join(os.path.dirname(__file__), "data",
                       "dataset_digests_evabyte.json")) as f:
    DIGESTS = json.load(f)


def tiny_doc() -> dict:
    doc = BENCH.config(CONFIG)
    tiny.shrink_config(doc)
    return doc


def test_the_configuration_keeps_the_published_sizes():
    """Every number of the published config stands in the file under its
    own key, but for the keys ``reduced`` names."""
    doc = BENCH.config(CONFIG)
    assert doc["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    for key, value in doc["published"].items():
        if key not in doc["reduced"]:
            assert doc[key] == value, key
    model = doc["experiment"]["model"]
    assert (model["width"], model["num_heads"], model["ffn_dim"]) == (
        doc["hidden_size"], doc["num_attention_heads"],
        doc["intermediate_size"])
    assert (model["depth"], model["seq_len"]) == (
        doc["num_hidden_layers"], doc["max_position_embeddings"])
    assert (model["window_size"], model["chunk_size"],
            model["num_pred_heads"], model["vocab_size"]) == (
        doc["window_size"], doc["chunk_size"], doc["num_pred_heads"],
        doc["vocab_size"])
    assert model["rope_theta"] == doc["rope_theta"]
    assert doc["dataset"]["input_shape"] == [model["seq_len"]]
    assert doc["dataset"]["horizon"] == model["num_pred_heads"]


def test_bytes_kind_labels_every_position_for_every_head():
    doc = tiny_doc()
    data = generate.dataset(BENCH, doc, DIGESTS["traffic"], seed=11)
    x, y = data.x_train, data.y_train
    assert x.shape == (16, 128) and y.shape == (16, 128, 8)
    assert data.x_test.shape == (8, 128) and x.dtype == y.dtype == np.int32
    for j in range(8):
        np.testing.assert_array_equal(y[:, :-1 - j, j], x[:, 1 + j:])
    assert set(np.unique(x)) <= {3, *range(64, 320)}
    # The source is the same for every seed: a byte's successors are among
    # the same four.
    other = generate.dataset(BENCH, doc, DIGESTS["traffic"], seed=12)
    assert (other.x_train != x).any()

    def successors(a):
        pairs = np.stack([a[:, :-1].ravel(), a[:, 1:].ravel()], axis=1)
        pairs = pairs[(pairs >= 64).all(axis=1)]
        return {tuple(p) for p in pairs}

    assert len(successors(x) | successors(other.x_train)) <= 256 * 4


@pytest.mark.parametrize("seed", sorted(DIGESTS["sha256"]))
def test_bytes_kind_splits_do_not_move(seed):
    data = generate.dataset(BENCH, tiny_doc(), DIGESTS["traffic"], int(seed))
    assert {split: hashlib.sha256(
        getattr(data, split).tobytes()).hexdigest()
        for split in ("x_train", "y_train", "x_test", "y_test")
    } == DIGESTS["sha256"][seed]


def test_flops_at_the_tiny_size():
    doc = tiny_doc()
    model, dataset = doc["experiment"]["model"], doc["dataset"]
    # By hand: 4 windows of 32, 8 chunks a window.
    pairs = 4 * (32 * 33 // 2) + 8 * 32 * (0 + 1 + 2 + 3)
    assert pairs == 3648 == flops.admitted_pairs(model, dataset)
    per_layer = (128 * (4 * 64 * 64 + 3 * 64 * 160)    # projections, ffn
                 + 2 * 64 * pairs                      # scores, values
                 + 4 * 64 * (128 - 32))                # the summaries seen
    macs = 2 * per_layer + 128 * 64 * 8 * 320
    assert macs == 34_013_184
    assert flops.forward_flops(model, dataset) == 2 * macs
    assert flops.train_flops(model, dataset) == 6 * macs
    assert flops.attention_flops(model, dataset, train=False) == (
        2 * 2 * 2 * 64 * pairs)
    assert flops.attention_flops(model, dataset, train=True) == (
        3 * flops.attention_flops(model, dataset, train=False))


def test_flops_at_the_published_widths():
    doc = BENCH.config(CONFIG)
    model, dataset = doc["experiment"]["model"], doc["dataset"]
    pairs = 8 * (2048 * 2049 // 2) + 128 * 2048 * 28
    assert pairs == 24_125_440 == flops.admitted_pairs(model, dataset)
    # A query sees at most its window and 7 windows' summaries.
    assert 2048 + 7 * 128 == 2944
    per_token_layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert per_token_layer == 202_375_168
    macs = (4 * (16384 * per_token_layer + 2 * 4096 * pairs
                 + 4 * 4096 * (16384 - 2048))
            + 16384 * 4096 * 2560)
    assert flops.forward_flops(model, dataset) == 2 * macs
    # About 6 x 820 M matrix parameters x 16,384 tokens, and attention.
    assert flops.train_flops(model, dataset) == pytest.approx(85.4e12,
                                                              rel=0.005)
    kernel = flops.attention_flops(model, dataset, train=True)
    assert kernel == pytest.approx(4.74e12, rel=0.005)
    moved = flops.attention_bytes(model, dataset, train=True)
    # Keys and values with the summaries every window is handed: 8 x 2,944
    # rows of 4,096, read twice forward and four times backward.
    keys = 8 * 2944 * 4096 * 2
    queries = 16384 * 4096 * 2
    sums = 16384 * 32 * 4
    assert moved == 4 * (6 * queries + 6 * keys + 3 * sums)
    # The operations bound it on the v5e: 24.1 ms against 9.6 ms.
    assert kernel / 197e12 > 2 * moved / 819e9


def test_kernel_names_are_told_from_other_operations():
    assert _eva.is_kernel("flash_fwd.72 bf16[256,3072,128]")
    assert _eva.is_kernel("flash_dkv.39 bf16[256,3072,128]")
    assert _eva.is_kernel("transpose_jvp_flash_dq__.1 bf16[256,2048,128]")
    assert not _eva.is_kernel("fusion.12 bf16[1,16384,11008]")
    assert not _eva.is_kernel("custom-call.3 f32[8,128]")


# A size at which the CPU runs the probe in seconds and the attention is
# not uniform: heads of 16, three windows of 32.
SMALL_MODEL = {"width": 64, "depth": 2, "num_heads": 4, "seq_len": 96,
               "ffn_dim": 160, "window_size": 32, "chunk_size": 4}


class Intercepted:
    """The program's model with one flax module's call rewritten."""

    def __init__(self, model, interceptor):
        self.model, self.interceptor = model, interceptor

    def apply(self, *args, **kwargs):
        with nn.intercept_methods(self.interceptor):
            return self.model.apply(*args, **kwargs)


def drop_ffn_norm(next_fun, args, kwargs, context):
    """The feed-forward of the second block reads the stream unnormed."""
    if (context.module.name == "ffn_norm" and "block_1" in context.module.path
            and context.method_name == "__call__"):
        return args[0]
    return next_fun(*args, **kwargs)


def stand_in(dtype="bfloat16", interceptor=None, scale=6.0):
    """What ``probe.parity`` reads of a learner: its model, its seeded
    weights (enlarged, so that the softmaxes are not flat at this width)
    and its data."""
    import types

    doc = BENCH.config(CONFIG)
    doc["experiment"]["model"].update(SMALL_MODEL, dtype=dtype)
    doc["dataset"].update(input_shape=[96], n_train=8)
    data = generate.dataset(
        BENCH, doc, {"cohort": 1, "eval_every": 1, "holdout": 2}, seed=3)
    model = registry.build_model(ModelConfig(**doc["experiment"]["model"]))
    params = registry.init_params(
        model, jnp.asarray(data.x_train[:1]), jax.random.PRNGKey(3))
    params = jax.tree.map(lambda a: a * scale, params)
    if interceptor is not None:
        model = Intercepted(model, interceptor)
    learner = types.SimpleNamespace(model=model, params=params, dataset=data)
    return learner, BENCH.module("reference", doc["family"]), doc


def test_reference_agrees_with_the_program():
    got = probe.parity(*stand_in())
    assert got["ok"] and got["batch"] == 1, got
    assert got["ref_loss"] == pytest.approx(math.log(320), rel=0.1)


def test_float32_program_is_close_to_the_reference():
    """In float32 the two sides differ by rounding alone: what the
    tolerance allows for is bf16, not the reference."""
    got = probe.parity(*stand_in(dtype="float32"))
    assert got["loss_rel_gap"] < 1e-5 and got["grad_rel_gap_max"] < 1e-4, got


def test_a_removed_term_fails():
    got = probe.parity(*stand_in(interceptor=drop_ffn_norm))
    assert not got["ok"], got


def test_the_control_fails():
    """The reference with its forward products in fp8 in the program's
    place (``controls.py``): the gradient's number says so (the readings
    on the chip at full size: PERF.md section 6)."""
    learner, reference, doc = stand_in()
    control = controls.in_the_programs_place(
        learner, reference, doc["experiment"]["model"], controls.fp8())
    got = probe.parity(control, reference, doc)
    assert not got["ok"], got
    assert got["grad_rel_gap_max"] > reference.TOLERANCE["grad_leaf"]
