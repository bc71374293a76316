"""The ``nemotron_h`` family's own files: its configuration, its dataset
kind, its operation counts against a count by hand, how its parts are told
on a trace, its plain reference against the program at a small size, and
the control.  The cell's rehearsal end to end is ``test_cells_cpu.py``'s,
which finds every cell of ``BENCHMARK.json`` by name."""

import hashlib
import json
import math
import os
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import controls
import tiny
from benchmarks.flops import nemotron_h as flops
from benchmarks.harness import probe
from benchmarks.harness.spec import Bench
from benchmarks.layer_metrics import _hybrid
from benchmarks.traffic import generate
from colearn_federated_learning_tpu.models import registry
from colearn_federated_learning_tpu.utils.config import ModelConfig

# What the CPU cannot give (``tiny.NOT_ON_CPU``): these five read the device
# plane of a trace.  ``test_cells_cpu.py`` takes the set from ``tiny`` when
# its tests run, after every test module has been imported.
tiny.NOT_ON_CPU |= {"moe_routed_ms_per_round", "ssd_ms_per_round",
                    "ssd_roofline", "gqa_attention_ms_per_round",
                    "gqa_attention_roofline"}

BENCH = Bench(tiny.REPO)
CONFIG = "nemotron3_super_11of88"
CELL = "nemotron_hybrid_seq16k"
with open(os.path.join(os.path.dirname(__file__), "data",
                       "dataset_digests_nemotron_h.json")) as f:
    DIGESTS = json.load(f)


def tiny_doc() -> dict:
    doc = BENCH.config(CONFIG)
    tiny.shrink_config(doc)
    return doc


def test_the_configuration_keeps_the_published_widths():
    """Every key of the published config stands in the file under its own
    name and with its published value, but for the keys ``reduced`` names,
    and none of those is a width; the experiment's sizes are the file's."""
    doc = BENCH.config(CONFIG)
    entry = BENCH._entry("configs", CONFIG)
    assert doc["reduced"] == entry["reduced"]
    for key, value in doc["published"].items():
        if key in doc["reduced"]:
            assert doc[key] != value and key in doc["reduced_note"], key
        else:
            assert doc[key] == value, key
    widths = ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
              "moe_latent_size", "moe_intermediate_size", "expand",
              "moe_shared_expert_intermediate_size", "num_experts_per_tok",
              "intermediate_size")
    assert not set(widths) & set(doc["reduced"])
    assert doc["published"]["hybrid_override_pattern"].startswith(
        doc["hybrid_override_pattern"])
    model = doc["experiment"]["model"]
    assert model["layer_pattern"] == doc["hybrid_override_pattern"]
    assert len(model["layer_pattern"]) == doc["num_hidden_layers"] == 11
    assert [model["layer_pattern"].count(k) for k in "ME*"] == [5, 5, 1]
    assert (model["width"], model["head_dim"], model["mamba_head_dim"],
            model["ssm_state_size"], model["conv_kernel"],
            model["chunk_size"]) == (
        doc["hidden_size"], doc["head_dim"], doc["mamba_head_dim"],
        doc["ssm_state_size"], doc["conv_kernel"], doc["chunk_size"])
    assert (model["mamba_heads"], model["mamba_groups"], model["num_heads"],
            model["num_kv_heads"]) == (
        doc["mamba_num_heads"], doc["n_groups"], doc["num_attention_heads"],
        doc["num_key_value_heads"])
    assert model["mamba_heads"] * model["mamba_head_dim"] == (
        doc["expand"] * doc["hidden_size"] // 4)
    # The router keeps its published width and its experts a token.
    assert (model["num_experts"], model["experts_per_token"],
            model["experts_held"], model["routed_scale"]) == (
        doc["published"]["n_routed_experts"], doc["num_experts_per_tok"],
        doc["n_routed_experts"], doc["routed_scaling_factor"])
    assert (model["latent_dim"], model["expert_dim"],
            model["shared_expert_dim"]) == (
        doc["moe_latent_size"], doc["moe_intermediate_size"],
        doc["moe_shared_expert_intermediate_size"])
    assert model["vocab_size"] == model["num_classes"] == doc["vocab_size"]
    assert doc["dataset"]["input_shape"] == [model["seq_len"]] == [
        doc["max_position_embeddings"]]
    assert doc["dataset"]["vocab_size"] == doc["vocab_size"]
    # The share: 64 chips a layer, the mixers 4 ways, the vocabulary 8.
    pub = doc["published"]
    assert pub["n_routed_experts"] // doc["n_routed_experts"] == 64
    assert pub["mamba_num_heads"] // doc["mamba_num_heads"] == 4
    assert pub["num_attention_heads"] // doc["num_attention_heads"] == 4
    assert pub["vocab_size"] // doc["vocab_size"] == 8
    for word in ("8 pipeline stages", "64 chips", "4 ways", "8 ways"):
        assert word in doc["deployment"], word
    assert doc["departures"] and {"rotary", "init", "optimizer", "data"} <= (
        set(doc["assumed"]))


def test_the_shipped_experiment_is_the_configurations():
    """``colearn train --config nemotron_h_fedavg`` builds the model the
    cell measures."""
    from colearn_federated_learning_tpu.utils.config import get_config

    doc = BENCH.config(CONFIG)
    shipped = get_config("nemotron_h_fedavg")
    assert shipped.model == ModelConfig(**doc["experiment"]["model"])
    traffic = BENCH.traffic(BENCH.workload(CELL)["traffic"])
    cell = generate.experiment_config(doc, traffic, seed=0)
    assert (cell.fed.cohort_size, cell.fed.local_steps, cell.fed.batch_size,
            cell.fed.lr, cell.fed.momentum) == (
        shipped.fed.cohort_size, shipped.fed.local_steps,
        shipped.fed.batch_size, shipped.fed.lr, shipped.fed.momentum)
    model = registry.build_model(shipped.model)
    ids = jnp.zeros((1, shipped.model.seq_len), jnp.int32)
    shapes = jax.eval_shape(
        lambda: registry.init_params(model, ids, jax.random.PRNGKey(0)))
    count = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    # 773.6 M parameters: 9.28 GB at 12 B each on fit()'s path.
    assert count == 773_582_304 and count * 12 == pytest.approx(9.28e9,
                                                                rel=1e-3)


def test_tokens_kind_labels_every_position():
    doc = tiny_doc()
    data = generate.dataset(BENCH, doc, DIGESTS["traffic"], seed=11)
    x, y = data.x_train, data.y_train
    assert x.shape == y.shape == (16, 64) and data.x_test.shape == (8, 64)
    assert x.dtype == y.dtype == np.int32
    np.testing.assert_array_equal(y[:, :-1], x[:, 1:])
    assert 0 <= x.min() and x.max() < 96 and (x == 0).any()
    # The source is the same for every seed: a word's successors are among
    # the same four.
    other = generate.dataset(BENCH, doc, DIGESTS["traffic"], seed=12)
    assert (other.x_train != x).any()

    def successors(a, b):
        pairs = np.stack([a.ravel(), b.ravel()], axis=1)
        return {tuple(p) for p in pairs[(pairs > 0).all(axis=1)]}

    assert len(successors(x, y) | successors(
        other.x_train, other.y_train)) <= 95 * 4


@pytest.mark.parametrize("seed", sorted(DIGESTS["sha256"]))
def test_tokens_kind_splits_do_not_move(seed):
    data = generate.dataset(BENCH, tiny_doc(), DIGESTS["traffic"], int(seed))
    assert {split: hashlib.sha256(
        getattr(data, split).tobytes()).hexdigest()
        for split in ("x_train", "y_train", "x_test", "y_test")
    } == DIGESTS["sha256"][seed]


def test_flops_at_the_tiny_size():
    doc = tiny_doc()
    model, dataset = doc["experiment"]["model"], doc["dataset"]
    assert model["layer_pattern"] == "ME*E"
    # By hand: 64 tokens of width 32 in 4 chunks of 16; 4 heads of 8 in 2
    # groups, state 8; 6 of 16 experts a token, 4 held; 4 query heads of 8
    # on 2 key/value heads.
    scan = (4 * (16 * 17 // 2) * (2 * 8 + 4 * 8)      # C B^T, scores x
            + 2 * 64 * 4 * 8 * 8                      # chunk states, reads
            + 4 * 4 * 8 * 8)                          # the carry
    assert scan == 59_904 == flops.scan_macs(model, dataset)
    mamba = 64 * (32 * (2 * 32 + 2 * 16 + 4) + 32 * 32 + 4 * (32 + 32)) + scan
    held = 6 * 4 / 16
    assert held == flops.held_choices_per_token(model) == 1.5
    moe = 64 * (32 * 16 + 2 * 32 * 16 + 2 * 32 * 40 + held * 2 * 16 * 24)
    pairs = 64 * 65 // 2
    attention = 64 * 32 * (2 * 32 + 2 * 16) + 2 * 32 * pairs
    assert flops.layer_macs(model, dataset) == {
        "M": mamba, "E": moe, "*": attention}
    macs = mamba + 2 * moe + attention + 64 * 32 * 96
    assert macs == 1_544_704
    assert flops.forward_flops(model, dataset) == 2 * macs
    assert flops.train_flops(model, dataset) == 6 * macs
    assert flops.scan_flops(model, dataset, train=False) == 2 * scan
    assert flops.scan_flops(model, dataset, train=True) == 6 * scan
    assert flops.attention_flops(model, dataset, train=False) == (
        2 * 2 * 32 * pairs)
    assert flops.attention_flops(model, dataset, train=True) == (
        3 * flops.attention_flops(model, dataset, train=False))
    # x, y (32 each), B, C (16 each) in bf16 and a float32 step a head.
    assert flops.scan_bytes(model, dataset, train=False) == 64 * (
        (2 * 32 + 2 * 16) * 2 + 4 * 4)
    assert flops.scan_bytes(model, dataset, train=True) == 64 * (
        (5 * 32 + 6 * 16) * 2 + 3 * 4 * 4)
    assert flops.attention_bytes(model, dataset, train=True) == 6 * 64 * (
        32 * 2 + 16 * 2) + 3 * 64 * 4 * 4


def test_flops_at_the_published_widths():
    doc = BENCH.config(CONFIG)
    model, dataset = doc["experiment"]["model"], doc["dataset"]
    per_token = {
        "M": 4096 * 4640 + 2048 * 4096 + 4 * 2560,
        "E": (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
              + 22 * 8 / 512 * 2 * 1024 * 2688),
        "*": 4096 * (2 * 1024 + 2 * 128),
    }
    scan = flops.scan_macs(model, dataset)
    # A position's scan: half a chunk of pairs, two states, a 128th carry.
    assert scan / 16384 == pytest.approx(
        64.5 * (2 * 128 + 2048) + 2 * 32 * 64 * 128 + 32 * 64, rel=1e-9)
    got = flops.layer_macs(model, dataset)
    assert got["M"] == 16384 * per_token["M"] + scan
    assert got["E"] == 16384 * per_token["E"]
    assert got["*"] == 16384 * per_token["*"] + 2 * 1024 * (
        16384 * 16385 // 2)
    forward = flops.forward_flops(model, dataset)
    # 1.03 GFLOP a token forward; E layers 55%, M 27%, the head 13%,
    # attention 5%; 50.7 TFLOP a training step.
    assert forward / 16384 == pytest.approx(1.0316e9, rel=1e-3)
    shares = {k: 2 * 5 * got[k] / forward for k in "ME"}
    assert shares["E"] == pytest.approx(0.547, abs=2e-3)
    assert shares["M"] == pytest.approx(0.272, abs=2e-3)
    assert 2 * got["*"] / forward == pytest.approx(0.051, abs=2e-3)
    assert 2 * 16384 * 4096 * 16384 / forward == pytest.approx(0.130, abs=2e-3)
    assert flops.train_flops(model, dataset) == pytest.approx(50.7e12,
                                                              rel=2e-3)
    # The routed products at the expected rows: 1.8% of the step.
    routed = 5 * 16384 * 22 * 8 / 512 * 2 * 1024 * 2688
    assert 0.01 < 2 * routed / forward < 0.02
    # The scan is bound by its bytes on the v5e (2.4 ms against 1.7 ms a
    # step), the attention kernel by its operations (8.4 against 0.3).
    assert flops.scan_bytes(model, dataset, True) / 819e9 == pytest.approx(
        2.39e-3, rel=0.01)
    assert flops.scan_flops(model, dataset, True) / 197e12 == pytest.approx(
        1.68e-3, rel=0.01)
    assert flops.attention_flops(model, dataset, True) / 197e12 == (
        pytest.approx(8.37e-3, rel=0.01))
    assert flops.attention_bytes(model, dataset, True) / 819e9 < 0.3e-3


def test_parts_are_told_from_other_operations():
    """The labels are the v5e trace's own (my chip runs, PR 33)."""
    model = BENCH.config(CONFIG)["experiment"]["model"]
    dataset = BENCH.config(CONFIG)["dataset"]
    is_scan, is_routed = (_hybrid.scan_ops(model, dataset),
                          _hybrid.routed_ops(model, dataset))
    for label in SCAN_LABELS:
        assert is_scan(label) and not is_routed(label), label
    for label in ROUTED_LABELS:
        assert is_routed(label) and not is_scan(label), label
    for label in OTHER_LABELS:
        assert not is_scan(label) and not is_routed(label), label


SCAN_LABELS = (
    "fusion.4430 f32[128,1,1,2,16,64,128]",        # the chunks' states
    "fusion.4162 f32[128,2,16,64,128]",
    "fusion.4163 f32[1,1,128,128,2,16,64]",        # dt x by chunk
    "fusion.4129 bf16[128,2,16,128,128]",          # decays and scores
    "fusion.900 f32[128,2,128,128]",               # C B^T
    "copy.4732 bf16[1,1,128,128,2,128]",           # B or C by chunk
    "copy.619 f32[128,128,2,16]",                  # the steps
)
ROUTED_LABELS = (
    "fusion.4058 f32[1,16384,512]",                # the router's scores
    "constant_dynamic-slice_fusion.31 f32[4,1,4096,22]",
    "sort.518 s32[1,90112]", "sort.470 s32[360448]",
    "fusion.4298 f32[32768,1024]", "fusion.4382 bf16[32768,1024]",
    "maximum_multiply_fusion.35 bf16[32768,2688]", "sort.157 s32[32768]",
    "ragged-dot-none.49 bf16[32768,2688]", "ragged-dot-metadata.19 s32[71]",
    "copy-done.33 f32[1,8,1024,2688]",
)
OTHER_LABELS = (
    "fusion.929 f32[16384,16384]",                 # the logits
    "flash_dkv.13 bf16[8,16384,128]",
    "fusion.4263 bf16[16384,4096]", "fusion.4050 bf16[1,1,16384,4096]",
    "fusion.4040 bf16[16384,5376]",                # the shared expert
    "copy.5284 f32[1,4096,5376]",
    "convolution_bitcast_fusion.20 bf16[1,16384,4640]",   # in_proj
    "fusion.4089 bf16[1,1,16384,2048]",
    "convolution_bitcast_fusion.81 bf16[4,4096,4096]",    # latent down
    "dynamic-slice_bitcast_fusion.126 bf16[4,1,4096,1024]",
    "copy-done.572 f32[1,4096,1024]",
    "copy.4708 f32[256,8,128,128]",
)

# A size at which the CPU runs the probe in seconds and every layer kind is
# there twice over.
SMALL_MODEL = {"width": 64, "seq_len": 96, "layer_pattern": "ME*EM",
               "num_classes": 96, "vocab_size": 96,
               "mamba_heads": 4, "mamba_head_dim": 16, "mamba_groups": 2,
               "ssm_state_size": 16, "chunk_size": 32,
               "num_experts": 32, "experts_first": 8, "experts_held": 8,
               "experts_per_token": 6, "latent_dim": 32, "expert_dim": 48,
               "shared_expert_dim": 96,
               "num_heads": 4, "num_kv_heads": 2, "head_dim": 16}


class Intercepted:
    """The program's model with one flax module's call rewritten."""

    def __init__(self, model, interceptor):
        self.model, self.interceptor = model, interceptor

    def apply(self, *args, **kwargs):
        with nn.intercept_methods(self.interceptor):
            return self.model.apply(*args, **kwargs)


def drop_the_shared_expert(next_fun, args, kwargs, context):
    """The last mixture layer leaves its shared expert out."""
    if (context.method_name == "shared"
            and "layer_3" in context.module.path):
        return jnp.zeros_like(next_fun(*args, **kwargs))
    return next_fun(*args, **kwargs)


def stand_in(dtype="bfloat16", interceptor=None, scale=2.0):
    """What ``probe.parity`` reads of a learner: its model, its seeded
    weights (the matrices enlarged, so that the mixers weigh against the
    embedding at this width) and its data."""
    doc = BENCH.config(CONFIG)
    doc["experiment"]["model"].update(SMALL_MODEL, dtype=dtype)
    doc["dataset"].update(input_shape=[96], n_train=8, num_classes=96,
                          vocab_size=96)
    data = generate.dataset(
        BENCH, doc, {"cohort": 1, "eval_every": 1, "holdout": 2}, seed=3)
    model = registry.build_model(ModelConfig(**doc["experiment"]["model"]))
    params = registry.init_params(
        model, jnp.asarray(data.x_train[:1]), jax.random.PRNGKey(3))
    params = jax.tree.map(lambda a: a * scale if a.ndim >= 2 else a, params)
    if interceptor is not None:
        model = Intercepted(model, interceptor)
    learner = types.SimpleNamespace(model=model, params=params, dataset=data)
    return learner, BENCH.module("reference", doc["family"]), doc


def test_reference_agrees_with_the_program():
    got = probe.parity(*stand_in())
    assert got["ok"] and got["batch"] == 1, got
    assert got["ref_loss"] == pytest.approx(math.log(96), rel=0.1)


def test_float32_program_is_close_to_the_reference():
    """In float32 the two sides differ by rounding alone: what the
    tolerance allows for is bf16, not the reference."""
    got = probe.parity(*stand_in(dtype="float32"))
    assert got["loss_rel_gap"] < 1e-5 and got["grad_rel_gap_max"] < 1e-4, got


def test_a_removed_term_fails():
    got = probe.parity(*stand_in(interceptor=drop_the_shared_expert))
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
