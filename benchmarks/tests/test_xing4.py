"""The ``xing4`` family's own files: its configuration, its dataset kind,
its operation counts against a count by hand, how the widened residual
path is told on a trace, its entries in ``BENCHMARK.json``, its plain
reference against the program at a small size, and the control.  The
cell's rehearsal end to end is ``test_cells_cpu.py``'s, which finds every
cell of ``BENCHMARK.json`` by name."""

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
from benchmarks.flops import xing4 as flops
from benchmarks.harness import probe
from benchmarks.harness.spec import Bench
from benchmarks.layer_metrics import _program, mhc_ms_per_round
from benchmarks.traffic import generate
from colearn_federated_learning_tpu.models import registry
from colearn_federated_learning_tpu.utils.config import ModelConfig

# What the CPU cannot give (``tiny.NOT_ON_CPU``): these three read the
# device plane of a trace.  ``test_cells_cpu.py`` takes the set from ``tiny``
# when its tests run, after every test module has been imported.
DEVICE_TRACE_METRICS = {"mla_attention_ms_per_round",
                        "mla_attention_roofline", "mhc_ms_per_round"}
tiny.NOT_ON_CPU |= DEVICE_TRACE_METRICS

BENCH = Bench(tiny.REPO)
CONFIG = "xing4_5of40"
CELL = "xing_mla_mhc_seq8k"
METRICS = DEVICE_TRACE_METRICS | {"mhc_streams", "mtp_modules"}
with open(os.path.join(os.path.dirname(__file__), "data",
                       "dataset_digests_xing4.json")) as f:
    DIGESTS = json.load(f)


def tiny_doc() -> dict:
    doc = BENCH.config(CONFIG)
    tiny.shrink_config(doc)
    return doc


def test_the_configuration_keeps_the_published_widths():
    """Every key of the published config stands in the file under its own
    name and with its published value, but for the five keys ``reduced``
    names, and none of those is a width; the experiment's sizes are the
    file's; the deployment is 8 chips a layer."""
    doc = BENCH.config(CONFIG)
    entry = BENCH._entry("configs", CONFIG)
    assert doc["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "max_position_embeddings"]
    assert entry["source"] == doc["source"]
    for key, value in doc["published"].items():
        if key in doc["reduced"]:
            assert doc[key] != value and key in doc["reduced_note"], key
        else:
            assert doc[key] == value, key
    assert set(doc["reduced"]) <= set(doc["published"])
    model = doc["experiment"]["model"]
    assert (model["width"], model["q_rank"], model["kv_rank"],
            model["nope_dim"], model["rope_dim"], model["v_dim"],
            model["ffn_dim"], model["expert_dim"],
            model["shared_expert_dim"]) == (
        3584, 768, 512, 128, 64, 128, 9216, 1024, 1024) == (
        doc["hidden_size"], doc["q_lora_rank"], doc["kv_lora_rank"],
        doc["qk_nope_head_dim"], doc["qk_rope_head_dim"], doc["v_head_dim"],
        doc["intermediate_size"], doc["moe_intermediate_size"],
        doc["moe_intermediate_size"] * doc["n_shared_experts"])
    # The router keeps its published width, its experts a token, its scale.
    assert (model["num_experts"], model["experts_per_token"],
            model["experts_held"], model["routed_scale"]) == (
        doc["published"]["n_routed_experts"], doc["num_experts_per_tok"],
        doc["n_routed_experts"], doc["routed_scaling_factor"]) == (
        64, 4, 8, 2)
    assert doc["scoring_func"] == "sigmoid" and doc["norm_topk_prob"]
    assert doc["n_group"] == doc["topk_group"] == 1
    # The residual path and yarn, number for number.
    assert (model["hc_streams"], model["sinkhorn_iters"],
            model["sinkhorn_eps"], model["res_clamp_min"],
            model["res_clamp_max"], model["norm_eps"]) == (
        doc["hc_mult"], doc["hc_sinkhorn_iters"], doc["hc_eps"],
        doc["mhc_h_res_clamp_min"], doc["mhc_h_res_clamp_max"],
        doc["rms_norm_eps"]) == (4, 20, 1e-6, -30, 30, 1e-6)
    yarn = doc["rope_scaling"]
    assert (model["rope_theta"], model["yarn_factor"],
            model["yarn_original_max"], model["yarn_beta_fast"],
            model["yarn_beta_slow"], model["yarn_mscale_all_dim"]) == (
        doc["rope_theta"], yarn["factor"],
        yarn["original_max_position_embeddings"], yarn["beta_fast"],
        yarn["beta_slow"], yarn["mscale_all_dim"]) == (
        10000, 64, 4096, 32, 1, 1)
    assert yarn["type"] == "yarn" and yarn["mscale"] == yarn["mscale_all_dim"]
    assert (model["num_heads"], model["depth"], model["dense_layers"]) == (
        doc["num_attention_heads"], doc["num_hidden_layers"],
        doc["first_k_dense_replace"]) == (32, 5, 1)
    # The published prediction module is kept in the file and left out of
    # the cut: the departures say so, with the compiler's numbers.
    assert doc["num_nextn_predict_layers"] == 1 and model["mtp_modules"] == 0
    assert "no multi-token-prediction module" in doc["departures"][0]
    assert doc["num_key_value_heads"] == doc["num_attention_heads"]
    assert model["vocab_size"] == model["num_classes"] == doc["vocab_size"]
    assert doc["dataset"]["input_shape"] == [model["seq_len"]] == [
        doc["max_position_embeddings"]] == [8192]
    assert doc["dataset"]["vocab_size"] == doc["vocab_size"] == 16384
    assert doc["dataset"]["horizon"] == 1 + model["mtp_modules"] == 1
    # The floors: a whole period (1 dense) and 4 layers after it, 8
    # experts, an eighth of the vocabulary; 8 chips share a layer.
    pub = doc["published"]
    assert doc["num_hidden_layers"] - doc["first_k_dense_replace"] >= 4
    assert pub["n_routed_experts"] // doc["n_routed_experts"] == 8
    assert pub["vocab_size"] // doc["vocab_size"] == 8
    for word in ("8 chips share each layer", "8 ways", "held whole"):
        assert word in doc["deployment"], word
    assert doc["departures"] and {"maps", "rotary", "mtp", "init",
                                  "optimizer", "data"} <= set(doc["assumed"])


def test_the_shipped_experiment_is_the_configurations():
    """``colearn train --config xing4_fedavg`` builds the model the cell
    measures."""
    from colearn_federated_learning_tpu.utils.config import get_config

    doc = BENCH.config(CONFIG)
    shipped = get_config("xing4_fedavg")
    assert shipped.model == ModelConfig(**doc["experiment"]["model"])
    traffic = BENCH.traffic(BENCH.workload(CELL)["traffic"])
    assert (traffic["cohort"], traffic["num_clients"],
            traffic["examples_per_client"], traffic["local_steps"],
            traffic["batch"], traffic["holdout"], traffic["eval_every"]) == (
        1, 8, 16, 2, 1, 4, 2)
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
    # 759.5 M parameters: 9.11 GB at 12 B each on fit()'s path.
    assert count == 759_489_806 and count * 12 == pytest.approx(9.11e9,
                                                                rel=1e-3)
    per_layer = {k: sum(math.prod(a.shape) for a in jax.tree.leaves(v))
                 for k, v in shapes.items()}
    assert per_layer["layer_0"] == 128_225_590          # the dense layer
    assert per_layer["layer_1"] == per_layer["layer_4"] == 128_455_030
    assert "mtp_layer" not in per_layer
    # With the prediction module: 913.6 M, 10.96 GB.
    with_module = jax.eval_shape(lambda: registry.init_params(
        registry.build_model(ModelConfig(
            **{**doc["experiment"]["model"], "mtp_modules": 1})),
        ids, jax.random.PRNGKey(0)))
    assert sum(math.prod(a.shape)
               for a in jax.tree.leaves(with_module)) == 913_645_700
    assert per_layer["embed"] == per_layer["head"] == 16384 * 3584
    assert math.prod(shapes["layer_1"]["attn"]["q_b"]["kernel"].shape) == (
        768 * 32 * 192)


def test_tokens_ahead_kind_is_the_tokens_kind_looking_further():
    """``y[..., 0]`` is the ``tokens`` kind's ``y`` for the same seed, ``x``
    its ``x``; head 1's label at a position is head 0's at the next."""
    doc = tiny_doc()
    assert doc["dataset"]["horizon"] == 1        # the cell's: no module
    one = generate.dataset(BENCH, doc, DIGESTS["traffic"], seed=11)
    assert one.y_train.shape == (16, 64, 1)
    doc["dataset"]["horizon"] = 2                # a model with one module
    data = generate.dataset(BENCH, doc, DIGESTS["traffic"], seed=11)
    x, y = data.x_train, data.y_train
    assert x.shape == (16, 64) and y.shape == (16, 64, 2)
    assert data.x_test.shape == (8, 64) and data.y_test.shape == (8, 64, 2)
    np.testing.assert_array_equal(one.y_train, y[..., :1])
    assert x.dtype == y.dtype == np.int32
    np.testing.assert_array_equal(y[:, :-1, 0], x[:, 1:])
    np.testing.assert_array_equal(y[:, :-1, 1], y[:, 1:, 0])
    assert 0 <= y.min() and y.max() < 96 and (x == 0).any()
    plain = dict(doc, dataset=dict(doc["dataset"], kind="tokens"))
    same = generate.dataset(BENCH, plain, DIGESTS["traffic"], seed=11)
    np.testing.assert_array_equal(same.x_train, x)
    np.testing.assert_array_equal(same.y_train, y[..., 0])
    # The token past a row's next: a successor of the last, or a fresh
    # word after a separator; never a separator.
    assert (y[:, -1, 1] > 0).all()
    other = generate.dataset(BENCH, doc, DIGESTS["traffic"], seed=12)
    assert (other.x_train != x).any()


@pytest.mark.parametrize("seed", sorted(DIGESTS["sha256"]))
def test_tokens_ahead_kind_splits_do_not_move(seed):
    doc = tiny_doc()
    doc["dataset"]["horizon"] = DIGESTS["horizon"]
    data = generate.dataset(BENCH, doc, DIGESTS["traffic"], int(seed))
    assert {split: hashlib.sha256(
        getattr(data, split).tobytes()).hexdigest()
        for split in ("x_train", "y_train", "x_test", "y_test")
    } == DIGESTS["sha256"][seed]


def test_flops_at_the_tiny_size():
    doc = tiny_doc()
    model, dataset = doc["experiment"]["model"], doc["dataset"]
    assert (model["depth"], model["dense_layers"], model["mtp_modules"]) == (
        3, 1, 0)
    # By hand: 64 tokens of width 32 on 4 streams; 4 heads of 8 + 4 for the
    # scores and 8 for the values, ranks 12 and 8; 4 of 16 experts a token,
    # 4 held; feed-forward 48, experts 24.
    pairs = 64 * 65 // 2
    kernel = pairs * 4 * (12 + 8)
    assert kernel == flops.kernel_macs(model, dataset) == 166_400
    attention = 64 * (32 * 12 + 12 * 4 * 12 + 32 * (8 + 4) + 8 * 4 * 16
                      + 4 * 8 * 32) + kernel
    maps = 2 * 64 * (4 * 32) * 24
    held = 4 * 4 / 16
    assert held == flops.held_choices_per_token(model) == 1.0
    dense = attention + maps + 64 * 3 * 32 * 48
    moe = attention + maps + 64 * (32 * 16 + 3 * 32 * 24 + held * 3 * 32 * 24)
    assert flops.layer_macs(model, dataset) == {"dense": dense, "moe": moe}
    macs = dense + 2 * moe + 64 * 32 * 96
    assert (dense, moe, macs) == (1_038_848, 1_071_616, 3_378_688)
    assert flops.forward_flops(model, dataset) == 2 * macs
    assert flops.train_flops(model, dataset) == 6 * macs
    assert flops.attention_flops(model, dataset, train=False) == (
        2 * 3 * kernel)
    # With one prediction module: the joined streams' projection, one more
    # layer with experts and its kernel, a second head's logits.
    module = dict(model, mtp_modules=1)
    assert flops.forward_flops(module, dataset) == 2 * (
        macs + 64 * 2 * 32 * 32 + moe + 64 * 32 * 96) == 2 * 4_777_984
    assert flops.attention_flops(module, dataset, train=False) == (
        2 * 4 * kernel)
    assert flops.attention_flops(model, dataset, train=True) == (
        3 * flops.attention_flops(model, dataset, train=False))
    # q and k (4 heads of 12), v and the output (4 heads of 8) in bf16 and
    # a float32 log-sum a head; three times that with the backward's.
    assert flops.attention_bytes(model, dataset, train=False) == 3 * 64 * (
        2 * 48 * 2 + 2 * 32 * 2 + 4 * 4)
    assert flops.attention_bytes(model, dataset, train=True) == 3 * (
        flops.attention_bytes(model, dataset, train=False))


def test_flops_at_the_published_widths():
    doc = BENCH.config(CONFIG)
    model, dataset = doc["experiment"]["model"], doc["dataset"]
    pairs = 8192 * 8193 // 2
    assert flops.kernel_macs(model, dataset) == pairs * 32 * 320
    projections = (3584 * 768 + 768 * 32 * 192 + 3584 * 576
                   + 512 * 32 * 256 + 4096 * 3584)
    maps = 2 * 14336 * 24
    got = flops.layer_macs(model, dataset)
    assert got["dense"] == 8192 * (projections + maps + 3 * 3584 * 9216) + (
        pairs * 32 * 320)
    assert got["moe"] == 8192 * (
        projections + maps + 3584 * 64 + 3 * 3584 * 1024
        + 0.5 * 3 * 3584 * 1024) + pairs * 32 * 320
    forward = flops.forward_flops(model, dataset)
    # 9.50 TFLOP a sequence forward, 28.5 a training step; the attention
    # kernel 36% of it, the latent projections 24.5%, the dense
    # feed-forward 17%, the logits 10%, the shared experts 7.6%, the
    # routed products 3.8%, the maps' scores 0.6%.
    assert forward == pytest.approx(9.503e12, rel=1e-3)
    assert flops.train_flops(model, dataset) == pytest.approx(28.51e12,
                                                              rel=1e-3)
    share = lambda macs: 2 * macs / forward  # noqa: E731
    assert share(5 * pairs * 32 * 320) == pytest.approx(0.362, abs=2e-3)
    assert share(5 * 8192 * projections) == pytest.approx(0.245, abs=2e-3)
    assert share(8192 * 3 * 3584 * 9216) == pytest.approx(0.171, abs=2e-3)
    assert share(8192 * 3584 * 16384) == pytest.approx(0.101, abs=2e-3)
    assert share(4 * 8192 * 3 * 3584 * 1024) == pytest.approx(0.076, abs=2e-3)
    assert share(4 * 8192 * 0.5 * 3 * 3584 * 1024) == pytest.approx(
        0.038, abs=1e-3)
    assert share(5 * 8192 * maps) == pytest.approx(0.006, abs=1e-3)
    # With the prediction module it would be 12.3 and 37.0 TFLOP.
    assert flops.train_flops(dict(model, mtp_modules=1), dataset) == (
        pytest.approx(36.97e12, rel=1e-3))
    # The kernel is bound by its operations on the v5e: 52.3 ms a training
    # step against 6.2 ms for its bytes.
    assert flops.attention_flops(model, dataset, True) / 197e12 == (
        pytest.approx(52.3e-3, rel=0.01))
    assert flops.attention_bytes(model, dataset, True) / 819e9 == (
        pytest.approx(6.2e-3, rel=0.01))


def test_the_residual_path_is_told_from_other_operations():
    """The shapes are the configuration's, written as ``harness/xplane.py``
    labels an operation (a name, then its largest array); the kernel's
    label is the v5e trace's own (my chip runs, PR 35)."""
    doc = BENCH.config(CONFIG)
    mine = mhc_ms_per_round.stream_ops(doc["experiment"]["model"],
                                       doc["dataset"])
    for label in STREAM_LABELS:
        assert mine(label), label
    for label in OTHER_LABELS:
        assert not mine(label), label
    # Another family's configuration has no such path.
    reading = types.SimpleNamespace(
        config=BENCH.config("nemotron3_super_11of88"), rounds=2, trace=None)
    assert mhc_ms_per_round.read(reading) is None


STREAM_LABELS = (
    "fusion.1 bf16[1,4,8192,3584]", "fusion.2 f32[4,8192,3584]",
    "fusion.3 f32[1,8192,24]", "fusion.4 f32[24,8192]",
    "fusion.5 f32[14336,24]", "fusion.6 f32[4,3584,24]",
    "fusion.7 f32[1,4,4,8192]", "fusion.8 f32[4,1,8192]",
    "fusion.9 f32[16,8192]",
)
OTHER_LABELS = (
    "fusion.10 bf16[8192,3584]", "fusion.11 f32[1,8192,3584]",
    "flash_dkv.13 bf16[32,8192,192]", "fusion.12 f32[8192,16384]",
    "fusion.13 bf16[8192,9216]", "fusion.14 f32[8192,64]",
    "fusion.15 f32[2,4096,4]", "sort.1 s32[16384]",
    "ragged-dot-none.1 bf16[4096,1024]", "fusion.16 bf16[8192,32,256]",
    "copy.1 f32[8,3584,1024]", "fusion.17 bf16[8192,768]",
)


def test_the_new_entries_are_the_cells_alone():
    """The configuration, the cell and the five per-layer metrics: each
    metric lists this cell and no other, names a layer the benchmark has,
    moves the cell's end-to-end metric, and has a reader of its own that
    gives None where it finds nothing to read."""
    doc = BENCH.doc
    cell = BENCH.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "cohort1_seq8k_eval2", 1)
    assert len(cell["why"]) <= 200
    assert doc["workloads"][-1] == cell
    assert doc["configs"][-1]["name"] == CONFIG
    layers = {m["layer"] for m in doc["per_layer"][:-5]}
    added = doc["per_layer"][-5:]
    assert {m["name"] for m in added} == METRICS
    for m in added:
        assert m["workloads"] == [CELL] and m["layer"] in layers, m
        assert m["moves"] == "client_samples_per_s_per_chip"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["source"] == "device_trace") == (
            m["name"] in DEVICE_TRACE_METRICS)
        assert callable(BENCH.module("layer_metrics", m["name"]).read)
    for other in doc["workloads"][:-1]:
        assert not METRICS & {
            m["name"] for m in BENCH.metrics("per_layer", other["name"])}
    assert {m["name"] for m in BENCH.metrics("end_to_end", CELL)} == {
        "client_samples_per_s_per_chip", "setup_s"}
    # Untraced, or of a program that set no such gauge: nothing to read.
    untraced = types.SimpleNamespace(
        config=BENCH.config(CONFIG), rounds=2, trace=None)
    for name in DEVICE_TRACE_METRICS:
        assert BENCH.module("layer_metrics", name).read(untraced) is None


def test_gauge_readers_read_the_programs_registry(monkeypatch):
    monkeypatch.setattr(_program, "counter", {
        "mhc.streams": 4.0, "mtp.modules": 1.0}.get)
    for name, want in (("mhc_streams", 4.0), ("mtp_modules", 1.0)):
        assert BENCH.module("layer_metrics", name).read(None) == want
    monkeypatch.setattr(_program, "counter", {}.get)
    for name in ("mhc_streams", "mtp_modules"):
        assert BENCH.module("layer_metrics", name).read(None) is None


# A size at which the CPU runs the probe in seconds.
SMALL_MODEL = {"width": 64, "seq_len": 96, "depth": 3, "dense_layers": 1,
               "num_classes": 96, "vocab_size": 96, "num_heads": 4,
               "q_rank": 24, "kv_rank": 16, "nope_dim": 16, "rope_dim": 8,
               "v_dim": 16, "yarn_original_max": 24, "ffn_dim": 128,
               "num_experts": 16, "experts_first": 4, "experts_held": 8,
               "experts_per_token": 4, "expert_dim": 48,
               "shared_expert_dim": 48}


class Intercepted:
    """The program's model with one flax module's call rewritten."""

    def __init__(self, model, interceptor):
        self.model, self.interceptor = model, interceptor

    def apply(self, *args, **kwargs):
        with nn.intercept_methods(self.interceptor):
            return self.model.apply(*args, **kwargs)


def drop_the_shared_expert(next_fun, args, kwargs, context):
    """The last layer's mixture leaves its shared expert out."""
    if (context.method_name == "shared"
            and "layer_2" in context.module.path):
        return jnp.zeros_like(next_fun(*args, **kwargs))
    return next_fun(*args, **kwargs)


def stand_in(dtype="bfloat16", interceptor=None, scale=2.0, modules=0):
    """What ``probe.parity`` reads of a learner: its model, its seeded
    weights (the matrices enlarged, so that the sublayers weigh against the
    embedding at this width) and its data; ``modules``: the prediction
    modules (the cell's configuration has none)."""
    doc = BENCH.config(CONFIG)
    doc["experiment"]["model"].update(SMALL_MODEL, dtype=dtype,
                                      mtp_modules=modules)
    doc["dataset"].update(input_shape=[96], n_train=8, num_classes=96,
                          vocab_size=96, horizon=1 + modules)
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


@pytest.mark.parametrize("modules", [0, 1], ids=["no_mtp", "mtp"])
def test_reference_agrees_with_the_program(modules):
    got = probe.parity(*stand_in(modules=modules))
    assert got["ok"] and got["batch"] == 1, got
    assert got["ref_loss"] == pytest.approx(math.log(96), rel=0.1)


def test_float32_program_is_close_to_the_reference():
    """In float32 the two sides differ by rounding alone: what the
    tolerance allows for is bf16, not the reference."""
    got = probe.parity(*stand_in(dtype="float32"))
    assert got["loss_rel_gap"] < 1e-5 and got["grad_rel_gap_max"] < 1e-3, got


def test_a_removed_term_fails():
    got = probe.parity(*stand_in(interceptor=drop_the_shared_expert))
    assert not got["ok"], got


def test_the_control_fails():
    """The reference with its forward products in fp8 in the program's
    place (``controls.py``): the gradient's number says so (the readings
    on the chip at full size: PERF.md section 7)."""
    learner, reference, doc = stand_in()
    control = controls.in_the_programs_place(
        learner, reference, doc["experiment"]["model"], controls.fp8())
    got = probe.parity(control, reference, doc)
    assert not got["ok"], got
    assert got["grad_rel_gap_max"] > reference.TOLERANCE["grad_leaf"]
