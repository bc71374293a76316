"""The ``ling3`` family's own files: its configuration against the
catalog's row, its data set's splits, its operation counts against a count
by hand, its entries in ``BENCHMARK.json`` **found by name**, its readers,
its cell end to end at the tiny size, its plain reference against the
program at a small size, and the control."""

import hashlib
import json
import math
import os
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

import controls
import tiny
from benchmarks.flops import ling3 as flops
from benchmarks.harness import probe
from benchmarks.harness.spec import Bench
from benchmarks.layer_metrics import _program, _scopes
from benchmarks.traffic import generate
from colearn_federated_learning_tpu.models import registry
from colearn_federated_learning_tpu.utils.config import ModelConfig

# What the CPU cannot give (``tiny.NOT_ON_CPU``): these three read the
# device plane of a trace.
DEVICE_TRACE_METRICS = {"kda_ms_per_round", "kda_rule_ms_per_round",
                        "kda_rule_roofline"}
tiny.NOT_ON_CPU |= DEVICE_TRACE_METRICS

BENCH = Bench(tiny.REPO)
CONFIG = "ling3_flash_7of42"
CELL = "ling_kda_mla_hybrid"
METRICS = DEVICE_TRACE_METRICS | {"kda_chunk", "moe_groups_kept"}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size", "max_position_embeddings"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(os.path.dirname(__file__), "data",
                       "dataset_digests_ling3.json")) as f:
    DIGESTS = json.load(f)


def tiny_doc() -> dict:
    doc = BENCH.config(CONFIG)
    tiny.shrink_config(doc)
    return doc


def entry(section: str, name: str) -> dict:
    return BENCH._entry(section, name)


def test_the_configuration_keeps_the_published_widths():
    """Every key under ``published`` stands in the file under its own name
    with its published value, but for the five ``reduced`` names, none of
    them a width; the experiment's sizes are the file's; the deployment is
    64 chips a layer."""
    doc = BENCH.config(CONFIG)
    listed = entry("configs", CONFIG)
    assert doc["reduced"] == listed["reduced"] == REDUCED
    assert listed["source"] == doc["source"] and len(listed["source"]) <= 200
    for key, value in doc["published"].items():
        if key in REDUCED:
            assert doc[key] != value and key in doc["reduced_note"], key
        else:
            assert doc[key] == value, key
    assert set(REDUCED) <= set(doc["published"])
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank"))]
    model = doc["experiment"]["model"]
    assert (model["width"], model["kv_rank"], model["nope_dim"],
            model["rope_dim"], model["v_dim"], model["head_dim"],
            model["ffn_dim"], model["expert_dim"],
            model["shared_expert_dim"], model["conv_kernel"]) == (
        2560, 512, 128, 64, 128, 128, 6144, 768, 768, 4) == (
        doc["hidden_size"], doc["kv_lora_rank"], doc["qk_nope_head_dim"],
        doc["qk_rope_head_dim"], doc["v_head_dim"], doc["head_dim"],
        doc["intermediate_size"], doc["moe_intermediate_size"],
        doc["moe_shared_expert_intermediate_size"],
        doc["short_conv_kernel_size"])
    assert doc["q_lora_rank"] is None and doc["rotary_dim"] == 64
    # The router keeps its published width, its experts a token, its groups.
    assert (model["num_experts"], model["experts_per_token"],
            model["experts_held"], model["expert_groups"],
            model["expert_groups_kept"], model["routed_scale"]) == (
        doc["published"]["num_experts"], doc["num_experts_per_tok"],
        doc["num_experts"], doc["n_group"], doc["topk_group"],
        doc["routed_scaling_factor"]) == (512, 8, 8, 8, 4, 2.5)
    assert doc["score_function"] == "sigmoid" and doc["norm_topk_prob"]
    assert (model["kda_lower_bound"], model["layer_group_size"],
            model["rope_theta"], model["norm_eps"], model["num_heads"]) == (
        doc["kda_lower_bound"], doc["layer_group_size"], doc["rope_theta"],
        doc["rms_norm_eps"], doc["num_attention_heads"]) == (
        -5, 6, 6e6, 1e-6, 32)
    assert doc["kda_safe_gate"] and doc["no_kda_lora"] and doc["linear_silu"]
    assert (model["depth"], model["dense_layers"], model["first_layer"]) == (
        doc["num_hidden_layers"], doc["first_k_dense_replace"], 1) == (7, 1, 1)
    # The held layers run no clamp: the published limits are 0 up to 34.
    assert len(doc["expert_swiglu_limit_list"]) == 42
    assert not any(doc["expert_swiglu_limit_list"][1:8])
    assert not any(doc["share_expert_swiglu_limit_list"][1:8])
    assert "expert_limits" not in model
    assert model["vocab_size"] == model["num_classes"] == doc["vocab_size"]
    assert doc["dataset"]["input_shape"] == [model["seq_len"]] == [
        doc["max_position_embeddings"]]
    assert doc["dataset"]["vocab_size"] == doc["vocab_size"] == 19648
    # The floors: a whole period (5 KDA to 1 MLA) after the dense layer and
    # at least 4 layers after it, 8 experts, an eighth of the vocabulary.
    assert [m for m, _ in BENCH.module("reference", "ling3").layer_kinds(
        model)] == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    assert doc["num_hidden_layers"] - doc["first_k_dense_replace"] >= 6
    pub = doc["published"]
    assert pub["num_experts"] // doc["num_experts"] == 64
    assert pub["vocab_size"] // doc["vocab_size"] == 8
    for word in ("64 chips share each layer", "64 ways", "8 ways",
                 "held whole"):
        assert word in doc["deployment"], word
    assert len(doc["departures"]) >= 4 and {
        "kda", "mla", "experts", "init", "optimizer", "data"} <= set(
        doc["assumed"])


def test_every_number_of_the_catalogs_row_is_in_the_file():
    """The file holds every key of the catalog's ``config`` under the same
    name; numbers, and nested groups whole, are the row's but for the keys
    ``reduced`` names."""
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    doc = BENCH.config(CONFIG)
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in doc, key
        if key not in REDUCED:
            assert doc[key] == value, key
        assert doc["published"].get(key, value) == value, key


def test_the_shipped_experiment_is_the_configurations():
    """``colearn train --config ling3_fedavg`` builds the model the cell
    measures, and the file's count of it is the built model's."""
    from colearn_federated_learning_tpu.utils.config import get_config

    doc = BENCH.config(CONFIG)
    shipped = get_config("ling3_fedavg")
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
    # 884.5 M parameters: 10.61 GB at 12 B each on fit()'s path.
    assert count == 884_459_840 == doc["parameters"]["count"]
    assert count * 12 / 1e9 == pytest.approx(doc["parameters"]["gb_at_12_bytes"],
                                             abs=5e-3) == pytest.approx(10.61, abs=5e-3)
    size = lambda tree: sum(  # noqa: E731
        math.prod(a.shape) for a in jax.tree.leaves(tree))
    per_layer = {k: size(v) for k, v in shapes.items()}
    kda = size(shapes["layer_1"]["mixer"])
    mla = size(shapes["layer_4"]["mixer"])
    assert kda == 2560 * (5 * 4096 + 32) + 4 * 3 * 4096 + 4096 + 32 + 128 + (
        4096 * 2560) == 63_049_888
    assert mla == (2560 * 32 * 192 + 2560 * 576 + 512 + 512 * 32 * 256
                   + 2 * 192 + 2560 * 32 + 4096 * 2560) == 31_966_080
    experts = 8 * 3 * 2560 * 768 + 3 * 2560 * 768 + 2560 * 512 + 512
    assert per_layer["layer_0"] == kda + 3 * 2560 * 6144 + 2 * 2560
    assert per_layer["layer_1"] == per_layer["layer_6"] == (
        kda + experts + 2 * 2560)
    assert per_layer["layer_4"] == mla + experts + 2 * 2560
    assert per_layer["embed"] == per_layer["head"] == 19648 * 2560


def test_tokens_kind_at_the_configurations_slice():
    doc = tiny_doc()
    data = generate.dataset(BENCH, doc, DIGESTS["traffic"], seed=11)
    x, y = data.x_train, data.y_train
    assert x.shape == y.shape == (16, 64) and data.x_test.shape == (8, 64)
    assert 0 <= y.min() and y.max() < 96 and (x == 0).any()
    assert (y[:, :-1] == x[:, 1:]).all()


@pytest.mark.parametrize("seed", sorted(DIGESTS["sha256"]))
def test_the_splits_do_not_move(seed):
    data = generate.dataset(BENCH, tiny_doc(), DIGESTS["traffic"], int(seed))
    assert {split: hashlib.sha256(
        getattr(data, split).tobytes()).hexdigest()
        for split in ("x_train", "y_train", "x_test", "y_test")
    } == DIGESTS["sha256"][seed]


def test_flops_at_the_tiny_size():
    doc = tiny_doc()
    model, dataset = doc["experiment"]["model"], doc["dataset"]
    assert flops.mixer_kinds(model) == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    # By hand: 64 tokens of width 32; 4 heads of 8, chunks of 8; latent
    # attention 4 heads of 8 + 4 for the scores and 8 for the values, rank
    # 8; 4 of 16 experts a token, 4 held; feed-forward 48, experts 24.
    below, upto = 8 * 7 // 2, 8 * 9 // 2
    chunk = (below * 8 + upto * 8 + below * 16 + 3 * 8 * 8 * 8 + upto * 8)
    rule = 8 * 4 * chunk
    assert rule == flops.rule_macs(model, dataset) == 89_088
    kda = 64 * (32 * (5 * 32 + 4) + 32 * 32) + rule
    kernel = (64 * 65 // 2) * 4 * (12 + 8)
    mla = 64 * (32 * 48 + 32 * 12 + 8 * 4 * 16 + 32 * 4 + 32 * 32) + kernel
    held = 4 * 4 / 16
    assert held == flops.held_choices_per_token(model) == 1.0
    dense = 64 * 3 * 32 * 48
    moe = 64 * (32 * 16 + 3 * 32 * 24 + held * 3 * 32 * 24)
    assert flops.layer_macs(model, dataset) == {
        "kda": kda, "mla": mla, "dense": dense, "moe": moe}
    macs = 6 * kda + mla + dense + 6 * moe + 64 * 32 * 96
    assert flops.forward_flops(model, dataset) == 2 * macs
    assert flops.train_flops(model, dataset) == 6 * macs
    assert flops.rule_flops(model, dataset, train=False) == 2 * 6 * rule
    assert flops.rule_flops(model, dataset, train=True) == 6 * 6 * rule
    # q, k, v and the output in bf16, a float32 decay a channel and step a
    # head; the backward reads those and the output's gradient and writes
    # five gradients.
    wide, positions = 64 * 4 * 8, 64 * 4
    forward = wide * (2 + 2 + 2 + 4) + positions * 4 + wide * 2
    assert flops.rule_bytes(model, dataset, train=False) == 6 * forward
    assert flops.rule_bytes(model, dataset, train=True) == 6 * (
        2 * forward + wide * (2 + 2 + 2 + 4) + positions * 4)


def test_flops_at_the_published_widths():
    doc = BENCH.config(CONFIG)
    model, dataset = doc["experiment"]["model"], doc["dataset"]
    length = dataset["input_shape"][0]
    got = flops.layer_macs(model, dataset)
    rule = flops.rule_macs(model, dataset)
    # A chunk of 64 and a head: 4.45 M multiply-adds, 70 k a position,
    # where the recurrence one position at a time needs 3 x 128 x 128.
    assert rule / (length * 32) == 69_568
    assert got["kda"] == length * (2560 * 20512 + 4096 * 2560) + rule
    assert got["dense"] == length * 3 * 2560 * 6144
    assert got["moe"] == length * (
        2560 * 512 + 3 * 2560 * 768 + 0.125 * 3 * 2560 * 768)
    forward = flops.forward_flops(model, dataset)
    share = lambda macs: 2 * macs / forward  # noqa: E731
    # Of a sequence's forward operations: the six mixers' maps about two
    # thirds, the rule itself 2.6%.
    assert share(6 * length * (2560 * 20512 + 4096 * 2560)) == pytest.approx(
        0.62 if length == 8192 else 0.66, abs=0.03)
    assert share(6 * rule) == pytest.approx(0.026, abs=0.005)
    assert flops.train_flops(model, dataset) == 3 * forward
    # The rule is bound by its bytes on the v5e.
    steps = flops.rule_bytes(model, dataset, True) / 819e9
    assert steps > flops.rule_flops(model, dataset, True) / 197e12
    assert steps == pytest.approx(8.4e-3 * length / 8192, rel=0.02)


def test_the_new_entries_are_found_by_name():
    """The configuration, the cell and the five per-layer metrics, looked
    up by name wherever later PRs put theirs: each metric lists this cell
    and no other, names a layer the benchmark has, moves the cell's
    end-to-end metric, and has a reader of its own that gives None where it
    finds nothing to read."""
    doc = BENCH.doc
    cell = BENCH.workload(CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert cell["traffic"] in ("cohort1_seq8k_eval2", "cohort1_seq4k_eval2")
    traffic = BENCH.traffic(cell["traffic"])
    assert (traffic["cohort"], traffic["num_clients"],
            traffic["examples_per_client"], traffic["local_steps"],
            traffic["batch"], traffic["holdout"], traffic["eval_every"]) == (
        1, 8, 16, 2, 1, 4, 2)
    assert len(cell["why"]) <= 200
    assert entry("configs", CONFIG)["file"] == (
        f"benchmarks/configs/{CONFIG}.json")
    by_name = {m["name"]: m for m in doc["per_layer"]}
    assert METRICS <= set(by_name)
    others = {m["layer"] for m in doc["per_layer"] if m["name"] not in METRICS}
    for name in METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["layer"] in others, m
        assert m["moves"] == "client_samples_per_s_per_chip"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["source"] == "device_trace") == (
            name in DEVICE_TRACE_METRICS)
        assert callable(BENCH.module("layer_metrics", name).read)
    assert by_name["kda_rule_roofline"]["unit"] == "%"
    for other in doc["workloads"]:
        if other["name"] != CELL:
            assert not METRICS & {
                m["name"] for m in BENCH.metrics("per_layer", other["name"])}
    assert {m["name"] for m in BENCH.metrics("end_to_end", CELL)} == {
        "client_samples_per_s_per_chip", "setup_s"}
    assert "chunk_mfu" in {
        m["name"] for m in BENCH.metrics("per_layer", CELL)}
    # Untraced, or of a program without the scope: nothing to read.
    untraced = types.SimpleNamespace(
        config=BENCH.config(CONFIG), rounds=2, trace=None)
    for name in DEVICE_TRACE_METRICS:
        assert BENCH.module("layer_metrics", name).read(untraced) is None


def test_readers_read_the_programs_registry_and_scopes(monkeypatch):
    monkeypatch.setattr(_program, "counter", {
        "kda.chunk": 64.0, "moe.groups_kept": 4.0}.get)
    for name, want in (("kda_chunk", 64.0), ("moe_groups_kept", 4.0)):
        assert BENCH.module("layer_metrics", name).read(None) == want
    monkeypatch.setattr(_program, "counter", {}.get)
    for name in ("kda_chunk", "moe_groups_kept"):
        assert BENCH.module("layer_metrics", name).read(None) is None
    # The roofline: a round of 2 sequences whose rule took 100 ms of device
    # time against the 16.8 ms its bytes need at the published sizes.
    doc = BENCH.config(CONFIG)
    seen = []

    def under_ms(r, name):
        seen.append(name)
        return 100.0

    monkeypatch.setattr(_scopes, "under_ms", under_ms)
    reading = types.SimpleNamespace(
        config=doc, bench=BENCH, samples_per_round=2, chips=1,
        device_kind="TPU v5 lite", rounds=2)
    share = BENCH.module("layer_metrics", "kda_rule_roofline").read(reading)
    length = doc["dataset"]["input_shape"][0]
    assert share == pytest.approx(16.8 * length / 8192, rel=0.02)
    assert 0 < share < 100
    assert BENCH.module("layer_metrics", "kda_ms_per_round").read(
        reading) == 100.0
    assert seen == ["kda.rule", "kda"]
    monkeypatch.setattr(_scopes, "under_ms", lambda r, name: None)
    assert BENCH.module("layer_metrics", "kda_rule_roofline").read(
        reading) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_ling3")))


def test_the_tiny_cell_runs_end_to_end_and_is_correct(root):
    process = tiny.run(root, CELL, 1, seconds=1.0)
    assert process.returncode == 0, process.stderr[-2000:]
    out = tiny.lines(process)
    result = out[-1]
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"client_samples_per_s_per_chip",
                                      "setup_s"}
    checks = result["checks"]
    for name in ("loss_rel_gap", "grad_rel_gap_max", "eval_loss_rel_gap",
                 "eval_acc_gap"):
        assert checks[name][0] <= checks[name][1], name


def test_the_traced_tiny_cell_reports_its_counters(root):
    process = tiny.run(root, CELL, 1, seconds=0.5, trace=1)
    assert process.returncode == 0, process.stderr[-2000:]
    metrics = tiny.lines(process)[-1]["metrics"]
    assert metrics["kda_chunk"]["value"] == 8
    assert metrics["moe_groups_kept"]["value"] == 2
    assert metrics["chunk_mfu"]["value"] > 0
    assert not DEVICE_TRACE_METRICS & set(metrics)


# A size at which the CPU runs the probe in seconds.
SMALL_MODEL = {"width": 64, "seq_len": 96, "depth": 7, "dense_layers": 1,
               "num_classes": 96, "vocab_size": 96, "num_heads": 4,
               "head_dim": 16, "chunk_size": 16, "kv_rank": 16,
               "nope_dim": 16, "rope_dim": 8, "v_dim": 16, "ffn_dim": 128,
               "num_experts": 16, "experts_first": 4, "experts_held": 8,
               "experts_per_token": 4, "expert_groups": 4,
               "expert_groups_kept": 2, "expert_dim": 48,
               "shared_expert_dim": 48, "moe_row_tile": 16}


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
            and "layer_6" in context.module.path):
        return jnp.zeros_like(next_fun(*args, **kwargs))
    return next_fun(*args, **kwargs)


def stand_in(dtype="bfloat16", interceptor=None, scale=2.0, steep=False):
    """What ``probe.parity`` reads of a learner: its model, its seeded
    weights (the matrices enlarged, so that the sublayers weigh against the
    embedding at this width) and its data.  ``steep``: the decays'
    parameters drawn where the safe gate is not flat (as drawn most heads
    start near no decay, which is what the probe on the chip sees; in bf16
    a decay in the steep part carries the rounding of its map of the stream
    through every later position, a few per cent of a gradient at this
    size, so the float32 case alone takes them there)."""
    doc = BENCH.config(CONFIG)
    doc["experiment"]["model"].update(SMALL_MODEL, dtype=dtype)
    doc["dataset"].update(input_shape=[96], n_train=8, num_classes=96,
                          vocab_size=96)
    data = generate.dataset(
        BENCH, doc, {"cohort": 1, "eval_every": 1, "holdout": 2}, seed=3)
    model = registry.build_model(ModelConfig(**doc["experiment"]["model"]))
    params = registry.init_params(
        model, jnp.asarray(data.x_train[:1]), jax.random.PRNGKey(3))

    def moved(path, a):
        name = jax.tree_util.keystr(path)
        if steep and ("A_log" in name or "dt_bias" in name):
            return jax.random.normal(jax.random.PRNGKey(len(name)), a.shape)
        return a * scale if a.ndim >= 2 else a

    params = jax.tree_util.tree_map_with_path(moved, params)
    if interceptor is not None:
        model = Intercepted(model, interceptor)
    learner = types.SimpleNamespace(model=model, params=params, dataset=data)
    return learner, BENCH.module("reference", doc["family"]), doc


def test_reference_agrees_with_the_program():
    # At this width a choice of 4 of 16 experts within 2 of 4 groups moves
    # with bf16's rounding for more tokens than the cell's does: the
    # matrices are left as drawn, where the others enlarge them.
    got = probe.parity(*stand_in(scale=1.0))
    assert got["ok"] and got["batch"] == 1, got
    assert got["ref_loss"] == pytest.approx(math.log(96), rel=0.1)


def test_float32_program_is_close_to_the_reference():
    """In float32 the two sides differ by rounding alone: what the
    tolerance allows for is bf16, not the reference."""
    got = probe.parity(*stand_in(dtype="float32", steep=True))
    assert got["loss_rel_gap"] < 1e-5 and got["grad_rel_gap_max"] < 1e-3, got


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
