"""``moe_pair_sort_keys``: the gauge it reads, what a program without the
gauge gives, what the two share layers set at the cells' shapes, and its
entry in ``BENCHMARK.json``, added as a file and an entry with nothing
that was there edited."""

import jax
import jax.numpy as jnp
import pytest

import tiny
from benchmarks.harness.spec import Bench
from benchmarks.layer_metrics import _program, moe_pair_sort_keys
from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.models import moe

NAME = "moe_pair_sort_keys"
CELLS = ["nemotron_hybrid_seq16k", "xing_mla_mhc_seq8k"]


def test_reads_the_gauge_and_none_without_it(monkeypatch):
    monkeypatch.setattr(_program, "counter",
                        {"moe.pair_sort_keys": 32768.0}.get)
    assert moe_pair_sort_keys.read(None) == 32768.0
    # The parent of the PR that added the gauge; a family with no share
    # layer.
    monkeypatch.setattr(_program, "counter", {"moe.dispatch_rows": 8.0}.get)
    assert moe_pair_sort_keys.read(None) is None


LAYERS = {
    "latent_22_of_512": (moe.LatentMoEShare, dict(
        embed_dim=16, latent_dim=8, expert_dim=8, shared_dim=8,
        experts_total=512, experts_held=(0, 8), top_k=22), 16384),
    "gated_4_of_64": (moe.GatedMoEShare, dict(
        embed_dim=16, expert_dim=8, shared_dim=8, experts_total=64,
        experts_held=(0, 8), top_k=4), 8192),
}


@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_both_share_layers_set_it_at_trace_time(layer):
    """At the cells' tokens, blocks, experts and choices (the widths do not
    enter): 8 held experts x a block's 4,096 tokens, where a sort of every
    pair took 4,096 x 22 and 4,096 x 4 keys.  Nothing is run."""
    cls, sizes, tokens = LAYERS[layer]
    module = cls(**sizes)
    u = jax.ShapeDtypeStruct((tokens, 16), jnp.float32)
    telemetry.get_registry().gauge("moe.pair_sort_keys").set(-1)
    jax.eval_shape(lambda u: module.init(jax.random.PRNGKey(0), u), u)
    assert moe_pair_sort_keys.read(None) == 8 * 4096
    assert _program.counter("moe.dispatch_rows") == tokens * min(
        sizes["top_k"], 8)


def test_the_entry_lists_the_two_expert_cells():
    bench = Bench(tiny.REPO)
    assert bench.doc["per_layer"][-1] == {
        "name": NAME, "unit": "keys", "better": "lower",
        "source": "program_counter", "layer": "models",
        "moves": "client_samples_per_s_per_chip", "workloads": CELLS}
    assert bench.module("layer_metrics", NAME) is moe_pair_sort_keys
    for cell in bench.doc["workloads"]:
        names = {m["name"] for m in bench.metrics("per_layer", cell["name"])}
        assert (NAME in names) == (cell["name"] in CELLS)
