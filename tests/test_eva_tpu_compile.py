"""The EVA attention path, and a local step of the model around it,
compiled for a described TPU v5e at the cell's real widths, forward and
backward, with no chip attached: Mosaic refuses here what it would refuse
there (a misaligned slice, too much VMEM), which interpret mode cannot
show.  Skipped where no v5e can be described.

The topology is described inside a fixture, after this file's tests have
started, and in this file alone: a process that loads the TPU's library
keeps it until it exits.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """``ops/attention.py`` asks jax for its backend, which here is the
    CPU: hand it the v5e's answers (generation 5, Mosaic, not the
    interpreter), and keep what is compiled out of the persistent cache
    (an executable for an absent chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    from colearn_federated_learning_tpu.ops import attention

    blocks = attention._blocks
    monkeypatch.setattr(attention, "_tpu_generation", lambda: 5)
    monkeypatch.setattr(
        attention, "_blocks",
        lambda q, k, v, mask, bq, bk, interpret: blocks(
            q, k, v, mask, bq, bk, False))
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def test_eva_attention_compiles_at_the_published_widths(one_chip,
                                                        as_on_the_chip):
    """One sequence of 16,384 positions, 32 heads of 128, window 2,048,
    chunk 16: 8 folded rows of 896 summaries and 2,048 keys."""
    from colearn_federated_learning_tpu.ops.eva import eva_attention

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss_and_grads(q, k, v, mu, phi):
        def loss(*args):
            out = eva_attention(*args, window=2048, chunk=16, impl="flash")
            return jnp.sum(out.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
            q, k, v, mu, phi)

    qkv = shape(1, 16384, 32, 128)
    vector = shape(32, 128, dtype=jnp.float32)
    compiled = jax.jit(loss_and_grads).lower(
        qkv, qkv, qkv, vector, vector).compile()
    text = compiled.as_text()
    # The three kernels, by the names the benchmark's readers look for,
    # and no score matrix beside them.
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert name in text and "tpu_custom_call" in text, name
    assert "2048,2944]" not in text and "2048,3072]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


def test_latent_attention_kernels_compile_at_the_published_widths(
        one_chip, as_on_the_chip):
    """One sequence of 8,192 positions, 32 heads, scores over 192 (128 + 64
    rotary) and values of 128 under a scale of the caller's: Mosaic takes
    the 192-wide blocks as the arrays' full last dimension, forward and
    both backward kernels, and the result has the values' width."""
    from colearn_federated_learning_tpu.ops.attention import flash_attention

    def shape(width):
        return jax.ShapeDtypeStruct((1, 8192, 32, width), jnp.bfloat16,
                                    sharding=one_chip)

    def loss_and_grads(q, k, v):
        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True,
                                  scale=2.0048 * 192 ** -0.5)
            assert out.shape == (1, 8192, 32, 128)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(loss_and_grads).lower(
        shape(192), shape(192), shape(128)).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert name in text and "tpu_custom_call" in text, name
    assert "8192,8192]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_a_rematerialised_step_runs_flash_fwd_once_a_layer(one_chip,
                                                           as_on_the_chip):
    """One local step (gradient and SGD, the weights donated) of the
    shipped model, 4 rematerialised layers at the published widths, one
    sequence of 16,384: each block keeps the kernel's output and its
    log-sum, so ``flash_fwd`` is there 4 times (plain ``nn.remat``: 8),
    and the log-sum is kept dense (5.66 GB of temporaries; as the kernel
    writes it, a tile per row, 6.39; nothing kept, 5.25)."""
    from colearn_federated_learning_tpu.fed import losses
    from colearn_federated_learning_tpu.models import registry
    from colearn_federated_learning_tpu.utils.config import get_config

    config = get_config("evabyte_fedavg").model
    assert config.remat and config.depth == 4 and config.seq_len == 16384
    model = registry.build_model(config)

    def on_the_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    ids = jnp.zeros((1, config.seq_len), jnp.int32)
    y = jnp.zeros((1, config.seq_len, config.num_pred_heads), jnp.int32)
    params = jax.eval_shape(
        lambda: registry.init_params(model, ids, jax.random.PRNGKey(0)))

    def step(params, ids, y):
        grads = jax.grad(lambda p: losses.softmax_cross_entropy(
            model.apply({"params": p}, ids, train=True), y))(params)
        return jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)

    compiled = jax.jit(step, donate_argnums=0).lower(
        *on_the_chip((params, ids, y))).compile()
    kernels = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    for name, calls in (("flash_fwd", 4), ("flash_dq", 4), ("flash_dkv", 4)):
        assert sum(f"/{name}/" in line for line in kernels) == calls, name
    assert compiled.memory_analysis().temp_size_in_bytes < 6.0e9


def test_the_share_layer_compiles_under_a_client_axis(one_chip,
                                                      as_on_the_chip):
    """One chip's share of a LatentMoE layer at the published widths (8 of
    512 experts, top 22, latent 1,024, experts 2,688), one block of 4,096
    tokens, gradient and all, mapped over a client axis as
    ``fed/programs.py`` maps it: the chip's compiler refuses a grouped
    product with a batch dimension, so each of the eight (two in the
    forward loop's body; in the backward loop's the same two again, two for
    the rows and two for the banks) must come out as its own ``ragged-dot``
    kernel without one, at a tile's rows and not at the static bound of
    4,096 x 8, which nothing in the program has as an array of the latent or
    the expert width."""
    from colearn_federated_learning_tpu.models.moe import LatentMoEShare

    layer = LatentMoEShare(
        embed_dim=4096, latent_dim=1024, expert_dim=2688, shared_dim=5376,
        experts_total=512, experts_held=(0, 8), top_k=22, routed_scale=5.0,
        dtype=jnp.bfloat16)
    u = jnp.zeros((4096, 4096), jnp.float32)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), u)["params"])

    def grads(p, u):
        return jax.grad(lambda p, u: jnp.sum(
            layer.apply({"params": p}, u).astype(jnp.float32) ** 2),
            argnums=(0, 1))(p, u)

    def with_client_axis(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            (1,) + a.shape, a.dtype, sharding=one_chip), tree)

    compiled = jax.jit(jax.vmap(grads)).lower(
        *with_client_axis((params, u))).compile()
    kernels = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and "ragged-dot-none" in line.split("=")[0]]
    assert len(kernels) == 8, len(kernels)
    tile = layer.row_tile
    assert all(f"[{tile}," in line or "[8," in line.split("custom-call")[0]
               for line in kernels)
    assert not any(f"[32768,{width}]" in compiled.as_text()
                   for width in (1024, 2688))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


SHARE_LAYERS = {
    "latent_22_of_512": ("LatentMoEShare", dict(
        embed_dim=4096, latent_dim=1024, expert_dim=2688, shared_dim=5376,
        experts_total=512, experts_held=(0, 8), top_k=22, routed_scale=5.0),
        1024),
    "gated_4_of_64": ("GatedMoEShare", dict(
        embed_dim=3584, expert_dim=1024, shared_dim=1024, experts_total=64,
        experts_held=(0, 8), top_k=4, routed_scale=2.0), 3584),
}


@pytest.mark.parametrize("share", sorted(SHARE_LAYERS))
def test_the_share_layers_routing_gathers_rows_only(share, one_chip,
                                                    as_on_the_chip):
    """Both share layers at the published widths, a block of 4,096 tokens,
    gradient and all under a client axis, as the chip's compiler leaves
    them: every gather and scatter moves whole rows of the experts' input
    (the tile's rows and their way back), none a scalar of the scores, the
    weights or the pairs; the chosen scores' compare-select-sum over
    (tokens, choices, experts) is fused, so no array of that shape is
    written; and a block's pairs meet two sorts of 8 x 4,096 keys with the
    weights beside them, one forward and one backward."""
    import re

    from colearn_federated_learning_tpu.models import moe

    name, sizes, row_width = SHARE_LAYERS[share]
    layer = getattr(moe, name)(dtype=jnp.bfloat16, **sizes)
    u = jnp.zeros((4096, sizes["embed_dim"]), jnp.float32)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), u)["params"])

    def grads(p, u):
        return jax.grad(lambda p, u: jnp.sum(
            layer.apply({"params": p}, u).astype(jnp.float32) ** 2),
            argnums=(0, 1))(p, u)

    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (1,) + a.shape, a.dtype, sharding=one_chip), (params, u))
    text = jax.jit(jax.vmap(grads)).lower(*shapes).compile().as_text()
    moved = re.findall(r"= \w+\[([\d,]*)\]\S* (?:gather|scatter)\(", text)
    assert moved and all(
        dims.split(",")[-1] == str(row_width) for dims in moved), moved
    # Outside a fusion, an instruction's result is an array in memory.
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    wide = f"[4096,{sizes['top_k']},{sizes['experts_total']}]"
    body = None
    for line in text.splitlines():
        opened = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if opened:
            body = opened.group(1)
        elif body not in fused and " = " in line:
            assert wide not in line.split(" = ")[1].split("(")[0].replace(
                "1,", ""), line
    pair_sorts = re.findall(
        r"= \(s32\[1,32768\]\S*, f32\[1,32768\]\S*\) sort\(", text)
    assert len(pair_sorts) == 2, len(pair_sorts)
