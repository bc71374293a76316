"""The gated delta rule's kernels (ops/kda.py) and the short convolution's
(ops/kda_conv.py) compiled for a described TPU v5e at the cell's widths,
forward and backward, with no chip attached:
Mosaic refuses here what it would refuse there (an alignment, too much
VMEM, a product it cannot lower), which interpret mode cannot show.
Skipped where no v5e can be described.

The topology is described inside a fixture, after this file's tests have
started, and in this file alone: a process that loads the TPU's library
keeps it until it exits.
"""

import re

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
    """``ops/kda.py`` and ``ops/kda_conv.py`` ask jax for its backend, which
    here is the CPU: hand them the chip's answer (Mosaic, not the
    interpreter), and keep what is compiled out of the persistent cache (an
    executable for an absent chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    from colearn_federated_learning_tpu.ops import kda, kda_conv

    monkeypatch.setattr(kda, "_interpret", lambda: False)
    monkeypatch.setattr(kda_conv, "_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _kernels(text):
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def test_the_rule_compiles_at_the_cells_widths(one_chip, as_on_the_chip):
    """One sequence of 4,096 positions, 32 heads of 128, chunks of 64 in
    sub-blocks of 16, bf16 q, k, v and float32 decays and steps: value and
    all five gradients, one forward and one backward kernel, and no chunk's
    pair matrices or solve (``[.., 64, 64]``) as an array of the program."""
    from colearn_federated_learning_tpu import telemetry
    from colearn_federated_learning_tpu.ops.kda import kda_chunked

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss_and_grads(q, k, v, g, beta):
        def loss(*args):
            out = kda_chunked(*args, chunk=64, sub_block=16)
            assert out.shape == (1, 4096, 32, 128) and out.dtype == v.dtype
            return jnp.sum(out.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss, argnums=range(5))(q, k, v, g, beta)

    counter = telemetry.get_registry().counter(
        "ops.kda_trace_total", labels={"mode": "mosaic"})
    traced = counter.value
    wide = shape(1, 4096, 32, 128)
    compiled = jax.jit(loss_and_grads).lower(
        wide, wide, wide, shape(1, 4096, 32, 128, dtype=jnp.float32),
        shape(1, 4096, 32, dtype=jnp.float32)).compile()
    assert counter.value - traced == 2
    text = compiled.as_text()
    kernels = _kernels(text)
    for name in ("kda_fwd", "kda_bwd"):
        assert sum(name in line for line in kernels) == 1, name
    assert ",64,64]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_the_evaluations_rule_keeps_nothing(one_chip, as_on_the_chip):
    """The primal call, as the evaluation program makes it, writes the
    output alone: one forward kernel with one result."""
    from colearn_federated_learning_tpu.ops.kda import kda_chunked

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    wide = shape(1, 4096, 32, 128)
    compiled = jax.jit(kda_chunked).lower(
        wide, wide, wide, shape(1, 4096, 32, 128, dtype=jnp.float32),
        shape(1, 4096, 32, dtype=jnp.float32)).compile()
    kernels = _kernels(compiled.as_text())
    assert len(kernels) == 1 and "kda_fwd" in kernels[0]
    assert kernels[0].split(" = ")[1].startswith("bf16[1,4096,4096]")


def test_the_convolution_compiles_at_the_cells_widths(one_chip,
                                                      as_on_the_chip):
    """The maps' float32 sums of one sequence, (1, 4,096, 20,512), four taps
    over q's, k's and v's 3 x 32 heads of 128, bf16 q, k and v: value and
    both gradients, one forward and one backward kernel, and the kernels
    read q's, k's and v's columns where they lie: no float32 copy of them
    is sliced out of the sums."""
    from colearn_federated_learning_tpu import telemetry
    from colearn_federated_learning_tpu.ops.kda_conv import conv_qkv

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss_and_grads(x, taps):
        def loss(x, taps):
            q, k, v, rest = conv_qkv(x, taps, heads=32, head_dim=128,
                                     eps=1e-6, dtype=jnp.bfloat16)
            assert q.shape == (1, 4096, 4096) and q.dtype == jnp.bfloat16
            return sum(jnp.sum(a.astype(jnp.float32) ** 2)
                       for a in (q, k, v, rest))

        return jax.value_and_grad(loss, argnums=(0, 1))(x, taps)

    counter = telemetry.get_registry().counter(
        "ops.kda_conv_trace_total", labels={"mode": "mosaic"})
    traced = counter.value
    compiled = jax.jit(loss_and_grads).lower(
        shape(1, 4096, 20512), shape(4, 12288)).compile()
    assert counter.value - traced == 2
    text = compiled.as_text()
    kernels = _kernels(text)
    for name in ("kda_conv_fwd", "kda_conv_bwd"):
        assert sum(name in line for line in kernels) == 1, name
    assert not re.search(r"= f32\[1,4096,12288\]\S* slice\(", text)
