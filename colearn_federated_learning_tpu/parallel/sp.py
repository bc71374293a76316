"""Sequence-parallel model execution helpers.

A model built with ``seq_axis_name`` (models/registry.py) computes on
sequence SHARDS: ring attention over the axis, global position offsets,
psum-finished pooling.  These helpers wrap such a model in the
``shard_map`` it requires, for use OUTSIDE the federated engine (the engine
wires SP into its own round shard_map; see fed/engine.py).
"""

from __future__ import annotations

from typing import Callable

import jax
from jax.sharding import Mesh, PartitionSpec as P


def make_sp_apply(model, mesh: Mesh, seq_axis: str = "seq") -> Callable:
    """``fn(params, ids) -> logits`` running ``model`` sequence-parallel.

    ``ids``: full (B, L) token batch; internally sharded (B, L/S) per
    device along ``seq_axis``.  Logits are replicated (the model's pooling
    psum makes them identical on every shard).
    """
    if seq_axis not in mesh.shape:
        raise ValueError(f"mesh {tuple(mesh.shape)} has no {seq_axis!r} axis")

    def fwd(params, ids):
        return model.apply({"params": params}, ids, train=False)

    fn = jax.shard_map(
        fwd,
        mesh=mesh,
        in_specs=(P(), P(None, seq_axis)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def make_sp_loss_grad(model, loss_fn: Callable, mesh: Mesh,
                      seq_axis: str = "seq") -> Callable:
    """``fn(params, ids, labels) -> (loss, grads)`` sequence-parallel.

    Grads are pmean'd over ``seq_axis``; paired with the model's
    ``psum_for_grad_pmean`` pooling collective (parallel/collectives.py)
    this reconstructs the exact full-sequence gradient, replicated on every
    device (ready for any optimizer step).
    """
    if seq_axis not in mesh.shape:
        raise ValueError(f"mesh {tuple(mesh.shape)} has no {seq_axis!r} axis")

    def local(params, ids, labels):
        logits = model.apply({"params": params}, ids, train=True)
        return loss_fn(logits, labels)

    def body(params, ids, labels):
        loss, grads = jax.value_and_grad(local)(params, ids, labels)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, seq_axis), grads)
        return loss, grads

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(None, seq_axis), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)
