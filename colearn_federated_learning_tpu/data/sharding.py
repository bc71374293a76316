"""Packing ragged per-client shards into static-shape stacked arrays.

Hard part #1 from SURVEY.md §7: clients own different numbers of examples,
but jit needs static shapes.  We pad every client's shard to a common
capacity ``M`` and carry a true-count vector; local training samples batch
indices modulo the true count so padding rows are never trained on, and the
FedAvg weight of a client is its true count, so padding never biases the
average either.

The leading axis of every leaf is the CLIENT axis — the axis that `vmap`
maps over on one chip and that `shard_map` shards over the device mesh.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class ClientShards:
    """Stacked, padded per-client data: leaves shaped (num_clients, M, ...)."""

    x: np.ndarray        # (C, M, *example_shape)
    y: np.ndarray        # (C, M) int32; (C, M, ...) with a label per token
    counts: np.ndarray   # (C,) int32 — true examples per client

    @property
    def num_clients(self) -> int:
        return self.x.shape[0]

    @property
    def capacity(self) -> int:
        return self.x.shape[1]


def pack_client_shards(
    x: np.ndarray,
    y: np.ndarray,
    parts: list[np.ndarray],
    capacity: int = 0,
) -> ClientShards:
    """Stack per-client index lists into padded (C, M, ...) arrays.

    ``capacity`` defaults to the largest shard.  Padding rows repeat the
    client's own data (cyclic tiling) rather than zeros, so even an
    out-of-range gather during debugging yields valid examples; correctness
    does not depend on it because sampling is always taken modulo
    ``counts``.
    """
    sizes = [len(p) for p in parts]
    if min(sizes) == 0:
        raise ValueError("pack_client_shards: a client has zero examples")
    cap = capacity or max(sizes)
    C = len(parts)
    # One fused (C*cap,) index vector, then a single row gather — the bulk
    # memcpy runs thread-parallel in the native library when available
    # (native/src/gather.cpp; the 3400-client config moves GBs here).
    tiled_all = np.empty((C, cap), dtype=np.int64)
    counts = np.zeros((C,), dtype=np.int32)
    for c, idx in enumerate(parts):
        take = np.asarray(idx[:cap])
        reps = int(np.ceil(cap / len(take)))
        tiled_all[c] = np.tile(take, reps)[:cap]
        counts[c] = min(len(idx), cap)
    from colearn_federated_learning_tpu import native

    flat = tiled_all.reshape(-1)
    xs = native.gather_rows(np.ascontiguousarray(x), flat)
    xs = xs.reshape((C, cap) + x.shape[1:])
    ys = np.asarray(y, np.int32)[tiled_all]
    return ClientShards(x=xs, y=ys, counts=counts)


def pad_clients_to_multiple(shards: ClientShards, multiple: int) -> ClientShards:
    """Pad the client axis so it divides the device mesh evenly.

    Ghost clients get count 0, which zeroes their FedAvg weight — they train
    on garbage (copies of client 0's rows) but contribute nothing.
    """
    C = shards.num_clients
    rem = (-C) % multiple
    if rem == 0:
        return shards
    pad_x = np.repeat(shards.x[:1], rem, axis=0)
    pad_y = np.repeat(shards.y[:1], rem, axis=0)
    return ClientShards(
        x=np.concatenate([shards.x, pad_x], axis=0),
        y=np.concatenate([shards.y, pad_y], axis=0),
        counts=np.concatenate([shards.counts, np.zeros(rem, np.int32)]),
    )
