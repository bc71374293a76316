"""Rank-r LoRA adapter plane: parameter-efficient federation.

Every round of the dense planes moves a full model delta per client, so
the wire plane's best uplink reduction is whatever the codec squeezes
out of O(model) floats (topk8: 12.62x, PERF.md §6).  LoRA (Hu et al.,
arXiv 2106.09685 — pattern only) changes the OBJECT being federated:
each targeted weight W keeps a frozen base and trains a rank-r pair
``B (m, r)`` / ``A (r, n)`` whose product is the update,

    W_eff = W + (alpha / r) * reshape(B @ A, W.shape),

so clients train and ship ONLY the factors — uplink drops from O(model)
to O(r * d) per adapted matrix, and because the factors are small DENSE
tensors they stay maskable under the Bonawitz secure-aggregation
protocol and foldable per aggregator slice, unlike sparse topk frames.

Targeting is driven by :mod:`parallel/partition`'s regex rule tables —
the SAME single source of partition truth the sharded server uses: a
leaf is adapted iff its first-matching rule carries a non-``None``
shard spec (the attention qkv + MLP matmuls, embeddings, MoE banks) and
the leaf has rank >= 2.  Biases/norms that the rules replicate stay
frozen at the base value — the classic adapters-only regime.

Factorization picks the split that minimizes ``m + n`` over the leaf's
dims (``B`` absorbs the leading group, ``A`` the trailing group), so a
``(D, H, hd)`` attention kernel factors as ``(D, r) x (r, H*hd)`` —
O(r * D) — instead of pairing a tiny leading dim with a huge flattened
tail.  Factors inherit the base param's PartitionSpec on the sharded
axis: a base sharded on its leading dim shards ``B`` as ``P(axis,
None)``; a base sharded on the first trailing dim shards ``A`` as
``P(None, axis)`` (both correspond to contiguous row-major blocks of
the flattened factor dims); any other sharded dim replicates the
factors — numerics are placement-independent either way.

Everything here is pure-jax tree math; the client/server wiring lives
in fed/local.py (factor-only trainer), comm/worker.py and
comm/coordinator.py (factor uplink + shard-wise merge).
"""

from __future__ import annotations

import re
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from colearn_federated_learning_tpu.parallel import partition

# Factor leaves live under these keys at the adapted param's tree
# position; the pair dict replaces the base leaf in the factor tree.
A_KEY = "lora_a"
B_KEY = "lora_b"

# Default init scale for A (B starts at zero, so the initial delta is
# exactly zero and round 0 matches the base model bit-for-bit).
DEFAULT_SIGMA = 0.02


# ------------------------------------------------------------ targeting --
def _compile_rules(rules):
    out = []
    for rule in rules:
        pat, spec = rule[0], rule[1]
        ndim = rule[2] if len(rule) > 2 else None
        out.append((re.compile(pat), spec, ndim))
    return out


def _raw_spec(compiled, name: str, shape) -> Any:
    """First-match raw rule spec for a '/'-joined path — the same
    ordered ``re.search`` walk :func:`partition.match_partition_rules`
    resolves PartitionSpecs with, but BEFORE divisibility resolution:
    targeting must not depend on the mesh size of the current run."""
    if len(shape) == 0:
        return None
    for pat, spec, ndim in compiled:
        if ndim is not None and len(shape) != ndim:
            continue
        if pat.search(name):
            return spec
    return None


def target_paths(params: Any, model_name: str = "",
                 rules: Optional[tuple] = None) -> dict:
    """``{path: shape}`` of the adapted leaves: first-matching partition
    rule has a non-None spec AND the leaf has rank >= 2.

    Bias leaves are never adapted even when rank >= 2 (reshaped-head
    attention biases are (heads, head_dim)): rank-r factors on a bias
    cost ``r*(m+n)`` against an ``m*n`` original — MORE bytes, no
    low-rank structure to exploit."""
    compiled = _compile_rules(
        rules if rules is not None else partition.rules_for_model(model_name))
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        shape = tuple(np.shape(leaf))
        name = partition.path_str(path)
        if name.rsplit("/", 1)[-1] == "bias":
            continue
        if len(shape) >= 2 and _raw_spec(compiled, name, shape) is not None:
            out[name] = shape
    return out


def split_point(shape) -> int:
    """Factorization split k minimizing prod(shape[:k]) + prod(shape[k:])
    (ties break low — deterministic, shape-only)."""
    best_k, best = 1, None
    for k in range(1, len(shape)):
        m = int(np.prod(shape[:k], dtype=np.int64))
        n = int(np.prod(shape[k:], dtype=np.int64))
        if best is None or m + n < best:
            best_k, best = k, m + n
    return best_k


def factor_dims(shape) -> tuple[int, int]:
    """(m, n) of the ``B (m, r) @ A (r, n)`` factorization for a leaf."""
    k = split_point(shape)
    return (int(np.prod(shape[:k], dtype=np.int64)),
            int(np.prod(shape[k:], dtype=np.int64)))


def _nested_set(tree: dict, path: str, value: Any) -> None:
    keys = path.split("/")
    node = tree
    for k in keys[:-1]:
        node = node.setdefault(k, {})
    node[keys[-1]] = value


def init_factors(params: Any, rank: int, key: Optional[jax.Array] = None,
                 model_name: str = "", rules: Optional[tuple] = None,
                 sigma: float = DEFAULT_SIGMA) -> dict:
    """Factor tree for ``params``: at every adapted leaf position a
    ``{A_KEY: (r, n) f32, B_KEY: (m, r) f32}`` pair; non-adapted leaves
    are absent entirely (the uplink ships ONLY factors).

    ``A ~ N(0, sigma)`` per leaf (deterministically keyed by the leaf's
    index under ``key``), ``B = 0`` — so the initial adapter delta is
    exactly zero.  ``key=None`` zeros A too: the shape-template mode
    folder construction and wire pricing use (frame lengths depend only
    on shapes/dtypes)."""
    targets = target_paths(params, model_name=model_name, rules=rules)
    out: dict = {}
    for i, (path, shape) in enumerate(sorted(targets.items())):
        m, n = factor_dims(shape)
        if key is None:
            a = jnp.zeros((rank, n), jnp.float32)
        else:
            a = sigma * jax.random.normal(
                jax.random.fold_in(key, i), (rank, n), jnp.float32)
        _nested_set(out, path, {
            A_KEY: a,
            B_KEY: jnp.zeros((m, rank), jnp.float32),
        })
    return out


def factor_index(factors: Any) -> dict:
    """Flatten a factor tree to ``{path: (A, B)}`` (trace-time walk)."""
    out: dict = {}

    def walk(node, prefix):
        if isinstance(node, Mapping):
            keys = set(node.keys())
            if keys == {A_KEY, B_KEY}:
                out[prefix] = (node[A_KEY], node[B_KEY])
            else:
                for k in node:
                    walk(node[k], f"{prefix}/{k}" if prefix else str(k))

    walk(factors, "")
    return out


def count_factor_params(factors: Any) -> int:
    return sum(int(np.prod(np.shape(l), dtype=np.int64))
               for l in jax.tree.leaves(factors))


# ---------------------------------------------------------- apply / merge --
def _adapted_tree(params: Any, factors: Any, alpha: float, rank: int) -> Any:
    """params + (alpha/rank) * reshape(B @ A) at every factor position.

    Float32 accumulate, base dtype preserved (the downlink
    ``apply_dense_delta`` convention) — inside jit this differentiates
    w.r.t. the factors with the base frozen; eagerly it IS the merge."""
    idx = factor_index(factors)
    scale = alpha / float(rank)

    def f(path, w):
        ab = idx.get(partition.path_str(path))
        if ab is None:
            return w
        a, b = ab
        delta = (b @ a).reshape(np.shape(w)) * scale
        return (w.astype(jnp.float32) + delta).astype(jnp.dtype(w.dtype))

    return jax.tree_util.tree_map_with_path(f, params)


def apply_adapters(params: Any, factors: Any, alpha: float,
                   rank: int) -> Any:
    """Effective params for the forward pass (pure-jax; jit-safe)."""
    return _adapted_tree(params, factors, alpha, rank)


def merge_adapters(params: Any, factors: Any, alpha: float,
                   rank: int) -> Any:
    """Fold B·A·(alpha/r) INTO the base params — same math as
    :func:`apply_adapters`, named for the server's merge event.  On a
    tp-sharded params tree run it under jit: every op is elementwise in
    the base leaf (plus a small replicated ``B @ A`` contraction over
    r), so XLA keeps each leaf's output in its input sharding — no
    full-tree gather."""
    return _adapted_tree(params, factors, alpha, rank)


def reset_factors(factors: Any) -> Any:
    """Post-merge reset: B <- 0 (the merged delta is now in the base),
    A kept — the next cycle resumes from the same A basis, keeping one
    compile signature and exact oracle reproducibility."""

    def walk(node):
        if isinstance(node, Mapping):
            if set(node.keys()) == {A_KEY, B_KEY}:
                return {A_KEY: node[A_KEY],
                        B_KEY: jnp.zeros_like(node[B_KEY])}
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(factors)


# ------------------------------------------------------ sharding specs --
def factor_specs(params: Any, rank: int, axis: str = "model",
                 model_name: str = "", rules: Optional[tuple] = None,
                 sizes: Optional[Mapping[str, int]] = None) -> dict:
    """PartitionSpec tree for a factor tree — the base param's resolved
    spec inherited onto the factor whose flattened dim group contains
    the sharded base dim as its MAJOR (row-contiguous) component:

    - base sharded at dim 0        -> B: P(axis, None)
    - base sharded at dim split(k) -> A: P(None, axis)
    - anything else                -> replicated factors

    Divisibility follows :func:`partition._resolve_spec` semantics: an
    indivisible factor dim replicates (numerics-exact either way)."""
    rules = rules if rules is not None else partition.rules_for_model(
        model_name)
    sizes = dict(sizes or {})
    specs = partition.match_partition_rules(
        rules, params, axis=axis, sizes=sizes)
    spec_by_path = {
        partition.path_str(p): s
        for p, s in jax.tree_util.tree_leaves_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))
    }
    size = int(sizes.get(axis, 0))
    out: dict = {}
    for path, shape in sorted(target_paths(
            params, model_name=model_name, rules=rules).items()):
        spec = spec_by_path.get(path, P())
        sharded_dim = next(
            (d for d, name in enumerate(spec) if name == axis), None)
        k = split_point(shape)
        m, n = factor_dims(shape)
        a_spec, b_spec = P(), P()
        if sharded_dim == 0 and (not size or m % size == 0):
            b_spec = P(axis, None)
        elif sharded_dim == k and (not size or n % size == 0):
            a_spec = P(None, axis)
        _nested_set(out, path, {A_KEY: a_spec, B_KEY: b_spec})
    return out
