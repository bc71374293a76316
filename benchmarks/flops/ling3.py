"""Operations the ``ling3`` family needs per example (one sequence of
``input_shape[0]`` tokens), and what its delta rule needs, from the shapes.

Counted, two operations a multiply-add.  A layer:

- a Kimi-delta-attention mixer: the maps of the stream (``width x (5 H d +
  H)``: q, k, v, the decay's, the output gate's, beta's), the output map
  (``H d x width``) and the rule itself, **the chunked algorithm's products**
  (``ops/kda.py``; ``C`` positions a chunk, a head's keys and values ``d``
  wide): the pairs' decayed products of keys with keys below the diagonal
  and of queries with keys on and below it (``C (C - 1) / 2`` and ``C (C +
  1) / 2`` pairs of ``d``), the unit-triangular solve (``C (C - 1) / 2``
  rows of ``2 d``), the pseudo-values' correction by the state, the state's
  update and the queries' read of it (``C d d`` each) and the pairs' weighted
  pseudo-values (``C (C + 1) / 2`` of ``d``); a sequential recurrence would
  need ``3 d d`` a position and head, the chunked form about 70 d: that
  is what runs, and what is counted;
- or latent attention: its maps (``width x H (d_n + d_r)``, ``width x
  (kv_rank + d_r)``, ``kv_rank x H (d_n + d_v)``, ``width x H`` for the
  heads' gates, ``H d_v x width``) and the kernel's scores and weighted
  values over the causal pairs (``flops/xing4.py`` ``kernel_macs``);
- the feed-forward: three products of ``width x ffn_dim`` in a dense layer;
  in a layer with experts the router over all the experts, the shared
  expert's three products and the routed experts' three products **at the
  expected rows**: ``experts_per_token x experts_held / num_experts`` of a
  token's choices fall on an expert held here (0.125 at the published
  sizes; the choice within groups leaves that expectation as it is).

The logits are ``width x vocabulary``.  Not counted: the embedding look-up,
the norms, the short convolutions (four taps a channel), rotary, softmax,
the gates, the decays and their running sums, sigmoid, top-k, sort, gather
and scatter, the loss and the optimiser; pairs that are computed and masked,
lanes that are padded; rows past the routed ones; anything recomputed.  The
backward pass costs two more products per product.
"""

from __future__ import annotations

from benchmarks.flops.xing4 import held_choices_per_token, kernel_macs
from benchmarks.reference.ling3 import layer_kinds


def _length(dataset: dict) -> int:
    return dataset["input_shape"][0]


def mixer_kinds(model: dict) -> list[str]:
    """Every held layer's mixer, by the reference's rule."""
    return [mixer for mixer, _ in layer_kinds(model)]


def rule_macs(model: dict, dataset: dict) -> float:
    """Forward multiply-adds of one layer's chunked delta rule over one
    sequence (whole chunks: the padding's are not work done, the cell's
    length has none)."""
    chunk, d = model["chunk_size"], model["head_dim"]
    below, upto = chunk * (chunk - 1) // 2, chunk * (chunk + 1) // 2
    per_chunk = (below * d + upto * d           # A and B
                 + below * 2 * d                # the solve, [W | U0]
                 + 3 * chunk * d * d            # W S, the update, Q S
                 + upto * d)                    # B U
    return float(_length(dataset) / chunk * model["num_heads"] * per_chunk)


def layer_macs(model: dict, dataset: dict) -> dict[str, float]:
    """Forward multiply-adds over one sequence of the two mixers and the
    two feed-forwards."""
    length, width, heads = _length(dataset), model["width"], model["num_heads"]
    d_n, d_r, d_v = model["nope_dim"], model["rope_dim"], model["v_dim"]
    inner = heads * model["head_dim"]
    return {
        "kda": length * (width * (5 * inner + heads) + inner * width)
        + rule_macs(model, dataset),
        "mla": length * (
            width * heads * (d_n + d_r) + width * (model["kv_rank"] + d_r)
            + model["kv_rank"] * heads * (d_n + d_v) + width * heads
            + heads * d_v * width) + kernel_macs(model, dataset),
        "dense": length * 3 * width * model["ffn_dim"],
        "moe": length * (
            width * model["num_experts"]
            + 3 * width * model["shared_expert_dim"]
            + held_choices_per_token(model) * 3 * width
            * model["expert_dim"]),
    }


def forward_flops(model: dict, dataset: dict) -> float:
    """``model``/``dataset``: the configuration's sections of those names."""
    per_kind = layer_macs(model, dataset)
    macs = (sum(per_kind[kind] for kind in mixer_kinds(model))
            + model["dense_layers"] * per_kind["dense"]
            + (model["depth"] - model["dense_layers"]) * per_kind["moe"]
            + _length(dataset) * model["width"] * model["vocab_size"])
    return 2.0 * macs


def train_flops(model: dict, dataset: dict) -> float:
    return 3.0 * forward_flops(model, dataset)


def rule_flops(model: dict, dataset: dict, train: bool) -> float:
    """What the delta rule of ``ops/kda.py`` has to do for one sequence
    through every layer that has it; with ``train`` the backward's two
    products a product too."""
    forward = 2.0 * mixer_kinds(model).count("kda") * rule_macs(
        model, dataset)
    return 3.0 * forward if train else forward


def rule_bytes(model: dict, dataset: dict, train: bool,
               itemsize: int = 2) -> float:
    """The least the rule moves for one sequence through every layer that
    has it, each array once: forward it reads q, k, v (``itemsize`` an
    element), the float32 log-decays a channel and steps a head, and writes
    the output; backward it reads those and the output's gradient and
    writes five gradients."""
    positions = _length(dataset) * model["num_heads"]
    wide = positions * model["head_dim"]
    forward = wide * (3 * itemsize + 4) + positions * 4 + wide * itemsize
    backward = forward + wide * (3 * itemsize + 4) + positions * 4
    return float(mixer_kinds(model).count("kda")
                 * (forward + backward if train else forward))
