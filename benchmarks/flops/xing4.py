"""Operations the ``xing4`` family needs per example (one sequence of
``input_shape[0]`` tokens), and what its attention kernel needs, from the
shapes and the mask.

Counted, two operations a multiply-add.  A layer, dense or with experts:

- latent attention's five projections (``width x q_rank``, ``q_rank x H
  (d_n + d_r)``, ``width x (kv_rank + d_r)``, ``kv_rank x H (d_n + d_v)``,
  ``H d_v x width``) and, over the pairs the causal mask admits and every
  head, the scores (``d_n + d_r`` a pair) and the weighted values
  (``d_v``);
- the stream maps' scores, twice a layer: ``n width x (2 n + n^2)``;
- the feed-forward: three products of ``width x ffn_dim`` in a dense
  layer; in a layer with experts the router over all the experts, the
  shared expert's three products and the routed experts' three products
  **at the expected rows**: a token chooses ``experts_per_token`` of
  ``num_experts`` uniformly, so ``experts_per_token x experts_held /
  num_experts`` of those held here (0.5 at the published sizes).

The prediction module is the projection of the joined streams (``2 width x
width``) and one layer with experts; each head's logits are ``width x
vocabulary``.  Not counted: the embedding look-up, the norms, rotary,
softmax, the gates, the Sinkhorn iterations and the streams' mixing
(``n^2 + 2 n`` multiply-adds an element of the stream, none of them a
matrix product on the chip), sigmoid, top-k, sort, gather and scatter, the
loss and the optimiser; pairs a kernel visits and masks, lanes it pads;
rows past the routed ones; anything recomputed.  The backward pass costs
two more products per product.
"""

from __future__ import annotations


def _length(dataset: dict) -> int:
    return dataset["input_shape"][0]


def causal_pairs(dataset: dict) -> int:
    length = _length(dataset)
    return length * (length + 1) // 2


def held_choices_per_token(model: dict) -> float:
    return (model["experts_per_token"] * model["experts_held"]
            / model["num_experts"])


def kernel_macs(model: dict, dataset: dict) -> float:
    """Multiply-adds of one layer's attention kernel over one sequence,
    forward: scores over ``d_n + d_r`` and values of ``d_v`` a pair and
    head."""
    return float(causal_pairs(dataset) * model["num_heads"] * (
        model["nope_dim"] + model["rope_dim"] + model["v_dim"]))


def layer_macs(model: dict, dataset: dict) -> dict[str, float]:
    """Forward multiply-adds of a dense layer and of a layer with experts
    over one sequence."""
    length, width, heads = _length(dataset), model["width"], model["num_heads"]
    d_n, d_r, d_v = model["nope_dim"], model["rope_dim"], model["v_dim"]
    n = model["hc_streams"]
    attention = length * (
        width * model["q_rank"] + model["q_rank"] * heads * (d_n + d_r)
        + width * (model["kv_rank"] + d_r)
        + model["kv_rank"] * heads * (d_n + d_v)
        + heads * d_v * width) + kernel_macs(model, dataset)
    maps = 2 * length * n * width * (2 * n + n * n)
    dense = length * 3 * width * model["ffn_dim"]
    moe = length * (
        width * model["num_experts"] + 3 * width * model["shared_expert_dim"]
        + held_choices_per_token(model) * 3 * width * model["expert_dim"])
    return {"dense": attention + maps + dense, "moe": attention + maps + moe}


def forward_flops(model: dict, dataset: dict) -> float:
    """``model``/``dataset``: the configuration's sections of those names."""
    length, width = _length(dataset), model["width"]
    per_kind = layer_macs(model, dataset)
    modules = model["mtp_modules"]
    macs = (model["dense_layers"] * per_kind["dense"]
            + (model["depth"] - model["dense_layers"]) * per_kind["moe"]
            + modules * (length * 2 * width * width + per_kind["moe"])
            + (1 + modules) * length * width * model["vocab_size"])
    return 2.0 * macs


def train_flops(model: dict, dataset: dict) -> float:
    return 3.0 * forward_flops(model, dataset)


def attention_flops(model: dict, dataset: dict, train: bool) -> float:
    """What the attention kernel of ``ops/attention.py`` has to do for one
    sequence through every layer and the prediction module's: scores and
    weighted values over the causal pairs of every head; with ``train``
    the backward's four products too (the scores it recomputes are not
    work done)."""
    forward = 2.0 * (model["depth"] + model["mtp_modules"]) * kernel_macs(
        model, dataset)
    return 3.0 * forward if train else forward


def attention_bytes(model: dict, dataset: dict, train: bool,
                    itemsize: int = 2) -> float:
    """The least the kernel moves for one sequence through every layer,
    each array once, the keys at ``d_n + d_r`` a head and the values at
    ``d_v`` as the kernel is handed them: forward it reads q, k and v and
    writes the output and a float32 log-sum a query and head; backward it
    reads those, the output's gradient and the log-sums, and writes three
    gradients."""
    length, heads = _length(dataset), model["num_heads"]
    scored = length * heads * (model["nope_dim"] + model["rope_dim"]) * itemsize
    valued = length * heads * model["v_dim"] * itemsize
    sums = length * heads * 4
    forward = 2 * scored + 2 * valued + sums
    backward = 4 * scored + 4 * valued + 2 * sums
    return float((model["depth"] + model["mtp_modules"])
                 * (forward + backward if train else forward))
