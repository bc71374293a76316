"""Operations the ``nemotron_h`` family needs per example (one sequence of
``input_shape[0]`` tokens), and what its state-space scan and its
attention kernel need, from the shapes and the mask.

Counted, two operations a multiply-add, a layer of each kind:

``M``  the two projections (``width x (2 H P + 2 G N + H)`` in, ``H P x
       width`` out), the convolution's taps, and the scan as the chunked
       algorithm needs it (``scan_macs``): inside a chunk the causal pairs
       of ``C_t . B_s`` (``N`` a group) and of scores times inputs (``P`` a
       head), the chunk's state (``P N`` a head and position), reading the
       carried state (``P N`` a head and position) and carrying it (``P N``
       a head and chunk);
``E``  the router over all the experts, the latent projections down and
       up, the shared expert's two products, and the routed experts' two
       products **at the expected rows**: a token chooses
       ``experts_per_token`` of ``num_experts`` uniformly, so
       ``experts_per_token x experts_held / num_experts`` of those held
       here (0.34 at the published sizes);
``*``  the four projections (key and value at their fewer heads) and the
       scores and weighted values over the pairs the causal mask admits.

and the head.  Not counted: the embedding look-up, the norms, the gates,
softmax, sigmoid, top-k, sort, gather and scatter, the loss and the
optimiser; pairs a kernel visits and masks; rows past the routed ones;
anything recomputed.  The backward pass costs two more products per
product.
"""

from __future__ import annotations


def _length(dataset: dict) -> int:
    return dataset["input_shape"][0]


def scan_macs(model: dict, dataset: dict) -> float:
    """Multiply-adds of the state-space scan of one ``M`` layer over one
    sequence, forward."""
    length, chunk = _length(dataset), model["chunk_size"]
    heads, width = model["mamba_heads"], model["mamba_head_dim"]
    groups, state = model["mamba_groups"], model["ssm_state_size"]
    chunk = min(chunk, length)
    pairs = (length // chunk) * chunk * (chunk + 1) // 2
    return float(pairs * (groups * state + heads * width)
                 + 2 * length * heads * width * state
                 + (length // chunk) * heads * width * state)


def causal_pairs(dataset: dict) -> int:
    length = _length(dataset)
    return length * (length + 1) // 2


def held_choices_per_token(model: dict) -> float:
    return (model["experts_per_token"] * model["experts_held"]
            / model["num_experts"])


def layer_macs(model: dict, dataset: dict) -> dict[str, float]:
    """Forward multiply-adds of one layer of each kind over one
    sequence."""
    length, width = _length(dataset), model["width"]
    inner = model["mamba_heads"] * model["mamba_head_dim"]
    bc = model["mamba_groups"] * model["ssm_state_size"]
    mamba = (length * (width * (2 * inner + 2 * bc + model["mamba_heads"])
                       + inner * width
                       + model["conv_kernel"] * (inner + 2 * bc))
             + scan_macs(model, dataset))
    moe = length * (
        width * model["num_experts"] + 2 * width * model["latent_dim"]
        + 2 * width * model["shared_expert_dim"]
        + held_choices_per_token(model)
        * 2 * model["latent_dim"] * model["expert_dim"])
    q = model["num_heads"] * model["head_dim"]
    kv = model["num_kv_heads"] * model["head_dim"]
    attention = (length * width * (2 * q + 2 * kv)
                 + 2 * q * causal_pairs(dataset))
    return {"M": mamba, "E": moe, "*": attention}


def forward_flops(model: dict, dataset: dict) -> float:
    """``model``/``dataset``: the configuration's sections of those names."""
    per_kind = layer_macs(model, dataset)
    macs = (sum(per_kind[letter] for letter in model["layer_pattern"])
            + _length(dataset) * model["width"] * model["vocab_size"])
    return 2.0 * macs


def train_flops(model: dict, dataset: dict) -> float:
    return 3.0 * forward_flops(model, dataset)


def scan_flops(model: dict, dataset: dict, train: bool) -> float:
    """What the scans of every ``M`` layer have to do for one sequence."""
    forward = 2.0 * model["layer_pattern"].count("M") * scan_macs(
        model, dataset)
    return 3.0 * forward if train else forward


def scan_bytes(model: dict, dataset: dict, train: bool,
               itemsize: int = 2) -> float:
    """The least the scans of every ``M`` layer move between HBM and the
    chip for one sequence, each array once: forward they read ``x``, ``B``,
    ``C`` and a float32 step a head and write ``y``; backward they read
    those and ``y``'s gradient and write four gradients."""
    inner = model["mamba_heads"] * model["mamba_head_dim"]
    bc = model["mamba_groups"] * model["ssm_state_size"]
    steps = model["mamba_heads"] * 4
    forward = (2 * inner + 2 * bc) * itemsize + steps
    backward = (3 * inner + 4 * bc) * itemsize + 2 * steps
    per_token = forward + backward if train else forward
    return float(model["layer_pattern"].count("M") * _length(dataset)
                 * per_token)


def attention_flops(model: dict, dataset: dict, train: bool) -> float:
    """What the attention kernel of ``ops/attention.py`` has to do for one
    sequence through every ``*`` layer: scores and weighted values over
    the causal pairs of every query head; with ``train`` the backward's
    four products too (the scores it recomputes are not work done)."""
    forward = (2.0 * model["layer_pattern"].count("*") * 2
               * model["num_heads"] * model["head_dim"]
               * causal_pairs(dataset))
    return 3.0 * forward if train else forward


def attention_bytes(model: dict, dataset: dict, train: bool,
                    itemsize: int = 2) -> float:
    """The least the kernel moves for one sequence through every ``*``
    layer, each array once and the keys and values at the heads they have
    (a kernel handed a copy a query head moves more): forward it reads q,
    k and v and writes the output and a float32 log-sum a query and head;
    backward it reads those, the output's gradient and the log-sums, and
    writes three gradients."""
    length = _length(dataset)
    queries = length * model["num_heads"] * model["head_dim"] * itemsize
    keys = length * model["num_kv_heads"] * model["head_dim"] * itemsize
    sums = length * model["num_heads"] * 4
    forward = 2 * queries + 2 * keys + sums
    backward = 4 * queries + 4 * keys + 2 * sums
    return float(model["layer_pattern"].count("*")
                 * (forward + backward if train else forward))
