"""The one traffic generator: a configuration's data set and a traffic
mix's federation shape, made from ``--seed``.

The class-conditional generators are copies of
``colearn_federated_learning_tpu/data/synthetic.py`` (images: a smoothed
class prototype plus Gaussian noise; text: class token buckets over random
ids, 0-padded to a random length), kept here so that a later PR cannot
change what the benchmark feeds the program.  Two things differ from the
originals, neither in what a sample looks like: the draws are made in
float32 and in bulk (every run of every cell pays this in set-up), and
the class structure (prototypes, token buckets) is the same for every
seed, so that seeds vary the samples, the partition and the weights but
not the task whose loss ``train_loss_r8_15`` reads.

A traffic mix is a JSON file of parameters beside this module:

    cohort         clients per round (``fed.cohort_size``)
    eval_every     rounds per chunk; the window is whole chunks, each
                   ending in one pass over the holdout
    holdout        examples in the held-out split
    num_clients, local_steps, batch, examples_per_client
                   optional; replace the configuration's own
"""

from __future__ import annotations

import numpy as np

from colearn_federated_learning_tpu.data.registry import Dataset, DatasetSpec
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)

PROTO_SEED = 1234     # the task itself: one class structure for all seeds
_REQUIRED = ("cohort", "eval_every", "holdout")
_OPTIONAL = ("num_clients", "local_steps", "batch", "examples_per_client")


def check_traffic(traffic: dict) -> None:
    missing = [k for k in _REQUIRED if k not in traffic]
    unknown = sorted(set(traffic) - set(_REQUIRED) - set(_OPTIONAL) - {"why"})
    if missing or unknown:
        raise ValueError(
            f"traffic mix needs {list(_REQUIRED)}, may set "
            f"{list(_OPTIONAL)}; missing {missing}, unknown {unknown}")


def experiment_config(config_doc: dict, traffic: dict,
                      seed: int) -> ExperimentConfig:
    """The ``ExperimentConfig`` a cell runs: the configuration's sections
    with the traffic mix's federation shape laid over them."""
    check_traffic(traffic)
    sections = config_doc["experiment"]
    data = dict(sections["data"])
    fed = dict(sections["fed"])
    run = dict(sections.get("run", {}))
    fed["cohort_size"] = traffic["cohort"]
    run["eval_every"] = traffic["eval_every"]
    for key, (section, field) in {
        "num_clients": (data, "num_clients"),
        "local_steps": (fed, "local_steps"),
        "batch": (fed, "batch_size"),
        "examples_per_client": (data, "max_examples_per_client"),
    }.items():
        if key in traffic:
            section[field] = traffic[key]
    run.update(seed=seed, backend="auto")
    return ExperimentConfig(
        data=DataConfig(**data), model=ModelConfig(**sections["model"]),
        fed=FedConfig(**fed), run=RunConfig(**run))


def images(n: int, shape: tuple[int, int, int], n_classes: int,
           rng: np.random.Generator, noise: float = 0.35):
    h, w, c = shape
    lo = max(2, h // 4), max(2, w // 4)
    protos_lo = np.random.default_rng(PROTO_SEED).normal(
        0.5, 0.5, size=(n_classes, *lo, c))
    up = np.ones((h // lo[0] + 1, w // lo[1] + 1))[..., None]
    protos = np.stack([np.kron(p, up)[:h, :w, :] for p in protos_lo])
    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    x = rng.standard_normal(size=(n, h, w, c), dtype=np.float32)
    x *= np.float32(noise)
    x += protos.astype(np.float32)[y]
    np.clip(x, 0.0, 1.0, out=x)
    return x, y


def text(n: int, seq_len: int, vocab_size: int, n_classes: int,
         rng: np.random.Generator, signal_tokens: int = 48):
    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    base = 1000
    x = rng.integers(1, vocab_size, size=(n, seq_len), dtype=np.int32)
    # A class's topic vocabulary is a run of ids above ``base``.
    topical = (base + y[:, None] * signal_tokens + rng.integers(
        0, signal_tokens, size=(n, seq_len), dtype=np.int32))
    x = np.where(rng.random((n, seq_len), dtype=np.float32) < 0.3,
                 topical, x)
    lengths = rng.integers(seq_len // 4, seq_len + 1, size=n)
    x[np.arange(seq_len)[None, :] >= lengths[:, None]] = 0
    return x, y


def dataset(config_doc: dict, traffic: dict, seed: int) -> Dataset:
    """Train and holdout splits for one run, handed to the program as its
    own ``Dataset``.  The two splits draw from one generator in turn, so
    they are disjoint draws of the same classes."""
    check_traffic(traffic)
    d = config_doc["dataset"]
    spec = DatasetSpec(
        name=d["name"], kind=d["kind"], input_shape=tuple(d["input_shape"]),
        num_classes=d["num_classes"], n_train=d["n_train"],
        n_test=traffic["holdout"], vocab_size=d.get("vocab_size", 0))
    rng = np.random.default_rng(seed)
    if spec.kind == "image":
        make = lambda n: images(n, spec.input_shape, spec.num_classes, rng)  # noqa: E731
    elif spec.kind == "text":
        make = lambda n: text(n, spec.input_shape[0], spec.vocab_size,  # noqa: E731
                              spec.num_classes, rng)
    else:
        raise ValueError(
            f"no generator for dataset kind {spec.kind!r} (image | text)")
    x_train, y_train = make(spec.n_train)
    x_test, y_test = make(spec.n_test)
    return Dataset(spec, x_train, y_train, x_test, y_test, "synthetic")
