"""Dataset registry for the five benchmark corpora (BASELINE.json configs).

Resolution order per dataset name:
1. A real on-disk copy: ``$COLEARN_DATA_DIR/<name>.npz`` with arrays
   ``x_train, y_train, x_test, y_test`` (the standard keras-style layout).
2. Deterministic synthetic data with identical shapes (data/synthetic.py) —
   required because this sandbox has no network and no dataset files.

Either way the caller receives static-shape numpy arrays; everything after
this point is jit-compatible.
"""

from __future__ import annotations

import dataclasses
import os
import zlib

import numpy as np

from colearn_federated_learning_tpu.data import synthetic


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    kind: str                      # "image" | "text" | "timeseries" | "bytes"
                                   # | "tokens"
    input_shape: tuple[int, ...]   # per-example shape: image HWC,
                                   # text (seq_len,), timeseries (T, F)
    num_classes: int
    n_train: int                   # synthetic fallback sizes
    n_test: int
    vocab_size: int = 0            # text only
    horizon: int = 1               # "tokens_ahead": labels a position


SPECS: dict[str, DatasetSpec] = {
    "mnist": DatasetSpec("mnist", "image", (28, 28, 1), 10, 60_000, 10_000),
    "cifar10": DatasetSpec("cifar10", "image", (32, 32, 3), 10, 50_000, 10_000),
    "cifar100": DatasetSpec("cifar100", "image", (32, 32, 3), 100, 50_000, 10_000),
    "femnist": DatasetSpec("femnist", "image", (28, 28, 1), 62, 80_000, 10_000),
    "agnews": DatasetSpec("agnews", "text", (128,), 4, 120_000, 7_600),
    # Tiny variants for tests / smoke runs (same shapes, far fewer rows).
    "mnist_tiny": DatasetSpec("mnist_tiny", "image", (28, 28, 1), 10, 2_000, 400),
    "cifar10_tiny": DatasetSpec("cifar10_tiny", "image", (32, 32, 3), 10, 2_000, 400),
    "agnews_tiny": DatasetSpec("agnews_tiny", "text", (64,), 4, 1_000, 200, vocab_size=2_000),
    # IoT traffic windows (T, F) — the reference's ACTUAL task domain
    # (network-anomaly detection at the edge, SURVEY.md §0); 8 classes =
    # benign + 7 attack families.
    "iot_traffic": DatasetSpec("iot_traffic", "timeseries", (64, 16), 8,
                               40_000, 8_000),
    "iot_traffic_tiny": DatasetSpec("iot_traffic_tiny", "timeseries",
                                    (64, 16), 8, 2_000, 400),
    # Packed byte documents for a next-byte model (models/evabyte.py): an
    # example is one sequence, its labels the 8 bytes after each position.
    "bytes": DatasetSpec("bytes", "bytes", (16_384,), 320, 128, 4,
                         vocab_size=320),
    "bytes_tiny": DatasetSpec("bytes_tiny", "bytes", (128,), 320, 64, 8,
                              vocab_size=320),
    # Packed token documents for a next-token model (models/nemotron_h.py)
    # over a slice of its vocabulary: an example is one sequence, its
    # labels the token after each position.
    "tokens": DatasetSpec("tokens", "tokens", (16_384,), 16_384, 128, 4,
                          vocab_size=16_384),
    "tokens_tiny": DatasetSpec("tokens_tiny", "tokens", (64,), 96, 64, 8,
                               vocab_size=96),
    # The same stream at 4,096 positions over an eighth of a 157,184-word
    # vocabulary (models/ling3.py).
    "tokens_4k": DatasetSpec("tokens_4k", "tokens", (4_096,), 19_648, 128, 4,
                             vocab_size=19_648),
    # The same stream for a model with sequential prediction modules
    # (models/xing4.py): the labels of a position are the ``horizon``
    # tokens after it, one and each module's (the shipped model has none).
    "tokens_ahead": DatasetSpec("tokens_ahead", "tokens_ahead", (8_192,),
                                16_384, 128, 4, vocab_size=16_384,
                                horizon=1),
    "tokens_ahead_tiny": DatasetSpec("tokens_ahead_tiny", "tokens_ahead",
                                     (64,), 96, 64, 8, vocab_size=96,
                                     horizon=2),
}
# Kinds whose labels are a block per example (one per position), not a class.
LABEL_PER_TOKEN_KINDS = ("bytes", "tokens", "tokens_ahead")


@dataclasses.dataclass
class Dataset:
    spec: DatasetSpec
    x_train: np.ndarray
    y_train: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray
    source: str  # "disk" | "synthetic"


def _load_disk(spec: DatasetSpec) -> Dataset | None:
    """Load ``$COLEARN_DATA_DIR/<name>.npz`` (keras-style arrays written by
    ``scripts/fetch_data.py``).  A present-but-malformed file raises — a
    user who staged real data must never silently train on synthetic."""
    root = os.environ.get("COLEARN_DATA_DIR", "")
    if not root:
        return None
    path = os.path.join(root, f"{spec.name}.npz")
    if not os.path.exists(path):
        return None
    arrays = {}
    with np.load(path) as z:
        missing = [k for k in ("x_train", "y_train", "x_test", "y_test")
                   if k not in z]
        if missing:
            raise ValueError(f"{path} is missing arrays {missing} "
                             "(expected the keras-style x/y train/test "
                             "layout)")
        for split in ("train", "test"):
            x, y = z[f"x_{split}"], z[f"y_{split}"]
            want = spec.input_shape
            # Accept trailing-singleton-channel omission for grayscale
            # images ((N, 28, 28) on disk vs spec (28, 28, 1)).
            if (spec.kind == "image" and x.ndim == len(want)
                    and want[-1] == 1 and x.shape[1:] == want[:-1]):
                x = x[..., None]
            if x.shape[1:] != want:
                raise ValueError(
                    f"{path}: x_{split} per-example shape {x.shape[1:]} "
                    f"does not match the {spec.name} spec {want}")
            if len(x) != len(y):
                raise ValueError(
                    f"{path}: x_{split}/y_{split} row counts differ "
                    f"({len(x)} vs {len(y)})")
            if spec.kind == "image" and x.dtype == np.uint8:
                x = x.astype(np.float32) / 255.0   # keras raw-byte layout
            if spec.kind not in LABEL_PER_TOKEN_KINDS:
                y = y.reshape(-1)
            # Range-check BEFORE the int32 cast: a corrupt wide integer
            # must not wrap into the valid range and pass.
            if y.size and (int(y.min()) < 0
                           or int(y.max()) >= spec.num_classes):
                raise ValueError(
                    f"{path}: y_{split} labels outside "
                    f"[0, {spec.num_classes})")
            arrays[f"x_{split}"], arrays[f"y_{split}"] = x, y.astype(np.int32)
    return Dataset(spec, arrays["x_train"], arrays["y_train"],
                   arrays["x_test"], arrays["y_test"], "disk")


def _make_synthetic(spec: DatasetSpec, seed: int) -> Dataset:
    # proto_seed shared across splits: one class structure, disjoint draws.
    proto_seed = 7919 * seed + zlib.crc32(spec.name.encode()) % 10_000
    if spec.kind == "timeseries":
        x_tr, y_tr = synthetic.synthetic_traffic_classification(
            spec.n_train, spec.input_shape, spec.num_classes, seed=seed,
            proto_seed=proto_seed,
        )
        x_te, y_te = synthetic.synthetic_traffic_classification(
            spec.n_test, spec.input_shape, spec.num_classes, seed=seed + 1,
            proto_seed=proto_seed,
        )
        return Dataset(spec, x_tr, y_tr, x_te, y_te, "synthetic")
    if spec.kind == "bytes":
        x_tr, y_tr = synthetic.synthetic_byte_stream(
            spec.n_train, spec.input_shape[0], seed=seed)
        x_te, y_te = synthetic.synthetic_byte_stream(
            spec.n_test, spec.input_shape[0], seed=seed + 1)
        return Dataset(spec, x_tr, y_tr, x_te, y_te, "synthetic")
    if spec.kind in ("tokens", "tokens_ahead"):
        ahead = {"horizon": spec.horizon} if spec.kind == "tokens_ahead" else {}
        x_tr, y_tr = synthetic.synthetic_token_stream(
            spec.n_train, spec.input_shape[0], spec.vocab_size, seed=seed,
            **ahead)
        x_te, y_te = synthetic.synthetic_token_stream(
            spec.n_test, spec.input_shape[0], spec.vocab_size, seed=seed + 1,
            **ahead)
        return Dataset(spec, x_tr, y_tr, x_te, y_te, "synthetic")
    if spec.kind == "image":
        x_tr, y_tr = synthetic.synthetic_image_classification(
            spec.n_train, spec.input_shape, spec.num_classes, seed=seed,
            proto_seed=proto_seed,
        )
        x_te, y_te = synthetic.synthetic_image_classification(
            spec.n_test, spec.input_shape, spec.num_classes, seed=seed + 1,
            proto_seed=proto_seed,
        )
    else:
        vocab = spec.vocab_size or 30_522
        x_tr, y_tr = synthetic.synthetic_text_classification(
            spec.n_train, spec.input_shape[0], vocab, spec.num_classes, seed=seed
        )
        x_te, y_te = synthetic.synthetic_text_classification(
            spec.n_test, spec.input_shape[0], vocab, spec.num_classes, seed=seed + 1
        )
    return Dataset(spec, x_tr, y_tr, x_te, y_te, "synthetic")


def get_dataset(name: str, seed: int = 0, max_train: int = 0, max_test: int = 0) -> Dataset:
    if name not in SPECS:
        raise KeyError(f"unknown dataset {name!r}; available: {sorted(SPECS)}")
    spec = SPECS[name]
    ds = _load_disk(spec) or _make_synthetic(spec, seed)
    if max_train and len(ds.x_train) > max_train:
        ds = dataclasses.replace(ds, x_train=ds.x_train[:max_train], y_train=ds.y_train[:max_train])
    if max_test and len(ds.x_test) > max_test:
        ds = dataclasses.replace(ds, x_test=ds.x_test[:max_test], y_test=ds.y_test[:max_test])
    return ds
