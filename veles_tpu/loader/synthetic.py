"""Loaders over the deterministic synthetic datasets (and real files
when present).  Regenerate in ``load_data`` so snapshots stay small —
the generator args, not the arrays, are pickled."""

from __future__ import annotations

from typing import Any, Optional, Tuple

import numpy as np

from veles_tpu import datasets
from veles_tpu.loader.base import TEST, TRAIN, VALID
from veles_tpu.loader.fullbatch import FullBatchLoader


class SyntheticClassificationLoader(FullBatchLoader):
    """Procedural image-classification dataset, fully determined by the
    constructor args (veles_tpu/datasets.py)."""

    def __init__(self, workflow=None, n_train: int = 1000,
                 n_valid: int = 200, n_test: int = 0,
                 shape: Tuple[int, ...] = (28, 28, 1),
                 n_classes: int = 10, noise: float = 0.4,
                 max_shift: int = 2, seed: int = 20260729,
                 targets_from_data: bool = False,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.gen_args = dict(n_train=n_train, n_valid=n_valid,
                             n_test=n_test, shape=tuple(shape),
                             n_classes=n_classes, noise=noise,
                             max_shift=max_shift, seed=seed)
        self.targets_from_data = targets_from_data

    def load_data(self) -> None:
        a = self.gen_args
        train, valid, test = datasets.synthetic_classification(
            a["n_train"], a["n_valid"], a["shape"],
            n_classes=a["n_classes"], noise=a["noise"],
            max_shift=a["max_shift"], seed=a["seed"],
            n_test=a["n_test"])
        xs, ys = [], []
        for klass, split in ((TEST, test), (VALID, valid),
                             (TRAIN, train)):
            if split is None:
                self.class_lengths[klass] = 0
                continue
            self.class_lengths[klass] = len(split[0])
            xs.append(split[0])
            ys.append(split[1])
        self.original_data.mem = np.concatenate(xs, axis=0)
        self.original_labels.mem = \
            np.concatenate(ys, axis=0).astype(np.int32)
        if self.targets_from_data:
            self.original_targets.mem = self.original_data.mem

    def __getstate__(self) -> dict:
        # drop the bulky arrays; load_data regenerates them on resume
        return self.getstate_dropping("original_data",
                                      "original_labels",
                                      "original_targets")


class DeviceSyntheticLoader(SyntheticClassificationLoader):
    """The synthetic set born directly in HBM (datasets.
    synthetic_classification_device): zero host datagen and zero
    host->device upload.  The TPU-first answer to 'building the
    ImageNet-scale benchmark set costs minutes of single-core numpy +
    an upload' — the benchmark's dataset is procedural, so the
    accelerator generates it where it will be consumed.

    On a mesh device the set is generated REPLICATED under a
    ``NamedSharding`` — every device runs the same cheap gen program,
    so the future multi-chip benchmark pays zero host datagen and zero
    per-device upload exactly where those hurt most.

    Falls back to the host generator whenever the device path cannot
    serve: numpy backend, a set that exceeds the HBM residency budget
    (streaming needs host arrays by design), or a normalization
    request (the fit reads the host array).
    """

    def load_data(self) -> None:
        dev = self.device
        a = self.gen_args
        n_total = a["n_train"] + a["n_valid"] + a["n_test"]
        est_bytes = int(np.prod(a["shape"])) * 4 * n_total
        if dev is None or not getattr(dev, "is_jax", False) \
                or est_bytes > self._resident_budget() \
                or self.normalization_type != "none" \
                or self.normalizer is not None:
            super().load_data()
            return
        mesh = getattr(dev, "mesh", None)
        sharding = None
        if mesh is not None:
            from veles_tpu.parallel.mesh import replicated_sharding
            sharding = replicated_sharding(mesh)
        data, labels = datasets.synthetic_classification_device(
            n_total, a["shape"], n_classes=a["n_classes"],
            noise=a["noise"], max_shift=a["max_shift"], seed=a["seed"],
            jax_device=None if sharding is not None else dev.jax_device,
            sharding=sharding)
        # [test | valid | train] layout; one device stream serves all
        # three splits (split membership is positional, like the host
        # generator's concatenation)
        self.class_lengths[TEST] = a["n_test"]
        self.class_lengths[VALID] = a["n_valid"]
        self.class_lengths[TRAIN] = a["n_train"]
        self.original_data.devmem = data
        self.original_labels.devmem = labels
        if self.targets_from_data:
            self.original_targets.devmem = data


class _RealFileMixin:
    """Shared 'real files if pre-placed, else synthetic' load_data for
    loaders over a (train, test) split pair returned by a
    ``try_load_real_*`` function."""

    def _load_real_or_synthetic(self, real) -> None:
        if real is None:
            super().load_data()
            return
        # n_train / n_valid act as caps on the real files too — a
        # config asking for a 100-sample smoke run must not silently
        # train on all the rows just because real files exist on disk
        # (datasets.cap_real is the single policy point)
        (tx, ty), (vx, vy), _ = datasets.cap_real(
            real, self.gen_args["n_train"], self.gen_args["n_valid"])
        self.class_lengths[TEST] = 0
        self.class_lengths[VALID] = len(vx)
        self.class_lengths[TRAIN] = len(tx)
        self.original_data.mem = np.concatenate([vx, tx], axis=0)
        self.original_labels.mem = np.concatenate(
            [vy, ty], axis=0).astype(np.int32)
        if self.targets_from_data:
            self.original_targets.mem = self.original_data.mem


class MnistLoader(_RealFileMixin, SyntheticClassificationLoader):
    """Real MNIST IDX files if pre-placed under the data dir, else the
    synthetic 28x28x1 stand-in (this image has no datasets and no
    network — SURVEY.md §0)."""

    def __init__(self, workflow=None, n_train: int = 60000,
                 n_valid: int = 10000, **kwargs: Any) -> None:
        super().__init__(workflow, n_train=n_train, n_valid=n_valid,
                         shape=(28, 28, 1), seed=28281, **kwargs)

    def load_data(self) -> None:
        self._load_real_or_synthetic(datasets.try_load_real_mnist())


class Cifar10Loader(_RealFileMixin, SyntheticClassificationLoader):
    """Real CIFAR-10 batch files (binary or python-pickle layout) if
    pre-placed under the data dir, else the synthetic 32x32x3
    stand-in."""

    def __init__(self, workflow=None, n_train: int = 50000,
                 n_valid: int = 10000, **kwargs: Any) -> None:
        kwargs.setdefault("noise", 0.5)
        kwargs.setdefault("seed", 32323)
        super().__init__(workflow, n_train=n_train, n_valid=n_valid,
                         shape=(32, 32, 3), **kwargs)

    def load_data(self) -> None:
        self._load_real_or_synthetic(datasets.try_load_real_cifar10())


class PackedBytesLoader(FullBatchLoader):
    """Rows of packed byte documents (``datasets.
    synthetic_packed_bytes``): an INTEGER store ``[rows, seq_len]`` and
    nothing else — a next-token loss reads its targets off the rows
    themselves, so there is no label and no target store.  Regenerated
    in ``load_data`` from the constructor args."""

    def __init__(self, workflow=None, n_train: int = 4,
                 n_valid: int = 0, seq_len: int = 128,
                 median_len: int = 2048, seed: int = 320320,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.gen_args = dict(n_train=n_train, n_valid=n_valid,
                             seq_len=seq_len, median_len=median_len,
                             seed=seed)

    def make_rows(self, n_rows: int) -> np.ndarray:
        a = self.gen_args
        return datasets.synthetic_packed_bytes(
            n_rows, a["seq_len"], a["seed"], median_len=a["median_len"])

    def load_data(self) -> None:
        a = self.gen_args
        self.class_lengths[TEST] = 0
        self.class_lengths[VALID] = a["n_valid"]
        self.class_lengths[TRAIN] = a["n_train"]
        self.original_data.mem = self.make_rows(
            a["n_valid"] + a["n_train"])

    def __getstate__(self) -> dict:
        return self.getstate_dropping("original_data")


class PackedTokensLoader(PackedBytesLoader):
    """The same store over a vocabulary that is a parameter: ids
    ``0 .. vocab_size - 2`` from the chain, ``vocab_size - 1`` the
    separator (``datasets.synthetic_packed_tokens``)."""

    def __init__(self, workflow=None, vocab_size: int = 512,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.vocab_size = vocab_size

    def make_rows(self, n_rows: int) -> np.ndarray:
        a = self.gen_args
        return datasets.synthetic_packed_tokens(
            n_rows, a["seq_len"], a["seed"], self.vocab_size - 1,
            self.vocab_size - 1, median_len=a["median_len"])
