"""Evaluators: loss, error counts, and the err_output seed for backward.

Reference parity: veles/znicz/evaluator.py — ``EvaluatorSoftmax``
(cross-entropy, n_err, confusion matrix; err_output = probs - onehot,
i.e. the fused softmax+CE gradient) and ``EvaluatorMSE``.

The pure ``metrics_fn`` is shared by the eager path and the fused step;
metrics come out as arrays so the fused TPU path can accumulate them
on-device without a host sync per minibatch (Decision reads them once
per class — SURVEY.md §7 "hard parts").

Padded minibatch rows (static-shape remainder handling) are excluded
everywhere via ``mask``; losses/gradients normalize by the REAL row
count ``mask.sum()``.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from veles_tpu.accelerated_units import AcceleratedUnit
from veles_tpu.memory import Vector


class EvaluatorBase(AcceleratedUnit):
    def __init__(self, workflow=None, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.input = Vector(name=f"{self.name}.input")      # net output
        self.err_output = Vector(name=f"{self.name}.err_output")
        self.n_err = Vector(name=f"{self.name}.n_err")      # scalar
        self.loss = Vector(name=f"{self.name}.loss")        # scalar (sum)
        self.count = Vector(name=f"{self.name}.count")      # scalar (rows)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        # .shape raises AttributeError while the producing forward is
        # uninitialized -> Workflow.initialize retries us later.
        in_shape = self.input.shape
        if not self.err_output:
            self.err_output.mem = np.zeros(in_shape, np.float32)
        # err_output is scratch (written by every firing before a read):
        # no upload of its zeros — at a sequence model's logits they are
        # hundreds of MB of HBM that nothing would ever read
        self.err_output.initialize(device, upload=False)
        for v in (self.n_err, self.loss, self.count):
            v.initialize(device)

    def metrics_fn(self, output: Any, target: Any, mask: Any) \
            -> Dict[str, Any]:
        """Pure: {err_output, n_err, loss_sum, count}."""
        raise NotImplementedError


class EvaluatorSoftmax(EvaluatorBase):
    """Cross-entropy over class probabilities.

    ``input`` holds the softmax unit's probabilities; ``labels`` are
    int32 class ids.  err_output = (probs - onehot) * mask / n_valid —
    d(mean CE)/d(logits), completing the softmax+CE fusion with the
    producing unit's ``activation_mode == 'softmax'`` contract.
    """

    def __init__(self, workflow=None, n_classes: int = None,  # type: ignore
                 compute_confusion: bool = True, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.n_classes = n_classes
        self.compute_confusion = compute_confusion
        self.labels = Vector(name=f"{self.name}.labels")
        self.mask = Vector(name=f"{self.name}.mask")
        self.confusion = Vector(name=f"{self.name}.confusion")
        self.max_idx = Vector(name=f"{self.name}.max_idx")

    def initialize(self, device=None, **kwargs) -> None:
        if self.n_classes is None:
            self.n_classes = int(self.input.shape[-1])
        super().initialize(device=device, **kwargs)
        if self.compute_confusion and not self.confusion:
            self.confusion.mem = np.zeros(
                (self.n_classes, self.n_classes), np.int64)
            self.confusion.initialize(None)  # host-side accumulator

    def metrics_fn(self, output, target, mask):
        eps = 1e-12
        if isinstance(output, np.ndarray):
            n = mask.sum()
            onehot = np.eye(output.shape[-1],
                            dtype=output.dtype)[target]
            err = (output - onehot) * mask[:, None] / np.maximum(n, 1.0)
            pred = output.argmax(-1)
            n_err = ((pred != target) * mask).sum()
            p = output[np.arange(len(target)), target]
            loss_sum = -(np.log(np.maximum(p, eps)) * mask).sum()
            return {"err_output": err.astype(np.float32),
                    "n_err": np.float32(n_err),
                    "loss_sum": np.float32(loss_sum),
                    "count": np.float32(n),
                    "max_idx": pred.astype(np.int32)}
        import jax.numpy as jnp
        n = mask.sum()
        onehot = jnp.eye(output.shape[-1], dtype=output.dtype)[target]
        err = (output - onehot) * mask[:, None] / jnp.maximum(n, 1.0)
        pred = output.argmax(-1)
        n_err = ((pred != target) * mask).sum()
        p = jnp.take_along_axis(output, target[:, None], axis=-1)[:, 0]
        loss_sum = -(jnp.log(jnp.maximum(p, eps)) * mask).sum()
        return {"err_output": err.astype(jnp.float32),
                "n_err": n_err.astype(jnp.float32),
                "loss_sum": loss_sum.astype(jnp.float32),
                "count": n.astype(jnp.float32),
                "max_idx": pred.astype(jnp.int32)}

    def run(self) -> None:
        numpy_mode = self.device is None or not self.device.is_jax
        if numpy_mode:
            out = self.input.map_read()
            target = self.labels.map_read()
            mask = self.mask.map_read()
            m = self.metrics_fn(out, target, mask)
            self.err_output.reset(m["err_output"])
            self.n_err.reset(np.float32([m["n_err"]]))
            self.loss.reset(np.float32([m["loss_sum"]]))
            self.count.reset(np.float32([m["count"]]))
            self.max_idx.reset(m["max_idx"])
        else:
            if self._compiled is None:
                self._compiled = self.device.compile(self.metrics_fn)
            m = self._compiled(self.input.unmap(), self.labels.unmap(),
                               self.mask.unmap())
            self.err_output.devmem = m["err_output"]
            self.n_err.devmem = m["n_err"]
            self.loss.devmem = m["loss_sum"]
            self.count.devmem = m["count"]
            self.max_idx.devmem = m["max_idx"]
        if self.compute_confusion:
            # host-side confusion accumulation (read once per minibatch
            # in eager modes; the fused path accumulates on device)
            pred = np.asarray(self.max_idx.map_read()
                              if numpy_mode else self.max_idx.devmem)
            target = np.asarray(self.labels.map_read())
            mask = np.asarray(self.mask.map_read())
            valid = mask > 0
            np.add.at(self.confusion.mem, (target[valid], pred[valid]), 1)


class EvaluatorNextByte(EvaluatorBase):
    """Next-token cross-entropy at every position for every prediction
    head (``loss_function="next_byte"``: ids of bytes, or of any
    vocabulary the head spans).

    ``input`` holds the head's f32 logits ``[rows, T, heads, vocab]``;
    the targets are the row of ids itself (``targets_from_data``: the
    fused step hands the data store in as the target store, no second
    store exists): head j at position n predicts id n + 1 + j, valid
    where that id exists.  ``loss_sum`` is the summed cross-entropy of
    the valid (row, n, j), ``count`` their number — so Decision's loss
    is the mean per prediction — ``n_err`` the wrong arg-max ones.
    err_output = (softmax - onehot) * valid / count: d(mean CE)/d
    logits.

    ``block_metrics`` is the same loss of a block of positions of the
    row, for a head too wide for its whole logits to exist
    (``engine/core.py`` ``build_blocked_head``)."""

    targets_from_data = True

    def __init__(self, workflow=None, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.target = Vector(name=f"{self.name}.target")
        self.mask = Vector(name=f"{self.name}.mask")

    def valid_count(self, output_shape, mask):
        """How many (row, position, head) of logits of
        ``output_shape`` have a target: the ``count`` of the whole."""
        import jax.numpy as jnp
        t, heads = int(output_shape[1]), int(output_shape[2])
        per_row = sum(max(t - 1 - j, 0) for j in range(heads))
        return jnp.sum(jnp.asarray(mask) > 0).astype(jnp.float32) \
            * per_row

    def block_metrics(self, output, target, mask, start=None, n=None):
        """The metrics of logits ``output`` for positions ``start ..
        start + output.shape[1]`` of the rows ``target`` (None: the
        whole row); the error under the mean over ``n`` valid
        predictions (None: this block's own)."""
        import jax
        import jax.numpy as jnp
        target = jnp.asarray(target)
        t, heads = target.shape[1], output.shape[2]
        pos = jnp.arange(output.shape[1])[:, None] + 1 \
            + jnp.arange(heads)[None, :]
        if start is not None:
            pos = pos + start
        tgt = target[:, jnp.minimum(pos, t - 1)]
        valid = (pos < t)[None] & (jnp.asarray(mask) > 0)[:, None, None]
        if n is None:
            n = valid.sum().astype(jnp.float32)
        logp = jax.nn.log_softmax(jnp.asarray(output), axis=-1)
        picked = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        loss_sum = -jnp.sum(jnp.where(valid, picked, 0.0))
        onehot = jax.nn.one_hot(tgt, output.shape[-1], dtype=logp.dtype)
        err = (jnp.exp(logp) - onehot) \
            * (valid[..., None] / jnp.maximum(n, 1.0))
        n_err = ((logp.argmax(-1) != tgt) & valid).sum()
        return {"err_output": err.astype(jnp.float32),
                "n_err": n_err.astype(jnp.float32),
                "loss_sum": loss_sum.astype(jnp.float32),
                "count": n}

    def metrics_fn(self, output, target, mask):
        return self.block_metrics(output, target, mask)

    def run(self) -> None:
        if self._compiled is None:
            self._compiled = self.device.compile(self.metrics_fn) \
                if self.device is not None and self.device.is_jax \
                else self.metrics_fn
        m = self._compiled(self.input.unmap(), self.target.unmap(),
                           self.mask.unmap())
        self.err_output.devmem = m["err_output"]
        self.n_err.devmem = m["n_err"]
        self.loss.devmem = m["loss_sum"]
        self.count.devmem = m["count"]


class EvaluatorMSE(EvaluatorBase):
    """Mean-squared-error evaluator (autoencoders, regression).

    Normalized per ELEMENT, not per row: with D = features/sample,
    loss_sum = sum over valid rows of 0.5 * ||y - t||^2 / D and
    err_output = (y - t) * mask / (n_valid * D).  Per-row-only
    normalization makes the gradient scale with the sample size (784x
    for MNIST images) and blows up any reasonable learning rate.
    """

    def __init__(self, workflow=None, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.target = Vector(name=f"{self.name}.target")
        self.mask = Vector(name=f"{self.name}.mask")

    def metrics_fn(self, output, target, mask):
        diff = output - target
        bshape = (-1,) + (1,) * (diff.ndim - 1)
        m = mask.reshape(bshape)
        n = mask.sum()
        d = float(np.prod(output.shape[1:]))
        if isinstance(output, np.ndarray):
            err = diff * m / np.maximum(n * d, 1.0)
            per_row = 0.5 * (diff * diff).reshape(len(diff), -1).sum(-1) / d
            loss_sum = (per_row * mask).sum()
            return {"err_output": err.astype(np.float32),
                    "n_err": np.float32(0.0),
                    "loss_sum": np.float32(loss_sum),
                    "count": np.float32(n)}
        import jax.numpy as jnp
        err = diff * m / jnp.maximum(n * d, 1.0)
        per_row = 0.5 * (diff * diff).reshape(len(diff), -1).sum(-1) / d
        loss_sum = (per_row * mask).sum()
        return {"err_output": err.astype(jnp.float32),
                "n_err": jnp.float32(0.0),
                "loss_sum": loss_sum.astype(jnp.float32),
                "count": n.astype(jnp.float32)}

    def run(self) -> None:
        numpy_mode = self.device is None or not self.device.is_jax
        if numpy_mode:
            m = self.metrics_fn(self.input.map_read(),
                                self.target.map_read(),
                                self.mask.map_read())
            self.err_output.reset(m["err_output"])
            self.n_err.reset(np.float32([m["n_err"]]))
            self.loss.reset(np.float32([m["loss_sum"]]))
            self.count.reset(np.float32([m["count"]]))
        else:
            if self._compiled is None:
                self._compiled = self.device.compile(self.metrics_fn)
            m = self._compiled(self.input.unmap(), self.target.unmap(),
                               self.mask.unmap())
            self.err_output.devmem = m["err_output"]
            self.n_err.devmem = m["n_err"]
            self.loss.devmem = m["loss_sum"]
            self.count.devmem = m["count"]
