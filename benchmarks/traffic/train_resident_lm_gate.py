"""Traffic kind ``train_resident_lm_gate``: train a language-model
configuration whose attention layers — full and sliding-window, with
head counts of their own — carry a sigmoid gate a head and rotate part
of each head, with a leading dense SwiGLU layer and a share of a
mixture of experts beside a shared expert behind every other layer,
several rows a step, on a store that lives in device memory, through
the program's normal loop.  ``train_resident_lm_swa``'s ``Cell``,
subclassed; what differs:

- ``build`` fails at once, before any data is made, on a program whose
  ``attention`` has no gate or rotated count, or whose ``moe`` has no
  routed scale; then it is ``train_resident_lm_swa``'s own set-up and
  ``follow_reference`` its own comparison, read against
  ``lib/reference_laguna.py`` (``lib/rebind.py``);
- ``MECHANISMS``: ``attn/window`` apart from ``attn/core`` (the full
  layers), the gate, the experts', the dense MLP's and the blocked
  loss's scopes.
"""

from __future__ import annotations

import inspect
import re

import numpy as np  # noqa: F401  (read by the rebound set-up)

from ..lib import reference_laguna as reference
from ..lib.rebind import rebound
from . import train_resident_lm_swa
from . import train_resident_seq  # noqa: F401  (read by the same)
from .train_resident import _placement  # noqa: F401  (read by the same)

#: device scopes of each mechanism (``veles_tpu/events.py``): what
#: ``lib/xplane.py`` ``scope_times`` sums for the readers
MECHANISMS = {
    "window_attention": re.compile(r"attn/(window)"),
    "attention": re.compile(r"attn/(core)"),
    "gate": re.compile(r"attn/(gate)"),
    "moe": re.compile(r"moe/(router|dispatch|experts|shared)"),
    "swiglu": re.compile(r"mlp/(swiglu)"),
    "loss": re.compile(r"loss/(block)"),
}

#: a rope specification that rotates half of a head of 128
HALF = {"rope_type": "default", "rope_theta": 1e4,
        "partial_rotary_factor": 0.5}


def require_layers(name: str) -> None:
    """Raise unless the program's ``attention`` takes a per-head gate
    and rotates part of a head, and its ``moe`` a routed scale."""
    from veles_tpu.ops import attention, moe
    ok = "gate" in inspect.signature(attention.Attention).parameters \
        and len(attention.rope_frequencies(HALF, 128)[0]) == 32 \
        and "routed_scale" in inspect.signature(moe.MoE).parameters
    if not ok:
        raise RuntimeError(
            f"the program's attention has no per-head gate or rotated "
            f"count, or its moe no routed scale: it cannot run "
            f"configuration {name!r}")


class Cell(train_resident_lm_swa.Cell):
    """One run of a ``train_resident_lm_gate`` cell."""

    def build(self) -> None:
        require_layers(self.cfg["name"])
        self._build()

    _build = rebound(train_resident_lm_swa.Cell.build, globals())
    follow_reference = rebound(
        train_resident_lm_swa.Cell.follow_reference, globals())
