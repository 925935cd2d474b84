"""One function's code read against another module's names."""

from __future__ import annotations

import types
from typing import Any, Dict


def rebound(fn, namespace: Dict[str, Any]):
    """``fn`` with its module-level names read from ``namespace`` (a
    module's ``globals()``): the same code over the names that module
    gives them — a reference's walk over another configuration's layer
    functions, a traffic kind's set-up over another reference."""
    out = types.FunctionType(fn.__code__, namespace, fn.__name__,
                             fn.__defaults__, fn.__closure__)
    out.__kwdefaults__ = fn.__kwdefaults__
    out.__doc__ = fn.__doc__
    return out


def rebound_class(cls, namespace: Dict[str, Any], name: str):
    """A class whose methods are ``cls``'s, each :func:`rebound`."""
    return type(name, (), {
        k: rebound(v, namespace) if isinstance(v, types.FunctionType)
        else v for k, v in vars(cls).items()
        if k not in ("__dict__", "__weakref__")})
