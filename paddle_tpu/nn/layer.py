"""The Layer (module) system.

TPU-native equivalent of the reference's ``paddle.nn.Layer``
(upstream layout: python/paddle/nn/layer/layers.py) — the stateful module
class holding parameters, buffers and sublayers, with ``state_dict`` /
``set_state_dict``, train/eval modes and named traversal.

Design for jax:
  * A parameter is a **raw** ``jax.Array`` stored as an instance attribute; a
    parallel ``Parameter`` handle records metadata (trainable, sharding spec,
    the local name).  There is no tensor subclass — jax removed
    ``__jax_array__`` — so the attribute itself is always a plain array and
    every jnp op works on it directly (eager mode ≙ the reference's dygraph).
  * The functional bridge :func:`functional_call` temporarily rebinds a pytree
    of parameter values onto the live module, runs ``forward`` and restores —
    this is what ``jax.jit`` / ``jax.grad`` trace through (static mode ≙ the
    reference's ``@to_static``), giving tape-free autograd via ``jax.grad``
    where the reference builds GradNodes in C++
    (paddle/fluid/eager/, upstream layout).
  * Sharding is declared at parameter creation (a ``PartitionSpec``) and
    collected by :meth:`Layer.param_shardings` for pjit — the GSPMD analogue of
    the reference's per-op dist attrs.
"""

from __future__ import annotations

import collections
import contextlib
import threading
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import dtype as _dtype_mod
from ..framework import random as _random

__all__ = ["Parameter", "Layer", "Sequential", "LayerList",
           "functional_call", "bind_params", "abstract_parameters"]

_ABSTRACT = threading.local()


@contextlib.contextmanager
def abstract_parameters():
    """Build layers WITHOUT initialising their parameters: inside,
    :meth:`Layer.create_parameter` registers a ``jax.ShapeDtypeStruct`` of
    the parameter's shape and dtype where it would run the initializer.
    For a model that is built to be LOADED (``set_state_dict`` checks each
    array against the placeholder's shape and replaces it): a model near
    the device's memory cannot hold its random weights beside the ones it
    is given, and its initializers, dispatched without waiting, hold their
    float32 temporaries side by side (a 9.1 GB bf16 model's constructor
    peaked at 16.5 GB of a 17.2 GB chip, PR 26).  Buffers are computed as
    ever.  A placeholder that is never replaced fails at first use."""
    prev = getattr(_ABSTRACT, "on", False)
    _ABSTRACT.on = True
    try:
        yield
    finally:
        _ABSTRACT.on = prev


class Parameter:
    """Metadata handle for one parameter of a :class:`Layer`.

    The authoritative value lives as a plain array attribute on the owning
    layer; this handle reads/writes it via the ``value`` property so that
    eager code (``self.weight``), optimizers (``param.value = new``) and the
    functional bridge all observe one consistent value.
    """

    __slots__ = ("_owner", "local_name", "trainable", "sharding", "is_buffer")

    def __init__(self, owner: "Layer", local_name: str, trainable: bool = True,
                 sharding=None, is_buffer: bool = False):
        self._owner = owner
        self.local_name = local_name
        self.trainable = trainable
        self.sharding = sharding
        self.is_buffer = is_buffer

    @property
    def value(self):
        return self._owner.__dict__[self.local_name]

    @value.setter
    def value(self, v):
        object.__setattr__(self._owner, self.local_name, v)

    @property
    def shape(self):
        return self.value.shape

    @property
    def dtype(self):
        return self.value.dtype

    @property
    def stop_gradient(self):  # reference-parity spelling
        return not self.trainable

    @stop_gradient.setter
    def stop_gradient(self, v):
        self.trainable = not v

    def __repr__(self):
        kind = "Buffer" if self.is_buffer else "Parameter"
        return (f"{kind}(name={self.local_name!r}, shape={tuple(self.shape)}, "
                f"dtype={self.dtype}, trainable={self.trainable}, "
                f"sharding={self.sharding})")


class Layer:
    """Base module class (parity: ``paddle.nn.Layer``)."""

    def __init__(self, name_scope: Optional[str] = None):
        object.__setattr__(self, "_parameters", collections.OrderedDict())
        object.__setattr__(self, "_buffers", collections.OrderedDict())
        object.__setattr__(self, "_sublayers", collections.OrderedDict())
        object.__setattr__(self, "training", True)
        object.__setattr__(self, "_name_scope", name_scope or type(self).__name__)

    # -- attribute plumbing -------------------------------------------------

    def __setattr__(self, name: str, value: Any) -> None:
        params = self.__dict__.get("_parameters")
        if params is None:
            raise RuntimeError(
                f"call super().__init__() in {type(self).__name__}.__init__ "
                "before assigning attributes")
        subs = self.__dict__["_sublayers"]
        bufs = self.__dict__["_buffers"]
        if isinstance(value, Layer):
            params.pop(name, None)
            bufs.pop(name, None)
            subs[name] = value
            object.__setattr__(self, name, value)
        elif name in params or name in bufs:
            # rebinding an existing parameter/buffer with a new array
            object.__setattr__(self, name, value)
        else:
            subs.pop(name, None)
            object.__setattr__(self, name, value)

    def __delattr__(self, name: str) -> None:
        self._parameters.pop(name, None)
        self._buffers.pop(name, None)
        self._sublayers.pop(name, None)
        object.__delattr__(self, name)

    # -- parameter / buffer creation ---------------------------------------

    def create_parameter(self, shape, dtype=None, initializer=None,
                         trainable: bool = True, sharding=None,
                         attr_name: Optional[str] = None):
        """Create + register a parameter; returns the raw array.

        Prefer ``self.w = self.create_parameter(..., attr_name="w")``; when
        ``attr_name`` is omitted a fresh auto name ``param_<i>`` is used and
        the attribute is installed automatically.
        """
        from . import initializer as I  # local import to avoid cycle

        dt = _dtype_mod.to_jax_dtype(dtype)
        init = initializer if initializer is not None else I.XavierNormal()
        if getattr(_ABSTRACT, "on", False):
            value = jax.ShapeDtypeStruct(tuple(shape), dt)
        else:
            value = init(shape, dt, _random.site_key())
        name = attr_name or f"param_{len(self._parameters)}"
        object.__setattr__(self, name, value)
        self._parameters[name] = Parameter(self, name, trainable=trainable,
                                           sharding=sharding)
        return value

    def register_buffer(self, name: str, value, persistable: bool = True):
        del persistable  # all buffers persist in state_dict (reference default)
        object.__setattr__(self, name, value)
        self._buffers[name] = Parameter(self, name, trainable=False,
                                        is_buffer=True)
        return value

    def add_sublayer(self, name: str, layer: "Layer") -> "Layer":
        setattr(self, name, layer)
        return layer

    def add_parameter(self, name: str, value, trainable: bool = True,
                      sharding=None):
        object.__setattr__(self, name, value)
        self._parameters[name] = Parameter(self, name, trainable=trainable,
                                           sharding=sharding)
        return value

    # -- traversal ----------------------------------------------------------

    def named_sublayers(self, prefix: str = "", include_self: bool = False
                        ) -> Iterator[Tuple[str, "Layer"]]:
        if include_self:
            yield prefix.rstrip("."), self
        for n, sub in self._sublayers.items():
            p = f"{prefix}{n}"
            yield p, sub
            yield from sub.named_sublayers(prefix=p + ".")

    def sublayers(self, include_self: bool = False) -> List["Layer"]:
        out = [self] if include_self else []
        out.extend(l for _, l in self.named_sublayers())
        return out

    def children(self) -> Iterator["Layer"]:
        return iter(self._sublayers.values())

    def named_parameters(self, prefix: str = "", include_buffers: bool = False
                         ) -> Iterator[Tuple[str, Parameter]]:
        for n, p in self._parameters.items():
            yield f"{prefix}{n}", p
        if include_buffers:
            for n, b in self._buffers.items():
                yield f"{prefix}{n}", b
        for n, sub in self._sublayers.items():
            yield from sub.named_parameters(prefix=f"{prefix}{n}.",
                                            include_buffers=include_buffers)

    def parameters(self, include_sublayers: bool = True) -> List[Parameter]:
        if not include_sublayers:
            return list(self._parameters.values())
        return [p for _, p in self.named_parameters()]

    def named_buffers(self, prefix: str = "") -> Iterator[Tuple[str, Parameter]]:
        for n, b in self._buffers.items():
            yield f"{prefix}{n}", b
        for n, sub in self._sublayers.items():
            yield from sub.named_buffers(prefix=f"{prefix}{n}.")

    # -- state dict ----------------------------------------------------------

    def state_dict(self, include_buffers: bool = True,
                   trainable_only: bool = False) -> Dict[str, jax.Array]:
        """Flat dict of dotted-name -> raw array (parity: ``Layer.state_dict``)."""
        out = collections.OrderedDict()
        for name, p in self.named_parameters(include_buffers=include_buffers):
            if trainable_only and not p.trainable:
                continue
            out[name] = p.value
        return out

    def trainable_state(self) -> Dict[str, jax.Array]:
        """The pytree of trainable parameter values (what jax.grad sees)."""
        return self.state_dict(include_buffers=False, trainable_only=True)

    def set_state_dict(self, state: Dict[str, Any], strict: bool = True):
        handles = dict(self.named_parameters(include_buffers=True))
        missing = [k for k in handles if k not in state]
        unexpected = [k for k in state if k not in handles]
        if strict and unexpected:
            raise KeyError(f"unexpected keys in state_dict: {unexpected}")
        for k, v in state.items():
            if k in handles:
                if not hasattr(v, "shape"):
                    v = jnp.asarray(v)
                if tuple(v.shape) != tuple(handles[k].shape):
                    raise ValueError(
                        f"shape mismatch for {k}: got {tuple(v.shape)}, "
                        f"expected {tuple(handles[k].shape)}")
                handles[k].value = v
        return missing

    load_dict = set_state_dict  # reference-parity alias

    # -- sharding -----------------------------------------------------------

    def param_shardings(self, include_buffers: bool = True
                        ) -> Dict[str, Any]:
        """Dotted-name -> PartitionSpec (or None) for every parameter."""
        out = {}
        for name, p in self.named_parameters(include_buffers=include_buffers):
            out[name] = p.sharding
        return out

    # -- modes / application -------------------------------------------------

    def train(self):
        object.__setattr__(self, "training", True)
        for l in self.sublayers():
            object.__setattr__(l, "training", True)
        return self

    def eval(self):
        object.__setattr__(self, "training", False)
        for l in self.sublayers():
            object.__setattr__(l, "training", False)
        return self

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    def astype(self, dtype) -> "Layer":
        """Cast all floating-point parameters to ``dtype`` in place."""
        dt = _dtype_mod.to_jax_dtype(dtype)
        for _, p in self.named_parameters(include_buffers=True):
            if jnp.issubdtype(p.value.dtype, jnp.floating):
                p.value = p.value.astype(dt)
        return self

    # ``Layer.to(dtype=...)`` parity
    def to(self, dtype=None):
        return self.astype(dtype) if dtype is not None else self

    # -- forward -------------------------------------------------------------

    def forward(self, *args, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward()")

    def __call__(self, *args, **kwargs):
        return self.forward(*args, **kwargs)

    def __repr__(self):
        lines = [type(self).__name__ + "("]
        for n, s in self._sublayers.items():
            sub = repr(s).split("\n")
            lines.append(f"  ({n}): " + sub[0])
            lines.extend("  " + l for l in sub[1:])
        lines.append(")")
        return "\n".join(lines) if len(lines) > 2 else type(self).__name__ + "()"


class Sequential(Layer):
    """Chain of layers (parity: ``paddle.nn.Sequential``)."""

    def __init__(self, *layers):
        super().__init__()
        # a single *list* argument is unwrapped; tuples are always treated as
        # (name, layer) pairs so Sequential(("fc", lin)) names correctly
        if len(layers) == 1 and isinstance(layers[0], list):
            layers = tuple(layers[0])
        for i, l in enumerate(layers):
            if isinstance(l, tuple):  # (name, layer) pairs
                self.add_sublayer(l[0], l[1])
            else:
                self.add_sublayer(str(i), l)

    def __len__(self):
        return len(self._sublayers)

    def __getitem__(self, i):
        return list(self._sublayers.values())[i]

    def __iter__(self):
        return iter(self._sublayers.values())

    def forward(self, x):
        for l in self._sublayers.values():
            x = l(x)
        return x


class LayerList(Layer):
    """Indexed list of sublayers (parity: ``paddle.nn.LayerList``)."""

    def __init__(self, layers=()):
        super().__init__()
        for i, l in enumerate(layers):
            self.add_sublayer(str(i), l)

    def append(self, layer: Layer):
        self.add_sublayer(str(len(self._sublayers)), layer)
        return self

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self

    def __len__(self):
        return len(self._sublayers)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return list(self._sublayers.values())[i]
        return self._sublayers[str(i if i >= 0 else len(self) + i)]

    def __iter__(self):
        return iter(self._sublayers.values())

    def forward(self, *a, **k):
        raise NotImplementedError("LayerList is a container; index into it")


@contextlib.contextmanager
def bind_params(model: Layer, state: Dict[str, Any], rng=None,
                eval_mode: bool = False):
    """Temporarily rebind a pytree of parameter values onto the live module.

    The single functional bridge every jit/grad entry point goes through
    (functional_call, the train/eval step builders, the driver hooks):
    values are restored on exit even on exception, so tracing never leaks
    tracers into the module.  ``rng`` pins the RNG key for stochastic layers;
    ``eval_mode`` traces with ``training=False`` (restored after).
    """
    handles = dict(model.named_parameters(include_buffers=True))
    old = {}
    was_training = model.training
    try:
        for k, v in state.items():
            h = handles[k]
            old[k] = h.value
            h.value = v
        if eval_mode:
            model.eval()
        if rng is not None:
            with _random.rng_guard(rng):
                yield model
        else:
            yield model
    finally:
        if eval_mode and was_training:
            model.train()
        for k, v in old.items():
            handles[k].value = v


def functional_call(model: Layer, state: Dict[str, Any], *args,
                    rng=None, **kwargs):
    """Run ``model(*args, **kwargs)`` with parameter values taken from ``state``.

    This is the functional bridge that makes the stateful Layer system
    jit/grad-compatible: ``state`` is a flat dict (as from
    :meth:`Layer.trainable_state`); original values are restored afterwards,
    so tracing never leaks tracers into the live module.  ``rng`` optionally
    pins the RNG key for stochastic layers (dropout) via
    :func:`paddle_tpu.framework.random.rng_guard`.
    """
    with bind_params(model, state, rng=rng):
        return model(*args, **kwargs)
