"""Functional module system for TPU-native networks.

Design: a :class:`Module` is a *static* Python object describing the network
topology; parameters and mutable state (e.g. BatchNorm running statistics) live
in plain pytrees (nested dicts of ``jax.Array``) threaded explicitly through
``init`` / ``apply``.  Nothing on the module itself ever holds an array, so the
whole forward + backward + optimizer update compiles into a single XLA graph,
can be freely ``jax.jit`` / ``jax.grad`` / ``shard_map``-transformed, and
replicates across a device mesh without any of the object-graph machinery a
stateful module system (torch ``nn.Module``) needs.

This plays the role torch's ``nn.Module`` plays for the reference scripts
(``/root/reference/mpspawn_dist.py:11-43`` defines ``ConvNet(nn.Module)``;
``/root/reference/example_mp.py:50`` instantiates ``torchvision`` ResNet-18),
but TPU-first: ``apply`` is a pure function of ``(params, state, inputs, rng)``.

Usage::

    model = ConvNet()
    params = model.init(jax.random.key(0))
    logits = model.apply(params, images)                        # stateless nets
    logits, new_state = model.apply(params, images, state=state,
                                    training=True, rng=key)     # BN / dropout
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Iterator, Optional, Tuple

import jax

__all__ = ["Module", "Remat", "Sequential", "current_context",
           "run_capturing_state"]


class _Context:
    """Per-``apply`` tracing context (parameters, state, rng, mode)."""

    __slots__ = ("params", "state", "training", "rng", "new_state", "rng_counter")

    def __init__(self, params, state, training, rng):
        self.params = params or {}
        self.state = state
        self.training = training
        self.rng = rng
        self.new_state = {} if state is not None else None
        self.rng_counter = 0

    def get_params(self, path: str) -> Dict[str, Any]:
        try:
            return self.params[path]
        except KeyError:
            raise KeyError(
                f"No parameters found for module at path {path!r}. "
                f"Available: {list(self.params)}. Did you pass the pytree "
                f"returned by Module.init()?"
            ) from None

    def get_state(self, path: str) -> Dict[str, Any]:
        if self.state is None:
            raise ValueError(
                f"Module at path {path!r} carries mutable state (e.g. BatchNorm "
                f"running stats) but apply() was called without state=. Pass "
                f"the pytree returned by Module.init_state()."
            )
        return self.state[path]

    def put_state(self, path: str, value: Dict[str, Any]) -> None:
        if self.new_state is not None:
            self.new_state[path] = value

    def next_rng(self):
        if self.rng is None:
            raise ValueError(
                "A module requested randomness (dropout/augmentation) in "
                "training mode but apply() was called without rng=."
            )
        key = jax.random.fold_in(self.rng, self.rng_counter)
        self.rng_counter += 1
        return key


_TLS = threading.local()


def _stack():
    if not hasattr(_TLS, "stack"):
        _TLS.stack = []
    return _TLS.stack


def current_context() -> Optional[_Context]:
    stack = _stack()
    return stack[-1] if stack else None


def _ctx() -> _Context:
    ctx = current_context()
    if ctx is None:
        raise RuntimeError(
            "Modules can only be called inside Module.apply() (or init()). "
            "Call model.apply(params, x) rather than model(x) at top level."
        )
    return ctx


class Module:
    """Base class for all network modules.

    Subclasses create submodules in ``__init__`` (attribute assignment
    registers them) and define ``forward(*args)``.  Leaf modules holding
    parameters override :meth:`create_params` (and :meth:`create_state` for
    mutable buffers).
    """

    def __init__(self):
        object.__setattr__(self, "_modules", {})
        object.__setattr__(self, "_path", None)

    # -- submodule registration ------------------------------------------------
    def __setattr__(self, name: str, value: Any) -> None:
        mods = self.__dict__.get("_modules")
        if mods is None:
            raise RuntimeError(
                f"Call super().__init__() in {type(self).__name__}.__init__ "
                "before assigning attributes."
            )
        if isinstance(value, Module):
            mods[name] = value
        elif name in mods:
            del mods[name]
        object.__setattr__(self, name, value)

    # -- tree walking ----------------------------------------------------------
    def named_modules(self, prefix: str = "", _seen=None) -> Iterator[Tuple[str, "Module"]]:
        """Depth-first (pre-order) walk over ``(dotted_path, module)``.

        A module instance registered under several names (weight tying) is
        yielded once, at its first path — so tied modules share one parameter
        set rather than initializing divergent dead copies.
        """
        if _seen is None:
            _seen = set()
        if id(self) in _seen:
            return
        _seen.add(id(self))
        yield prefix, self
        for name, mod in self._modules.items():
            sub = f"{prefix}.{name}" if prefix else name
            yield from mod.named_modules(sub, _seen)

    def _assign_paths(self) -> None:
        for path, mod in self.named_modules():
            object.__setattr__(mod, "_path", path)

    # -- leaf hooks ------------------------------------------------------------
    def create_params(self, key) -> Optional[Dict[str, Any]]:
        """Leaf modules return their parameter dict; composites return None."""
        return None

    def create_state(self) -> Optional[Dict[str, Any]]:
        """Leaf modules with mutable buffers return their initial state."""
        return None

    # -- public API ------------------------------------------------------------
    def init(self, key) -> Dict[str, Dict[str, Any]]:
        """Create the parameter pytree: ``{dotted_path: {name: array}}``.

        Keys are derived per-module by folding the traversal index into
        ``key``, so initialization is deterministic given the module tree —
        the TPU analogue of the reference's ``torch.manual_seed(0)`` giving
        identical parameters on every rank (/root/reference/mpspawn_dist.py:56).
        """
        self._assign_paths()
        params: Dict[str, Dict[str, Any]] = {}
        for i, (path, mod) in enumerate(self.named_modules()):
            sub = jax.random.fold_in(key, i)
            p = mod.create_params(sub)
            if p:
                params[path] = p
        return params

    def init_state(self) -> Dict[str, Dict[str, Any]]:
        """Create the mutable-state pytree (empty dict if the net has none)."""
        self._assign_paths()
        state: Dict[str, Dict[str, Any]] = {}
        for path, mod in self.named_modules():
            s = mod.create_state()
            if s:
                state[path] = s
        return state

    def has_state(self) -> bool:
        return any(m.create_state() for _, m in self.named_modules())

    def apply(self, params, *args, state=None, training: bool = False,
              rng=None, **kwargs):
        """Run the network as a pure function.

        Returns ``forward(*args)`` — or ``(output, new_state)`` when ``state``
        is passed (mutable-state nets must thread it).
        """
        self._assign_paths()
        ctx = _Context(params, state, training, rng)
        _stack().append(ctx)
        try:
            out = self.forward(*args, **kwargs)
        finally:
            _stack().pop()
        if state is not None:
            # Carry through entries the trace did not update (e.g. eval mode).
            new_state = dict(state)
            new_state.update(ctx.new_state)
            return out, new_state
        return out

    # -- forward ---------------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} does not define forward()."
        )

    def scope(self):
        """The module's own name as a ``jax.named_scope``, so the scope
        stack of every operation reads as the module path (block3/attn,
        block3/mlp/1); metadata only, the compiled program does not
        change.  ``__call__`` opens it around ``forward``; a module with a
        second entry point opens it there (``nn.HyperConnection.post``)."""
        return jax.named_scope(
            (self._path or type(self).__name__).rpartition(".")[2])

    def __call__(self, *args, **kwargs):
        _ctx()  # modules may only be invoked during apply()
        with self.scope():
            return self.forward(*args, **kwargs)

    # -- conveniences ----------------------------------------------------------
    def param_count(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    def __repr__(self) -> str:
        lines = [type(self).__name__ + "("]
        for name, mod in self._modules.items():
            body = repr(mod).replace("\n", "\n  ")
            lines.append(f"  ({name}): {body}")
        lines.append(")")
        return "\n".join(lines) if len(lines) > 2 else type(self).__name__ + "()"


class Sequential(Module):
    """Chain of modules applied in order (torch ``nn.Sequential`` analogue)."""

    def __init__(self, *modules: Module):
        super().__init__()
        for i, mod in enumerate(modules):
            setattr(self, str(i), mod)
        self._length = len(modules)

    def __len__(self) -> int:
        return self._length

    def __getitem__(self, i: int) -> Module:
        idx = i if i >= 0 else self._length + i
        if not 0 <= idx < self._length:
            raise IndexError(f"Sequential index {i} out of range "
                             f"(length {self._length})")
        return getattr(self, str(idx))

    def forward(self, x):
        for i in range(self._length):
            x = getattr(self, str(i))(x)
        return x


def run_capturing_state(module: Module, args: tuple, kwargs: dict = None):
    """Run ``module(*args, **kwargs)`` with the apply-context's state-update
    sink swapped for a fresh dict, returning ``(output, captured_updates)``.

    This turns a submodule's state writes (BN running stats, MoE aux
    losses) into explicit return values — required when the call runs
    inside a ``jax.checkpoint`` sub-trace, where writing to the outer
    context would leak tracers.  The caller re-publishes the updates via
    ``ctx.put_state`` outside the checkpointed region."""
    ctx = current_context()
    out_kwargs = kwargs or {}
    if ctx is None or ctx.new_state is None:
        return module(*args, **out_kwargs), {}
    saved = ctx.new_state
    ctx.new_state = {}
    try:
        out = module(*args, **out_kwargs)
        updates = ctx.new_state
    finally:
        ctx.new_state = saved
    return out, updates


class Remat(Module):
    """Activation checkpointing (``torch.utils.checkpoint.checkpoint``
    parity, as a wrapper module): the wrapped module's forward activations
    are NOT kept for backward — they are recomputed during the backward
    pass (``jax.checkpoint``), trading FLOPs for HBM.

    Usage::

        block = nn.Remat(TransformerBlock(...))
        y = block(x)

    NOTE: wrapping inserts one level into parameter paths — the wrapped
    module's params live under the ``inner`` attribute (``"<name>.X"``
    becomes ``"<name>.inner.X"``), so checkpoints trained without the
    wrapper need their keys remapped (or wrap before the first init).

    ``policy`` forwards to ``jax.checkpoint`` (e.g.
    ``jax.checkpoint_policies.dots_with_no_batch_dims_saveable`` keeps
    matmul outputs and recomputes the rest).  Keyword arguments and the
    module's parameters reach the inner module as closed-over values —
    ``jax.checkpoint`` differentiates through closures, so no explicit
    plumbing is needed; state updates are captured and re-published
    outside the sub-trace (see :func:`run_capturing_state`)."""

    def __init__(self, module: Module, policy=None):
        super().__init__()
        self.inner = module
        self.policy = policy

    def forward(self, *args, **kwargs):
        def inner_fn(*a):
            return run_capturing_state(self.inner, a, kwargs)

        out, updates = jax.checkpoint(inner_fn, policy=self.policy)(*args)
        ctx = current_context()
        if ctx is not None and updates:
            for path, val in updates.items():
                ctx.put_state(path, val)
        return out
