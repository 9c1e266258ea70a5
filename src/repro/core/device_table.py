"""Device-side handler tables: HAM's dispatch, compiled into one executable.

This is the TPU-native centrepiece of the adaptation (DESIGN.md §2).  The
paper's receiving side is: typeless buffer -> header key -> handler-vector
index -> call.  On a TPU worker, the analogous cost structure appears when a
runtime must *select which step function to run* (prefill / decode / update /
rollback ...).  Vendor-style dispatch pays a host round-trip plus executable
swap (or worse, a re-trace) per selection.  HAMax compiles the whole handler
vector into **one** XLA executable containing a ``jax.lax.switch`` over the
branches; the key then travels as device data and dispatch costs one
integer-indexed branch on device.

Constraints (the price of a shared executable, stated up front):

* all branches must accept the same payload pytree structure/shapes/dtypes
  and produce the same result structure — the "fixed payload spec handler
  class" (validated via ``jax.eval_shape`` at build time);
* like the host registry, keys are assigned by sorting stable names, so two
  differently-compiled processes (heterogeneous binaries: different meshes,
  device kinds) agree on every device key with zero communication.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from repro.core.errors import RegistryError, UnknownHandlerError


@dataclasses.dataclass(frozen=True)
class DeviceHandler:
    stable_name: str
    fn: Callable  # payload_pytree -> result_pytree


class DeviceHandlerTable:
    """Builds ``dispatch(key, payload)`` = ``lax.switch`` over sorted handlers."""

    def __init__(self):
        self._entries: dict[str, Callable] = {}
        self._sealed: list[DeviceHandler] | None = None

    def register(self, name: str, fn: Callable) -> Callable:
        if self._sealed is not None:
            raise RegistryError("device table already built")
        if name in self._entries and self._entries[name] is not fn:
            raise RegistryError(f"device handler name collision: {name!r}")
        self._entries[name] = fn
        return fn

    def handler(self, name: str):
        def wrap(fn: Callable) -> Callable:
            self.register(name, fn)
            return fn

        return wrap

    # -- init: sort -> keys (communication-free, as in the host registry) ---

    def seal(self) -> None:
        self._sealed = [
            DeviceHandler(n, self._entries[n]) for n in sorted(self._entries)
        ]

    @property
    def handlers(self) -> list[DeviceHandler]:
        if self._sealed is None:
            self.seal()
        return self._sealed

    def key_of(self, name: str) -> int:
        for i, h in enumerate(self.handlers):
            if h.stable_name == name:
                return i
        raise UnknownHandlerError(f"no device handler named {name!r}")

    def __len__(self) -> int:
        return len(self.handlers)

    # -- build the compiled switch table ------------------------------------

    def validate(self, payload_spec: Any, *operand_specs: Any) -> Any:
        """All branches must agree on the result spec for ``payload_spec``.

        Returns the common result spec.  ``jax.eval_shape`` costs no device
        memory — this is the registration-time type check, the analogue of
        the upcast being statically sound in C++.
        """
        specs = [jax.eval_shape(h.fn, payload_spec, *operand_specs)
                 for h in self.handlers]
        ref_tree = jax.tree_util.tree_structure(specs[0])
        ref_leaves = jax.tree_util.tree_leaves(specs[0])
        for h, s in zip(self.handlers[1:], specs[1:]):
            if jax.tree_util.tree_structure(s) != ref_tree:
                raise RegistryError(
                    f"device handler {h.stable_name!r} result tree structure "
                    f"differs from {self.handlers[0].stable_name!r}"
                )
            for a, b in zip(jax.tree_util.tree_leaves(s), ref_leaves):
                if a.shape != b.shape or a.dtype != b.dtype:
                    raise RegistryError(
                        f"device handler {h.stable_name!r} result leaf "
                        f"{a.shape}/{a.dtype} != {b.shape}/{b.dtype}"
                    )
        return specs[0]

    def build(
        self,
        payload_spec: Any,
        *operand_specs: Any,
        donate_payload: bool = False,
    ) -> Callable:
        """Compile ``dispatch(key, payload, *operands)``.

        ``donate_payload=True`` donates the payload buffers (serving loops
        thread a state pytree through the table; donation makes the update
        in-place on device — essential for multi-GB KV caches).

        ``operands`` are read-only inputs every branch receives after the
        payload — the serving table's model weights.  They travel as
        arguments, never donated: an array a branch closed over instead
        would be lowered into the executable as a constant, so every
        executable would carry its own copy of the weights.
        """
        self.validate(payload_spec, *operand_specs)
        branches = [h.fn for h in self.handlers]

        def dispatch(key, payload, *operands):
            return jax.lax.switch(key, branches, payload, *operands)

        donate = (1,) if donate_payload else ()
        return jax.jit(dispatch, donate_argnums=donate)
