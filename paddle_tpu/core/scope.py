"""Scope: hierarchical name -> value maps holding device arrays.

Capability-equivalent of the reference Scope/Variable (reference:
paddle/fluid/framework/scope.h:38, variable.h:25): persistable variables
(parameters, optimizer accumulators) live here between executor runs as
jax.Arrays resident on device; child scopes serve control-flow step state.
"""
from __future__ import annotations

from typing import Any, Dict, Iterator, Optional


class Scope:
    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, Any] = {}
        self.parent = parent
        self._kids = []

    def new_scope(self) -> "Scope":
        kid = Scope(self)
        self._kids.append(kid)
        return kid

    def set(self, name: str, value: Any) -> None:
        self._vars[name] = value

    def find(self, name: str) -> Optional[Any]:
        s: Optional[Scope] = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has(self, name: str) -> bool:
        return self.find(name) is not None

    def get(self, name: str) -> Any:
        v = self.find(name)
        if v is None:
            raise KeyError(f"variable {name!r} not found in scope")
        return v

    def values_of(self, names) -> list:
        """`get` of each name, in order: one pass over this scope's own
        dict where it holds them all (a step's state, read every call),
        name by name up the parents where it does not."""
        held = self._vars
        try:
            return [held[n] for n in names]
        except KeyError:
            return [self.get(n) for n in names]

    def update(self, values: Dict[str, Any]) -> None:
        """`set` of every item."""
        self._vars.update(values)

    def erase(self, name: str) -> None:
        self._vars.pop(name, None)

    def local_names(self) -> Iterator[str]:
        return iter(self._vars)

    def items(self):
        return self._vars.items()

    def __contains__(self, name: str) -> bool:
        return self.has(name)

    def __len__(self):
        return len(self._vars)


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def reset_global_scope() -> Scope:
    global _global_scope
    _global_scope = Scope()
    return _global_scope
