"""Logical-axis sharding API (the thin layer every model touches).

Models annotate activations with *logical* axes (``batch``, ``seq``,
``embed``, ``heads``, ``ff`` ...).  A :class:`ShardingRules` context resolves
logical axes to mesh axes; outside any context annotations are no-ops so the
same model code runs in single-device tests and in the 512-chip dry-run.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

MeshAxes = Union[None, str, tuple[str, ...]]


class ShardingRules:
    """Mapping logical axis -> mesh axis (or tuple, or None)."""

    def __init__(self, **rules: MeshAxes):
        self.rules: dict[str, MeshAxes] = dict(rules)

    def resolve(self, logical: Sequence[Optional[str]]) -> P:
        out = []
        for ax in logical:
            out.append(None if ax is None else self.rules.get(ax))
        return P(*out)

    def replace(self, **kw: MeshAxes) -> "ShardingRules":
        r = dict(self.rules)
        r.update(kw)
        return ShardingRules(**r)


def current_rules() -> Optional[ShardingRules]:
    return getattr(_state, "rules", None)


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextmanager
def use_rules(rules: ShardingRules, mesh: Optional[Mesh] = None):
    old_r = getattr(_state, "rules", None)
    old_m = getattr(_state, "mesh", None)
    _state.rules = rules
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.rules = old_r
        _state.mesh = old_m


def logical_spec(logical: Sequence[Optional[str]]) -> P:
    r = current_rules()
    if r is None:
        return P()
    return r.resolve(logical)


def shard(x, *logical: Optional[str]):
    """Annotate ``x`` with logical axes (no-op without rules/mesh)."""
    r = current_rules()
    if r is None:
        return x
    spec = r.resolve(logical)
    return jax.lax.with_sharding_constraint(x, spec)


def named_sharding(mesh: Mesh, logical: Sequence[Optional[str]], rules: ShardingRules) -> NamedSharding:
    return NamedSharding(mesh, rules.resolve(logical))
