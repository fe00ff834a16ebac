"""Logical axis rules with divisibility fallback.

MaxText-style indirection: model code annotates arrays with *logical* axis
names ("batch", "heads", "mlp", ...); a rule table maps logical names to mesh
axes. Resolution drops any mesh axis that does not evenly divide the dimension
(e.g. 24 attention heads on a 16-way ``model`` axis, or 8 Mixtral experts).
Without an active table (``use_rules``), ``logical_constraint`` is the
identity; the single-device serving path activates none.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Mapping, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> ordered candidate mesh axes. Earlier axes are applied first;
# each mesh axis may be used at most once per array.
LogicalRules = Mapping[str, Tuple[str, ...]]


class _RulesState(threading.local):
    def __init__(self):
        self.rules: Optional[LogicalRules] = None
        self.mesh: Optional[Mesh] = None


_STATE = _RulesState()


@contextlib.contextmanager
def use_rules(rules: Optional[LogicalRules], mesh: Optional[Mesh] = None):
    """Activate a logical-rule table (and optionally a mesh) for model code."""
    prev = (_STATE.rules, _STATE.mesh)
    _STATE.rules, _STATE.mesh = rules, mesh
    try:
        yield
    finally:
        _STATE.rules, _STATE.mesh = prev


def resolve_spec(
    shape: Sequence[int],
    logical_axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: LogicalRules,
) -> P:
    """Map logical axes to a PartitionSpec, dropping non-dividing mesh axes."""
    if len(shape) != len(logical_axes):
        raise ValueError(
            f"shape rank {len(shape)} != logical axes {logical_axes}"
        )
    used: set = set()
    out = []
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for dim, name in zip(shape, logical_axes):
        if name is None:
            out.append(None)
            continue
        candidates = rules.get(name, ())
        chosen = []
        remaining = dim
        for ax in candidates:
            if ax not in axis_sizes or ax in used:
                continue
            sz = axis_sizes[ax]
            if remaining % sz == 0:
                chosen.append(ax)
                used.add(ax)
                remaining //= sz
        if not chosen:
            out.append(None)
        elif len(chosen) == 1:
            out.append(chosen[0])
        else:
            out.append(tuple(chosen))
    # strip trailing Nones for a tidy spec
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def logical_constraint(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """``with_sharding_constraint`` by logical axis names; no-op w/o rules."""
    rules = _STATE.rules
    mesh = _STATE.mesh
    if rules is None or mesh is None:
        return x
    spec = resolve_spec(x.shape, logical_axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
