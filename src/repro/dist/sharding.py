"""Logical-axis sharding: name activation dims, resolve them per mesh.

Model code annotates activations with *logical* axis names
(``constrain(x, "batch", "seq", "embed")``) instead of mesh axes, so the
same forward pass runs unsharded in unit tests, on the host mesh, and on
the (2, 16, 16) production mesh without edits. The mapping from logical
name to mesh axes lives in one table (:data:`DEFAULT_RULES`, DESIGN.md §6):

  * ``batch``   -> ("pod", "data")   outer data parallelism / FSDP
  * ``heads`` / ``mlp`` / ``vocab`` / ``experts`` -> "model"  (TP / EP)
  * ``kv_seq`` -> "model"            decode KV cache sequence sharding
                                     (flash-decoding softmax; kv *heads*
                                     stay unsharded — GQA head counts are
                                     usually below the TP degree)
  * ``seq`` / ``embed`` / ``kv_heads`` -> None (left to XLA propagation)

``constrain`` is a no-op unless an :func:`axis_rules` context is active, so
importing a model never touches jax device state. Inside the context it
lowers to ``jax.lax.with_sharding_constraint`` with every rule filtered
against the live mesh: axes the mesh doesn't have are dropped, and a dim
that the surviving axes don't divide evenly is left unconstrained (small
test meshes must never make a model shape invalid).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "DEFAULT_RULES",
    "active_mesh",
    "axis_rules",
    "constrain",
    "logical_spec",
    "member_spec",
]

# One entry per logical activation axis: mesh axis name, tuple of names, or
# None (unconstrained). Axes missing from the live mesh are filtered at
# resolution time, so the same table serves (data,), (data, model) and
# (pod, data, model) meshes.
Rule = Union[None, str, Tuple[str, ...]]

DEFAULT_RULES: Dict[str, Rule] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": None,
    "kv_seq": "model",
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
}

_ACTIVE = threading.local()  # .stack: list of (mesh, rules)


def _filter_rule(rule: Rule, mesh: Mesh) -> Rule:
    """Drop mesh axes the live mesh doesn't have; collapse empties to None."""
    if rule is None:
        return None
    if isinstance(rule, str):
        return rule if rule in mesh.axis_names else None
    kept = tuple(a for a in rule if a in mesh.axis_names)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def logical_spec(name: Optional[str], *, mesh: Mesh, rules: Optional[Dict[str, Rule]] = None) -> Rule:
    """Resolve one logical axis name to a PartitionSpec entry for ``mesh``.

    Unknown names raise ``KeyError`` — a typo'd logical axis must fail loudly
    rather than silently replicate. ``None`` passes through (unconstrained).
    """
    if name is None:
        return None
    table = DEFAULT_RULES if rules is None else rules
    return _filter_rule(table[name], mesh)


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Optional[Dict[str, Rule]] = None):
    """Activate ``constrain`` with this mesh + rule table for the block.

    Nestable; the innermost context wins. Typical use::

        with mesh, axis_rules(mesh):
            step = jax.jit(make_train_step(...))
            state, metrics = step(state, batch)
    """
    stack = getattr(_ACTIVE, "stack", None)
    if stack is None:
        stack = _ACTIVE.stack = []
    stack.append((mesh, DEFAULT_RULES if rules is None else rules))
    try:
        yield mesh
    finally:
        stack.pop()


def active_mesh() -> Optional[Mesh]:
    """The innermost :func:`axis_rules` context's mesh, or None.

    Lets mesh-agnostic layers (e.g. the ``repro.service`` batcher putting
    bucket members on the logical ``batch`` axis) decide whether to request
    sharded ensembles without threading a mesh handle through their API.
    """
    stack = getattr(_ACTIVE, "stack", None)
    return stack[-1][0] if stack else None


def member_spec(n: int) -> Optional[Tuple[Mesh, P]]:
    """``(mesh, PartitionSpec)`` splitting a leading member dim of extent
    ``n`` over the active context's ``batch`` axes — the spec a
    ``shard_map`` over independent members takes — or None outside an
    :func:`axis_rules` context, or when those axes do not divide ``n``."""
    stack = getattr(_ACTIVE, "stack", None)
    if not stack:
        return None
    mesh, rules = stack[-1]
    rule = logical_spec("batch", mesh=mesh, rules=rules)
    if rule is None or n % _axis_extent(rule, mesh) != 0:
        return None
    return mesh, P(rule)


def _axis_extent(rule: Rule, mesh: Mesh) -> int:
    ext = 1
    for a in rule if isinstance(rule, tuple) else (rule,):
        ext *= mesh.shape[a]
    return ext


def constrain(x, *names: Optional[str]):
    """Annotate each dim of ``x`` with a logical axis name (or None).

    Outside an :func:`axis_rules` context this is the identity, which keeps
    unit tests and single-host examples mesh-free. Inside, it resolves every
    name through the active rule table and applies a sharding constraint,
    skipping dims the mesh extent does not divide.
    """
    stack = getattr(_ACTIVE, "stack", None)
    if not stack:
        return x
    mesh, rules = stack[-1]
    if len(names) != x.ndim:
        raise ValueError(
            f"constrain got {len(names)} axis names for rank-{x.ndim} value {x.shape}"
        )
    entries = []
    for dim, name in zip(x.shape, names):
        rule = logical_spec(name, mesh=mesh, rules=rules)
        if rule is not None and dim % _axis_extent(rule, mesh) != 0:
            rule = None  # uneven split: leave the dim to XLA propagation
        entries.append(rule)
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*entries)))
