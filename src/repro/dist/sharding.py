"""Logical-axis sharding resolution (the repo's one sharding vocabulary).

Model and launch code never name mesh axes directly.  Parameters, batches
and activations carry *logical* axis names (``batch``, ``seq``, ``heads``,
``vocab``, ``fsdp``, ...); a **rules table** maps each logical name to the
mesh axes it may shard over, and :func:`spec_for_shape` resolves a concrete
``PartitionSpec`` for one array shape on one mesh.

Resolution contract (property-tested in tests/test_sharding.py):

* **Claim order is rules-table order.**  Logical names claim mesh axes in
  the order they appear in the rules dict, so ``heads`` takes ``model``
  before ``seq`` can (context-parallel is the *fallback* when the head
  count is indivisible, not the default).
* **Divisibility is mandatory.**  A mesh axis is only taken when the dim
  is divisible by the product of all axes taken so far for that dim;
  otherwise the candidate is skipped (never a ragged shard).
* **Each mesh axis is used at most once** per spec.
* Candidate axes missing from the mesh (``pod`` on a single-pod mesh) are
  skipped silently, so one rules table serves every mesh shape.

:func:`constrain` is the activation anchor: inside an
:func:`activation_rules` context it resolves the logical axes against the
active (mesh, rules) and applies ``with_sharding_constraint``; outside any
context it returns its input unchanged, so pure-library use (single host,
no mesh) pays nothing.
"""

from __future__ import annotations

import contextlib
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array

# Logical axis -> candidate mesh axes, in claim-priority order (dict order
# IS the priority).  Zero-candidate entries are documentation: those axes
# stay replicated on purpose (embed = sequence-parallel residual stream,
# head_dim = always small, layers = scan axis).
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("data",),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "expert_mlp": ("model",),
    "experts": ("model",),
    "state": ("model",),
    "seq": ("model",),          # context-parallel fallback (after heads)
    "embed": (),
    "head_dim": (),
    "layers": (),
}

# Serving: weights shard over `model` only (no fsdp — ZeRO gathers would
# serialize every decode step).
SERVE_RULES: dict[str, tuple[str, ...]] = dict(DEFAULT_RULES, fsdp=())

# Long-context serving: sequence parallelism outranks head parallelism.
CONTEXT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": ("model",),
    **{k: v for k, v in SERVE_RULES.items() if k not in ("batch", "seq")},
}

# Single-token decode: there is no sequence axis worth sharding.
DECODE_RULES: dict[str, tuple[str, ...]] = dict(SERVE_RULES, seq=())


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> Mesh:
    """A mesh whose axes are all of type Auto."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def spec_for_shape(names: Sequence[str | None], shape: Sequence[int],
                   mesh: Mesh, rules: dict | None = None) -> P:
    """Resolve logical axis names for one array shape to a PartitionSpec."""
    rules = DEFAULT_RULES if rules is None else rules
    if len(names) != len(shape):
        raise ValueError(f"axes {names} do not match shape {tuple(shape)}")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))
    rank = {name: i for i, name in enumerate(rules)}
    order = sorted(
        (i for i, nm in enumerate(names) if nm is not None and nm in rules),
        key=lambda i: rank[names[i]])
    used: set[str] = set()
    entries: list[Any] = [None] * len(names)
    for i in order:
        got: list[str] = []
        prod = 1
        for ax in rules[names[i]]:
            if ax not in sizes or ax in used:
                continue
            if shape[i] % (prod * sizes[ax]) != 0:
                continue
            got.append(ax)
            prod *= sizes[ax]
        used.update(got)
        if got:
            entries[i] = got[0] if len(got) == 1 else tuple(got)
    return P(*entries)


# ---------------------------------------------------------------------------
# Activation anchoring (constrain) — trace-time context
# ---------------------------------------------------------------------------

# Stack of (mesh, rules) pushed by activation_rules; constrain reads the top.
_ACTIVE: list[tuple[Mesh, dict | None]] = []


class _ActivationRules(contextlib.AbstractContextManager):
    def __init__(self, mesh: Mesh, rules: dict | None):
        self._item = (mesh, rules)

    def __enter__(self):
        _ACTIVE.append(self._item)
        return self

    def __exit__(self, *exc):
        _ACTIVE.pop()
        return False


def activation_rules(mesh: Mesh, rules: dict | None = None):
    """Context manager enabling :func:`constrain` at trace time."""
    return _ActivationRules(mesh, rules)


def constrain(x: Array, axes: Sequence[str | None]) -> Array:
    """Anchor an activation to its logical-axis sharding.

    Identity (returns ``x`` itself) outside an :func:`activation_rules`
    context, so model code can call it unconditionally.
    """
    if not _ACTIVE:
        return x
    mesh, rules = _ACTIVE[-1]
    spec = spec_for_shape(tuple(axes), x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _is_axes_leaf(x) -> bool:
    return x is None or (isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x))


def tree_shardings_for_structs(axes: Any, structs: Any, mesh: Mesh,
                               rules: dict | None = None) -> Any:
    """NamedShardings for a pytree of structs from its logical-axes tree.

    ``axes`` leaves are tuples of logical names (or None = replicated),
    mirroring ``structs``'s tree of ShapeDtypeStructs/arrays.
    """
    def resolve(a, s):
        if s is None:
            return None
        if a is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, spec_for_shape(a, s.shape, mesh, rules))

    return jax.tree.map(resolve, axes, structs, is_leaf=_is_axes_leaf)


def shard_map(f, *, mesh: Mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with replication checking off by default.

    ``check=False`` by default: the dist substrates all produce
    value-replicated outputs via explicit collectives that replication
    inference cannot always see through (ring loops especially).
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
