"""Logical-axis sharding: named rules resolved against a physical mesh.

Model code annotates tensors with *logical* dimension names ("batch",
"heads", "mlp", ...). A rules table maps each logical name to an ordered
tuple of candidate *physical* mesh axes; `resolve_spec` turns (shape,
names, mesh, rules) into a concrete `PartitionSpec` with two safety
properties the tests pin down:

  * divisibility fallback — a dimension that a candidate axis does not
    divide evenly is replicated rather than unevenly sharded (so batch=1
    decode or kv_heads < model-parallelism never produce invalid specs);
  * no axis reuse — one physical axis shards at most one dimension of a
    given tensor (first logical name wins, later ones replicate).

`axis_rules(mesh, rules)` installs a context; `constrain(x, *names)`
applies `with_sharding_constraint` inside it and is the identity outside
(or under `axis_rules(None)`, which disables constraints inside shard_map
manual regions).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

Rules = Dict[str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# rule tables (policy variants used by launch/plans.py cells)
# ---------------------------------------------------------------------------

# bulk-bitwise cluster execution (core/cluster.py): the word-shard "chip"
# axis maps onto the physical chip mesh axis; the per-chip "bank" axis
# stays a local batch dimension (banks never leave their chip — a Buddy op
# is contained in one subarray). Single source for the chip-axis mapping;
# DEFAULT_RULES folds it in so `constrain`-style callers resolve it too.
CLUSTER_RULES: Rules = {"chip": ("chip",), "bank": ()}

DEFAULT_RULES: Rules = {
    **CLUSTER_RULES,
    # activations
    "batch": ("pod", "data"),
    "seq": (),
    "kv_seq": (),
    "embed_act": (),
    # params
    "fsdp": ("data",),
    "embed": (),
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "kv_flat": ("model",),
    "head_dim": (),
    "vocab": ("model",),
    "experts": ("model",),
    "state": (),
    "conv": (),
    "conv_w": (),
    "conv_b": (),
    "groups": (),
    "patches": (),
}

# data-parallel-only: params replicated across the dp axes (the model axis
# stays GSPMD-auto); used by the compressed signum/majority train step.
DP_RULES: Rules = {**DEFAULT_RULES, "fsdp": ()}

# sequence parallelism: long-context activations shard their seq dim.
SP_RULES: Rules = {**DEFAULT_RULES, "seq": ("model",)}

# decode-time sequence parallelism: the KV cache shards over model.
DECODE_SP_RULES: Rules = {**DEFAULT_RULES, "kv_seq": ("model",),
                          "kv_flat": ("model",)}


# ---------------------------------------------------------------------------
# context
# ---------------------------------------------------------------------------

_CTX = threading.local()


def _stack() -> List[Tuple[Any, Optional[Rules]]]:
    if not hasattr(_CTX, "stack"):
        _CTX.stack = []
    return _CTX.stack


@contextlib.contextmanager
def axis_rules(mesh=None, rules: Optional[Rules] = None):
    """Install (mesh, rules) for `constrain`/`current_mesh`/`current_rules`.

    `axis_rules(None)` pushes a *disabled* context: constraints inside are
    the identity even if an outer context is active (needed inside
    shard_map manual regions where constraint specs cannot be applied).
    """
    if mesh is not None and rules is None:
        rules = DEFAULT_RULES
    _stack().append((mesh, rules))
    try:
        yield
    finally:
        _stack().pop()


def current_mesh():
    """Mesh of the innermost `axis_rules` context (None if disabled/absent)."""
    s = _stack()
    return s[-1][0] if s else None


def current_rules() -> Optional[Rules]:
    """Rules of the innermost `axis_rules` context (None if disabled/absent)."""
    s = _stack()
    return s[-1][1] if s else None


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------


def _mesh_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def resolve_spec(shape: Sequence[int], names: Sequence[Optional[str]],
                 mesh, rules: Optional[Rules] = None) -> P:
    """Resolve logical dim names to a PartitionSpec for `mesh`.

    Per dimension: walk the rule's candidate axes in order, taking each
    axis that (a) exists in the mesh, (b) is not already used by an
    earlier dimension of this tensor, and (c) keeps the dimension evenly
    divisible by the product of taken axis sizes. No taken axes (or name
    None / unknown) -> replicated.
    """
    if rules is None:
        rules = current_rules() or DEFAULT_RULES
    sizes = _mesh_sizes(mesh)
    used: set = set()
    out: List[Any] = []
    for dim, name in zip(shape, names):
        if name is None:
            out.append(None)
            continue
        axes = rules.get(name, ())
        if isinstance(axes, str):
            axes = (axes,)
        taken: List[str] = []
        prod = 1
        for a in axes:
            if a not in sizes or a in used:
                continue
            if dim % (prod * sizes[a]) != 0:
                continue  # this axis doesn't divide; later ones may
            taken.append(a)
            prod *= sizes[a]
        used.update(taken)
        if not taken:
            out.append(None)
        elif len(taken) == 1:
            out.append(taken[0])
        else:
            out.append(tuple(taken))
    return P(*out)


def constrain(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """`with_sharding_constraint(x, resolve_spec(...))` under an active
    `axis_rules` context; the identity (same object) outside one."""
    mesh, rules = (_stack()[-1] if _stack() else (None, None))
    if mesh is None or rules is None:
        return x
    spec = resolve_spec(x.shape, names, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def strip_axes(rules: Rules, axes: Sequence[str]) -> Rules:
    """Rules with the given physical axes removed from every entry."""
    drop = set(axes)
    return {k: tuple(a for a in v if a not in drop) for k, v in rules.items()}


def _is_spec_leaf(x) -> bool:
    return x is None or (isinstance(x, tuple)
                         and all(e is None or isinstance(e, str) for e in x))


def tree_shardings(shapes: Any, specs: Any, mesh,
                   rules: Optional[Rules] = None) -> Any:
    """NamedSharding tree for `shapes` (leaves with .shape) given a
    matching tree of logical-name tuples (`specs`)."""
    if rules is None:
        rules = current_rules() or DEFAULT_RULES
    flat_shapes, treedef = jax.tree.flatten(shapes)
    flat_specs = jax.tree.leaves(specs, is_leaf=_is_spec_leaf)
    assert len(flat_shapes) == len(flat_specs), \
        (len(flat_shapes), len(flat_specs))
    out = []
    for leaf, names in zip(flat_shapes, flat_specs):
        if names is None:
            names = (None,) * len(leaf.shape)
        out.append(NamedSharding(
            mesh, resolve_spec(tuple(leaf.shape), names, mesh, rules)))
    return jax.tree.unflatten(treedef, out)


def match_vma(x: Any, ref: jax.Array) -> Any:
    """Make every leaf of `x` vary over (at least) the manual axes `ref`
    varies over — needed to seed scan/loop carries inside shard_map regions
    under vma typing."""
    ref_vma = jax.typeof(ref).vma

    def one(a):
        missing = tuple(sorted(ref_vma - jax.typeof(a).vma))
        return jax.lax.pcast(a, missing, to="varying") if missing else a

    return jax.tree.map(one, x)
