"""Logical-axis sharding: rule tables + the priority-based spec allocator.

Every parameter, cache and activation in this codebase is labelled with
*logical* axis names at init time (the ``AxesMaker`` tree mirrors the param
tree exactly — see ``repro.models.layers``). This module is the single place
where logical names meet a concrete mesh:

* :class:`AxisRules` — one table per deployment regime. A rule maps a
  logical name to an ordered tuple of mesh axes it may absorb, plus a
  priority deciding who wins a contested mesh axis.
* :func:`logical_to_spec` — the allocator. Walks the logical names of one
  tensor in priority order and greedily assigns mesh axes subject to two
  hard invariants (property-tested in ``tests/test_sharding.py``):

    1. each mesh axis is used **at most once** per tensor;
    2. an axis (or axis group) is only assigned when its size product
       **divides** the dimension — otherwise the dim drops to replicated.

  Divisibility-aware *fallback* is what makes the tables production-usable:
  ``kv_heads`` that cannot divide the model axis hand it down to ``kv_seq``
  (flash-decode sharding for GQA/MQA caches), ``experts`` that cannot divide
  it leave it to ``mlp`` (TP fallback), and the batch dim joins the ``pod``
  axis onto ``data`` on multi-pod meshes.
* :func:`sanitize_spec` — clamp an arbitrary spec to the same invariants.
* :func:`tree_shardings` — map a whole (axes, specs) tree pair to
  ``NamedSharding``s for ``StepBundle`` construction in ``launch/steps.py``.
* :func:`constrain` — ``with_sharding_constraint`` against the ambient mesh
  (no-op outside a mesh context), shared by the model code.

The rule tables themselves are documented in DESIGN.md §3.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Mapping

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# Logical names without a rule entry (and ``None`` placeholders) replicate.
DEFAULT_PRIORITY = 9


@dataclass(frozen=True)
class AxisRule:
    """Mesh axes one logical dim may absorb, in preference order."""

    axes: tuple[str, ...] = ()
    priority: int = DEFAULT_PRIORITY


@dataclass(frozen=True)
class AxisRules:
    """A named, immutable logical-name -> :class:`AxisRule` table."""

    name: str
    table: Mapping[str, AxisRule]

    def rule(self, logical: str | None) -> AxisRule | None:
        if logical is None:
            return None
        return self.table.get(logical)

    def priority(self, logical: str | None) -> int:
        rule = self.rule(logical)
        return rule.priority if rule is not None else DEFAULT_PRIORITY

    def override(self, **axes_by_name) -> "AxisRules":
        """Rebind the mesh axes of some logical names (priorities kept).

        Backs the ``REPRO_RULE_OVERRIDE`` hillclimb knob in
        ``launch/steps.py``: ``rules.override(kv_seq=("model", "data"),
        state=())`` returns a new table, the originals are never mutated.
        """
        table = dict(self.table)
        for name, axes in axes_by_name.items():
            prev = table.get(name)
            pri = prev.priority if prev is not None else DEFAULT_PRIORITY
            table[name] = AxisRule(tuple(axes), pri)
        return AxisRules(f"{self.name}+override", table)


# ---------------------------------------------------------------------------
# Rule tables (DESIGN.md §3)
# ---------------------------------------------------------------------------
#
# Priorities: 0 beats 1 beats 2 for a contested mesh axis; ties break by
# tensor position. The fallback chains (kv_heads -> kv_seq, experts -> mlp)
# are encoded purely as priority order — the lower-priority name only gets
# the axis when the higher-priority owner failed divisibility.

RULES_SERVE = AxisRules("serve", {
    # data parallelism: batch over data, joined with pod on multi-pod meshes
    "batch":        AxisRule(("pod", "data"), 0),
    # vocab-parallel logits / embedding table
    "vocab":        AxisRule(("model",), 0),
    # tensor parallelism over heads; EP over the same axis for MoE
    "heads":        AxisRule(("model",), 1),
    "kv_heads":     AxisRule(("model",), 1),
    "experts":      AxisRule(("model",), 1),
    # fallback owners of the model axis (TP for MoE, flash-decode for GQA)
    "mlp":          AxisRule(("model",), 2),
    "kv_seq":       AxisRule(("model",), 2),
    # paged KV pool: the page-pool axis plays the arena role the slot/batch
    # axis plays for whole-row arenas; interior page offsets replicate
    "pages":        AxisRule(("pod", "data"), 1),
    "page":         AxisRule((), 3),
    # replicated at serve time
    "seq":          AxisRule((), 3),
    "embed":        AxisRule((), 3),
    "expert_embed": AxisRule((), 3),
    "head_dim":     AxisRule((), 3),
    "kv_lora":      AxisRule((), 3),
    "state":        AxisRule((), 3),
    "time":         AxisRule((), 3),
    "layers":       AxisRule((), 3),
})

RULES_TRAIN = AxisRules("train", {
    "batch":        AxisRule(("pod", "data"), 0),
    "vocab":        AxisRule(("model",), 0),
    "heads":        AxisRule(("model",), 1),
    "kv_heads":     AxisRule(("model",), 1),
    "experts":      AxisRule(("model",), 1),
    "mlp":          AxisRule(("model",), 1),
    # sequence parallelism for activations (loses model to any priority-0/1
    # owner present on the same tensor, e.g. vocab on the logits)
    "seq":          AxisRule(("model",), 1),
    "kv_seq":       AxisRule(("model",), 2),
    "pages":        AxisRule(("data",), 2),
    "page":         AxisRule((), 3),
    # FSDP: params' embed dim sharded over data (batch never appears on the
    # same tensor, so the axes don't contest)
    "embed":        AxisRule(("data",), 2),
    "expert_embed": AxisRule(("data",), 2),
    "head_dim":     AxisRule((), 3),
    "kv_lora":      AxisRule((), 3),
    "state":        AxisRule((), 3),
    "time":         AxisRule((), 3),
    "layers":       AxisRule((), 3),
})

RULES_LONG = AxisRules("long", {
    "batch":        AxisRule(("pod", "data"), 0),
    "vocab":        AxisRule(("model",), 0),
    "heads":        AxisRule(("model",), 1),
    "kv_heads":     AxisRule(("model",), 1),
    "experts":      AxisRule(("model",), 1),
    "mlp":          AxisRule(("model",), 2),
    # 500k-token caches: the sequence dim absorbs every axis the batch and
    # kv-head dims left on the table (batch=1 and MQA/GQA head counts are
    # the norm at long context); a paged pool's page axis does the same
    "kv_seq":       AxisRule(("pod", "data", "model"), 2),
    "pages":        AxisRule(("pod", "data", "model"), 2),
    "page":         AxisRule((), 3),
    "seq":          AxisRule((), 3),
    "embed":        AxisRule((), 3),
    "expert_embed": AxisRule((), 3),
    "head_dim":     AxisRule((), 3),
    "kv_lora":      AxisRule((), 3),
    "state":        AxisRule((), 3),
    "time":         AxisRule((), 3),
    "layers":       AxisRule((), 3),
})


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------


def _mesh_sizes(mesh) -> dict:
    return dict(mesh.shape)


def _trimmed_spec(entries) -> P:
    entries = list(entries)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _absorb(candidates, dim, sizes, used):
    """Absorb mesh axes for one dim -> spec entry (or None).

    Considers only candidates present in the mesh and unused by this tensor
    so far, and picks the order-preserving subset with the **largest size
    product that divides** ``dim`` — the single definition of the allocator
    invariants, shared by :func:`logical_to_spec` and :func:`sanitize_spec`.
    Maximising (rather than greedy prefix absorption) matters on multi-pod
    meshes: batch=16 on (pod=2, data=16) must take the 16-way ``data`` axis,
    not lock in ``pod`` and stop at 2-way. Ties prefer earlier/fewer axes.
    """
    avail = [ax for ax in candidates if ax in sizes and ax not in used]
    best: tuple[str, ...] = ()
    best_prod = 0   # 0, not 1: a size-1 mesh axis is still worth naming
    for r in range(1, len(avail) + 1):
        for combo in itertools.combinations(avail, r):
            prod = math.prod(sizes[ax] for ax in combo)
            if prod > best_prod and dim % prod == 0:
                best, best_prod = combo, prod
    if not best:
        return None
    used.update(best)
    return best[0] if len(best) == 1 else best


def logical_to_spec(names, rules: AxisRules, *, shape, mesh) -> P:
    """Allocate mesh axes to one tensor's logical names -> PartitionSpec.

    ``names``: tuple of logical axis names (``None`` entries replicate);
    ``shape``: the tensor shape (divisibility checks); ``mesh``: anything
    with ``.shape``/``.axis_names`` (``Mesh`` or ``AbstractMesh``).

    Dims are visited in rule-priority order (ties by position), each
    greedily absorbing its candidate axes left-to-right. A candidate is
    taken only if it exists in the mesh, is still unused by this tensor,
    and keeps the absorbed size product dividing the dim — so indivisible
    dims fall through to the next name in the fallback chain or drop to
    replicated, and every produced spec satisfies the allocator invariants.
    """
    names = tuple(names)
    shape = tuple(shape)
    if len(names) != len(shape):
        raise ValueError(f"names/shape rank mismatch: {names} vs {shape}")
    sizes = _mesh_sizes(mesh)
    order = sorted(range(len(names)),
                   key=lambda i: (rules.priority(names[i]), i))
    used: set[str] = set()
    entries: list = [None] * len(names)
    for i in order:
        rule = rules.rule(names[i])
        if rule is None:
            continue
        entries[i] = _absorb(rule.axes, shape[i], sizes, used)
    return _trimmed_spec(entries)


def sanitize_spec(shape, spec: P, mesh) -> P:
    """Clamp an arbitrary PartitionSpec to the allocator invariants.

    Drops axes that are absent from the mesh, already used earlier in the
    spec, or whose size product stops dividing the dim; trims trailing
    ``None``s. Idempotent on allocator output. A spec with more entries
    than the tensor has dims is a caller bug and raises.
    """
    spec = tuple(spec)
    if len(spec) > len(shape):
        raise ValueError(f"spec rank exceeds tensor rank: {spec} vs {shape}")
    sizes = _mesh_sizes(mesh)
    used: set[str] = set()
    entries: list = []
    for dim, entry in zip(shape, spec + (None,) * (len(shape) - len(spec))):
        if entry is None:
            entries.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        entries.append(_absorb(axes, dim, sizes, used))
    return _trimmed_spec(entries)


# ---------------------------------------------------------------------------
# Tree-level helpers
# ---------------------------------------------------------------------------


def tree_shardings(axes_tree, specs_tree, mesh, rules: AxisRules):
    """(AxesMaker tree, SpecMaker tree) -> matching tree of NamedShardings.

    The two trees come from the same ``init_*`` code run under different
    makers, so they are structurally identical by construction; logical-axis
    tuples are the leaves of the axes tree (``layers.is_axes_leaf``).
    """
    from repro.models.layers import is_axes_leaf

    def one(axes, spec):
        return NamedSharding(
            mesh, logical_to_spec(axes, rules, shape=spec.shape, mesh=mesh))

    return jax.tree.map(one, axes_tree, specs_tree, is_leaf=is_axes_leaf)


def ambient_mesh():
    """The mesh ``jax.set_mesh`` made ambient, or ``None`` outside one
    (jax reports "no mesh" as an empty ``AbstractMesh``)."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def constrain(x, logical, rules: AxisRules | None):
    """Sharding hint against the ambient mesh (no-op without one).

    Inside ``jit`` under ``jax.set_mesh`` this pins the layout GSPMD must
    propagate; outside any mesh (unit tests, single-host runs) it returns
    ``x`` unchanged.
    """
    if rules is None:
        return x
    mesh = ambient_mesh()
    if mesh is None:
        return x
    spec = logical_to_spec(logical, rules, shape=x.shape, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, spec)
