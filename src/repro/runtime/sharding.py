"""Sharding resolution: logical specs -> physical NamedShardings.

Model code writes PartitionSpecs against logical axes (``"model"`` and the
``BATCH_AXES`` tuple ``("pod", "data")``).  This module resolves them for a
concrete mesh:

* single-pod mesh ("data", "model"): batch -> ("data",)
* multi-pod mesh ("pod", "data", "model"): batch -> ("pod", "data")
* smoke meshes (1 device): everything -> None

It also applies the FengHuang memory tiers: the memory kind of every
NamedSharding is resolved through :mod:`repro.memory.tiers` — local for
ordinary params, remote for pageable groups when the pager is enabled —
so the same spec tree places correctly on GPU/TPU (``device`` /
``pinned_host``) and on the CPU backend (where both tiers are
``unpinned_host`` and a hardcoded kind would be rejected outright).

The serving runtime runs its dispatches inside :func:`activate_mesh`
so :func:`maybe_constraint` — the logical-spec constraint model code
sprinkles on residuals and attention internals — resolves against the
serving mesh; outside a mesh context it stays a no-op.
"""
from __future__ import annotations

import contextlib
import re
import threading

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.memory import tiers as memtiers
from repro.models.base import BATCH_AXES

PAGEABLE_GROUPS = ("layers", "groups", "dec_layers", "enc_layers")


def resolve_spec(spec: P, mesh: Mesh) -> P:
    """Map logical axis entries to the axes present in ``mesh``."""
    axes = set(mesh.axis_names)
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, tuple):           # e.g. ("pod", "data")
            kept = tuple(a for a in entry if a in axes)
            out.append(kept if kept else None)
        elif entry == "model":
            out.append("model" if "model" in axes else None)
        elif entry in ("pod", "data"):
            out.append(entry if entry in axes else None)
        else:
            out.append(entry if entry in axes else None)
    return P(*out)


def _treat_as_leaf(x) -> bool:
    return isinstance(x, P)


def resolve_tree(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: resolve_spec(s, mesh), spec_tree,
                        is_leaf=_treat_as_leaf)


def named_shardings(spec_tree: Any, mesh: Mesh, *,
                    pageable_remote: bool = False) -> Any:
    """PartitionSpec tree -> NamedSharding tree.

    With ``pageable_remote=True``, specs under PAGEABLE_GROUPS are placed
    in the FengHuang remote tier — the weights will be paged into local
    memory by the orchestrator's layer scans.  Both tiers' memory kinds
    come from the :class:`~repro.memory.tiers.TierRegistry` for the
    current backend (``device`` local, ``pinned_host`` remote on TPU and
    CPU alike).
    """

    def convert(path, s):
        tier = memtiers.LOCAL
        if pageable_remote and path and getattr(path[0], "key", None) in PAGEABLE_GROUPS:
            tier = memtiers.REMOTE
        return memtiers.tier_sharding(mesh, resolve_spec(s, mesh), tier)

    return jax.tree_util.tree_map_with_path(convert, spec_tree,
                                            is_leaf=_treat_as_leaf)


def batch_spec(mesh: Mesh, *trailing) -> P:
    """Spec for (batch, ...) data: batch over ("pod","data") as available."""
    axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    return P(axes if axes else None, *trailing)


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully replicated placement on ``mesh`` (decode state, page tables,
    per-slot bookkeeping — everything the host mirrors byte-exactly).
    Local-tier resident, with the memory kind resolved through the tier
    registry like every other NamedSharding here — tier resolution has
    one owner."""
    return memtiers.tier_sharding(mesh, P(), memtiers.LOCAL)


def constraint(x, mesh: Mesh, spec: P):
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, resolve_spec(spec, mesh)))


# ---------------------------------------------------------------------------
# Ambient mesh
# ---------------------------------------------------------------------------

def mesh_axis_sizes(mesh) -> dict[str, int]:
    """Axis name -> size for a ``Mesh`` or an ``AbstractMesh``."""
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def ambient_mesh():
    """The (abstract) mesh enclosing the current trace or call — set by
    :func:`activate_mesh` — or None."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def activate_mesh(mesh: Mesh | None):
    """Context manager making ``mesh`` ambient for traces inside it, so
    bare-PartitionSpec constraints (and :func:`maybe_constraint`) resolve.
    ``None`` is a no-op context (single-device serving)."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)


def maybe_constraint(x, spec: P):
    """Sharding constraint against the *ambient* mesh.

    Model code calls this with logical specs (e.g. sequence-parallel
    residuals P(batch, "model", None)); outside a mesh context, or when an
    axis is missing / the dim isn't divisible, it's a no-op — so smoke
    tests and single-device runs are unaffected.
    """
    am = ambient_mesh()
    if am is None:
        return x
    sizes = mesh_axis_sizes(am)
    out = []
    for dim, entry in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        names = ()
        if entry is None:
            names = ()
        elif isinstance(entry, tuple):
            names = tuple(a for a in entry if a in sizes)
        elif entry in sizes:
            names = (entry,)
        total = 1
        for n in names:
            total *= sizes[n]
        if names and total > 1 and dim % total == 0:
            out.append(names if len(names) > 1 else names[0])
        else:
            out.append(None)
    if all(e is None for e in out):
        return x
    return _constrain(x, P(*out))


def _constrain(x, spec: P):
    """Sharding constraint against the ambient mesh; an eager array is
    placed on the concrete mesh :func:`activate_mesh` set."""
    if not isinstance(x, jax.core.Tracer):
        spec = NamedSharding(jax.sharding.get_mesh(), spec)
    return jax.lax.with_sharding_constraint(x, spec)


_TP_STATE = threading.local()


@contextlib.contextmanager
def gather_tp_mode():
    """Arm :func:`replicate_constraint` for the extent of a trace.

    The all-gather-TP boundary belongs ONLY to the serving placement
    (output projections replicated — ``serving_param_specs``); traced
    under a mesh with the *training* placement (e.g. the dry-run cost
    model, where ``wo`` stays contraction-sharded) the same constraint
    would inject per-layer replication gathers on top of the row-
    parallel partial sums — strictly worse traffic and wrong cost
    tables.  ``BatchedServer`` enters this context around every
    dispatch; everything else leaves the constraint a no-op."""
    prev = getattr(_TP_STATE, "gather", False)
    _TP_STATE.gather = True
    try:
        yield
    finally:
        _TP_STATE.gather = prev


def replicate_constraint(x):
    """Explicitly constrain ``x`` to FULLY REPLICATED under the ambient
    mesh — an all-gather when it is currently sharded.  This is the
    all-gather-TP boundary ``maybe_constraint`` cannot express: an
    all-``None`` spec is its no-op, while here replication is the whole
    point.  No-op outside :func:`gather_tp_mode` or a mesh context."""
    if not getattr(_TP_STATE, "gather", False):
        return x
    if ambient_mesh() is None:
        return x
    return _constrain(x, P())


# ---------------------------------------------------------------------------
# Per-axis collective accounting (the serving bench's wire-traffic row)
# ---------------------------------------------------------------------------

_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[0-9,]*\})?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_COMPONENT_RE = re.compile(r"[a-z0-9]+\[[0-9,]*\]")
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[^=]*?\}\}|\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)")
_IOTA_RE = re.compile(r"\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def _parse_groups(text: str) -> list[tuple[int, ...]] | None:
    """``replica_groups`` -> concrete device-id groups, for either HLO
    syntax: explicit ``{{0,1},{2,3}}`` or iota ``[g,s]<=[dims]T(perm)``
    (arange over dims, transposed by perm, reshaped to (g, s))."""
    import numpy as np

    if text.startswith("{"):
        found = re.findall(r"\{([0-9, ]+)\}", text)
        groups = [tuple(int(t) for t in g.split(",") if t.strip())
                  for g in found]
        return groups or None
    m = _IOTA_RE.match(text)
    if not m:
        return None
    g, s = int(m.group(1)), int(m.group(2))
    dims = [int(d) for d in m.group(3).split(",")]
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if m.group(4):
        ids = ids.transpose([int(p) for p in m.group(4).split(",")])
    return [tuple(int(i) for i in row) for row in ids.reshape(g, s)]


def _axes_for_groups(mesh, groups: list[tuple[int, ...]]) -> str:
    """Attribute a collective's device groups to mesh axes EXACTLY: the
    axis combination whose slices of ``mesh.devices`` reproduce the
    groups (so two same-size axes — data=2, model=2 — still attribute
    correctly).  Falls back to group-size matching when the mesh carries
    no concrete devices."""
    import itertools

    import numpy as np

    sizes = mesh_axis_sizes(mesh)
    live = [n for n in mesh.axis_names if sizes[n] > 1]
    actual = {frozenset(g) for g in groups}
    devices = getattr(mesh, "devices", None)
    if devices is not None:
        ids = np.vectorize(lambda d: d.id)(devices)
        names = list(mesh.axis_names)
        for r in range(1, len(live) + 1):
            for combo in itertools.combinations(live, r):
                order = ([names.index(n) for n in names if n not in combo]
                         + [names.index(n) for n in combo])
                k = 1
                for n in combo:
                    k *= sizes[n]
                expected = {frozenset(int(i) for i in row)
                            for row in ids.transpose(order).reshape(-1, k)}
                if expected == actual:
                    return "+".join(combo)
    # size heuristic (abstract meshes / exotic group shapes)
    g = len(next(iter(actual)))
    for r in range(1, len(live) + 1):
        for combo in itertools.combinations(live, r):
            total = 1
            for n in combo:
                total *= sizes[n]
            if total == g:
                return "+".join(combo)
    return f"group{g}"


def collective_bytes_by_axis(hlo_text: str, mesh: Mesh) -> dict[str, int]:
    """Payload bytes of every collective in ``hlo_text``, attributed to
    mesh axes by their concrete ``replica_groups`` device sets.

    Returns ``{axis_name: bytes, ...}`` (an axis that saw no traffic is
    absent); a group spanning several axes lands on a '+'-joined key.
    Collectives inside a scan/while body appear once in the text, so
    the result is per loop ITERATION — callers scale by trip count.
    """
    from repro.launch.hlo_cost import shape_bytes

    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        mg = _GROUPS_RE.search(line)
        groups = _parse_groups(mg.group(1)) if mg else None
        if not groups or len(groups[0]) <= 1:
            continue                      # degenerate single-device group
        axis = _axes_for_groups(mesh, groups)
        shape_text = m.group(1)
        if m.group(3) and shape_text.startswith("("):
            # async op: the tuple is (operand..., result) — only the
            # result component is wire payload, not the held operand
            parts = _SHAPE_COMPONENT_RE.findall(shape_text)
            if parts:
                shape_text = parts[-1]
        out[axis] = out.get(axis, 0) + shape_bytes(shape_text)
    return out


#: logical spec for sequence-parallel residual activations (B, S, d)
SEQ_SHARDED_ACTS = P(BATCH_AXES, "model", None)
