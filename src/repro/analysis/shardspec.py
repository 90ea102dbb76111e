"""Sharding-contract pass (SC2xx): pspec families and the psum invariant.

Two symbolic checks run against a cell *definition* (no devices needed —
they inspect declared PartitionSpecs, not placements):

  SC201  a spec entry names a mesh axis outside ``dist.sharding.MESH_AXES``
         — it can never resolve on a production mesh, so the constraint
         silently degrades to replicated (``_fit_spec`` drops it).
  SC202  a spec dim entry normalizes to an axis group outside
         ``dist.sharding.AXIS_GROUPS`` — an out-of-contract placement
         (wrong axis order changes the row-major shard index; ad-hoc
         pairings match no wrapper layout).

One structural check runs on the traced jaxpr:

  SC204  a ``shard_map`` consumes an operand sharded over an axis that no
         output keeps, but its body never reduces over that axis — the
         PR 4 bucket-merge invariant. Every ownership-masked device-local
         partial (packed lookup, tiered hot lookup, embedding bag, the
         train step's grads) must be followed by its ``psum``/``pmean``
         over exactly the row axes, or each device returns a partial
         result that the partitioner then treats as replicated (our
         wrappers pass ``check_vma=False``, so jax itself won't catch it).
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_walk import walk
from repro.dist.sharding import AXIS_GROUPS, MESH_AXES, normalize_entry

#: body primitives that reduce (or materialize) over a named mesh axis.
_REDUCING_PRIMS = frozenset({
    "psum", "psum2", "pmean", "pmax", "pmin", "all_gather",
    "reduce_scatter", "all_to_all", "ppermute", "pgather",
})


def _iter_specs(tree):
    """Every PartitionSpec leaf of a (possibly nested) pspec pytree."""
    if isinstance(tree, P):
        yield tree
        return
    for leaf in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, P)):
        if isinstance(leaf, P):
            yield leaf


def check_spec_tree(tree, where: str, *, role: str) -> list[Finding]:
    """SC201/SC202 over one declared pspec pytree (``role``: which input/
    output slot, for the message)."""
    findings = []
    for spec in _iter_specs(tree):
        for entry in tuple(spec):
            norm = normalize_entry(entry)
            if norm is None:
                continue
            unknown = [a for a in norm if a not in MESH_AXES]
            if unknown:
                findings.append(Finding(
                    "SC201", f"{role} spec {spec} names mesh axis "
                    f"{unknown[0]!r} not in the production mesh contract "
                    f"{sorted(MESH_AXES)}", where))
            elif norm not in AXIS_GROUPS:
                findings.append(Finding(
                    "SC202", f"{role} spec {spec} entry {entry!r} is not a "
                    f"registered axis group (dist.sharding.AXIS_GROUPS) — "
                    f"use a pspec family from dist/sharding.py", where))
    return findings


def check_celldef_specs(celldef) -> list[Finding]:
    """SC201/SC202 over every declared spec of a ``ServeCellDef``."""
    where = celldef.name
    findings = []
    for i, ps in enumerate(celldef.bound_pspecs):
        findings += check_spec_tree(ps, where, role=f"bound[{i}]")
    for i, ps in enumerate(celldef.request_pspecs):
        findings += check_spec_tree(ps, where, role=f"request[{i}]")
    findings += check_spec_tree(celldef.out_pspecs, where, role="out")
    return findings


def _specs_axes(specs) -> set:
    """Mesh axes named by a shard_map equation's in_specs/out_specs."""
    axes = set()
    for spec in _iter_specs(specs):
        for entry in tuple(spec):
            axes.update(normalize_entry(entry) or ())
    return axes


def _reduced_axes(jaxpr) -> set:
    """Axes any reducing/collective primitive in ``jaxpr`` (recursively)
    operates over."""
    axes = set()
    for item in walk(jaxpr):
        if item.eqn.primitive.name in _REDUCING_PRIMS:
            for ax in item.eqn.params.get("axes", ()) or ():
                axes.add(ax)
            ax = item.eqn.params.get("axis_name")
            if isinstance(ax, str):
                axes.add(ax)
            elif ax is not None:
                axes.update(ax)
    return axes


def check_shard_map_reductions(closed_jaxpr, where: str) -> list[Finding]:
    """SC204 over every shard_map equation in a traced cell."""
    findings = []
    for item in walk(closed_jaxpr):
        eqn = item.eqn
        if eqn.primitive.name != "shard_map":
            continue
        in_axes = _specs_axes(eqn.params["in_specs"])
        out_axes = _specs_axes(eqn.params["out_specs"])
        missing = in_axes - out_axes
        if not missing:
            continue
        covered = _reduced_axes(eqn.params["jaxpr"])
        unreduced = sorted(missing - covered)
        if unreduced:
            findings.append(Finding(
                "SC204", f"shard_map consumes operands sharded over "
                f"{unreduced} but no output keeps the axis and the body "
                f"never psums over it — each device returns an unmerged "
                f"partial (the bucket-merge invariant)",
                where, file=item.file, line=item.line))
    return findings
