"""fence — structural verification of the measured-region sandwich.

Sandwich invariant 1 (no engine's measured work can begin before the
start barrier) as a jaxpr dataflow check, plus — for width-packed
dispatches — per-subset isolation: every psum sandwich must be grouped
exactly along the declared engine subsets, and no collective may move
data across a subset boundary.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax


def measured_region_is_fenced(fn, *example_args, jaxpr=None,
                              subsets: Optional[Sequence[Sequence[int]]]
                              = None) -> bool:
    """Does the measured output depend — through DATAFLOW, not just
    program order — on the start-barrier psum?

    Walks the traced jaxpr: inside every ``shard_map`` body, takes the
    first psum equation (the start barrier), computes the forward
    dataflow closure of its outputs, and requires (a) the body's first
    output (the measured activity result) to lie inside that closure,
    and (b) every ``pallas_call`` reachable after the barrier —
    recursing through switch branches and loop bodies — to consume at
    least one operand inside the closure.  (b) extends the check past
    the ``pallas_call`` boundary: a kernel is the *actual* memory
    traffic of a Pallas rung activity, and one fed only by constants
    (e.g. a no-operand write stream) could be hoisted above the
    barrier even though the switch output downstream of it still
    "depends" on the fence.  A program whose barrier is advisory only
    — the pre-fix ``build_scenario_program``, where ``out`` had no
    data dependency on ``ready`` — returns False: XLA was free to
    begin the measured activity before the stressors were running.

    Fused whole-ladder programs (``build_ladder_program``) carry
    their psum sandwiches INSIDE a ``lax.scan``: there the check
    recurses into every psum-bearing scan/while body and requires the
    step itself to pass — the step's first output is the loop carry,
    which by construction value-consumes the stop barrier and stamp,
    so verifying the body verifies EVERY scanned rung sample (one body
    serves all steps structurally) — including every ladder of a
    sweep-batched stacked program, whose scan table merely gains a
    leading scenario axis.

    ``subsets`` declares a width-packed program's disjoint engine
    subsets (e.g. ``((0, 1), (2, 3))``); when given, each subset's
    fence is verified INDEPENDENTLY: every psum inside the measured
    region must carry ``axis_index_groups`` in which each declared
    subset appears as exactly one group (its own sandwich) and every
    other group is disjoint from all subsets (leftover engines may
    barrier among themselves), and no other collective may move data
    across a subset boundary.  A global psum, a group spanning two
    subsets, a group splitting one subset, or a cross-subset
    ``ppermute`` all make the packed measurement unattributable to one
    mesh slice — each returns False.

    Pass ``jaxpr=`` (a ClosedJaxpr, e.g. from
    ``jit(fn).trace(*args).jaxpr``) to reuse an existing trace
    instead of paying a second one here."""
    closed = jaxpr if jaxpr is not None \
        else jax.make_jaxpr(fn)(*example_args)
    bodies = _shard_map_bodies(closed.jaxpr)
    if not bodies:
        return False
    if not all(_first_out_depends_on_psum(b) for b in bodies):
        return False
    if subsets:
        decl = tuple(tuple(int(i) for i in s) for s in subsets)
        return all(_collectives_respect_subsets(b, decl)
                   for b in bodies)
    return True


def _sub_jaxprs(params: Dict[str, Any]):
    for v in params.values():
        for u in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(u, "jaxpr", u)
            if hasattr(inner, "eqns"):
                yield inner


def _shard_map_bodies(jaxpr) -> List[Any]:
    out = []
    for eqn in jaxpr.eqns:
        for inner in _sub_jaxprs(eqn.params):
            if "shard_map" in eqn.primitive.name:
                out.append(inner)
            else:
                out.extend(_shard_map_bodies(inner))
    return out


def _jaxpr_has_psum(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        if "psum" in eqn.primitive.name:
            return True
        for inner in _sub_jaxprs(eqn.params):
            if _jaxpr_has_psum(inner):
                return True
    return False


def _first_out_depends_on_psum(body) -> bool:
    live: set = set()
    seen_psum = False
    kernels_ok = True
    for eqn in body.eqns:
        invars = [v for v in eqn.invars if not hasattr(v, "val")]
        if not seen_psum and "psum" in eqn.primitive.name:
            seen_psum = True
            live.update(eqn.outvars)
            continue
        if not seen_psum and eqn.primitive.name in ("scan", "while"):
            inners = [j for j in _sub_jaxprs(eqn.params)
                      if _jaxpr_has_psum(j)]
            if inners:
                # a scanned/looped sandwich (the fused whole-ladder
                # program): every step must pass the same check — its
                # first output is the loop carry, which must consume
                # the step's own stop barrier, and every kernel inside
                # the step must consume fence-dependent operands.  One
                # body serves all steps, so this verifies every rung.
                if all(_first_out_depends_on_psum(j) for j in inners):
                    seen_psum = True
                    live.update(eqn.outvars)
                else:
                    kernels_ok = False
                continue
        if seen_psum:
            kernels_ok = kernels_ok and _kernels_fenced_in_eqn(eqn, live)
            if any(v in live for v in invars):
                live.update(eqn.outvars)
    out0 = body.outvars[0]
    return out0 in live and kernels_ok


def _is_live(v, live) -> bool:
    return not hasattr(v, "val") and v in live


def _kernels_fenced_in_eqn(eqn, live) -> bool:
    """Fence-reachability of the kernels *inside* one equation: a
    ``pallas_call`` must consume at least one fence-dependent operand;
    any other equation recurses into its sub-jaxprs (switch/cond
    branches, while/scan loop bodies, inner pjit calls) with the live
    set mapped onto the inner binders.  The mapping aligns outer
    operands to inner invars from the END — exact for pjit/scan, and
    for cond/switch (whose leading index operand has no binder) and
    while bodies (whose leading cond-consts belong to the other
    jaxpr) it aligns the carried values correctly, which is where the
    fenced operands live."""
    if "pallas_call" in eqn.primitive.name:
        return any(_is_live(v, live) for v in eqn.invars)
    ok = True
    for inner in _sub_jaxprs(eqn.params):
        inner_live = {iv for iv, ov in zip(reversed(inner.invars),
                                           reversed(eqn.invars))
                      if _is_live(ov, live)}
        ok = ok and _kernels_fenced_in_jaxpr(inner, inner_live)
    return ok


def _kernels_fenced_in_jaxpr(jaxpr, live) -> bool:
    live = set(live)
    ok = True
    for eqn in jaxpr.eqns:
        ok = ok and _kernels_fenced_in_eqn(eqn, live)
        if any(_is_live(v, live) for v in eqn.invars):
            live.update(eqn.outvars)
    return ok


# ---------------------------------------------------------------------------
# Packed-subset isolation
# ---------------------------------------------------------------------------

# cross-engine data-movement primitives whose grouping must respect the
# declared subsets (matched by substring against primitive names, which
# drift across jax versions: psum / psum_invariant / all_gather ...)
_GROUPED_COLLECTIVES = ("psum", "pmax", "pmin", "pmean", "all_gather",
                        "all_to_all", "reduce_scatter")


def _subset_of(idx: int, subsets) -> Optional[int]:
    for j, s in enumerate(subsets):
        if idx in s:
            return j
    return None            # leftover engine (idles outside all subsets)


def _psum_groups_isolate(groups, subsets) -> bool:
    """A fence psum isolates the declared subsets iff each subset is
    exactly one of its groups (every subset gets its OWN sandwich —
    neither merged with a sibling nor split in half) and every other
    group is disjoint from all subsets (leftover engines barriering
    among themselves are harmless)."""
    if groups is None:
        return len(subsets) <= 1
    declared = set(subsets)
    gset = {tuple(int(i) for i in g) for g in groups}
    if not declared <= gset:
        return False
    members = {i for s in subsets for i in s}
    return all(not (set(g) & members) for g in gset - declared)


def _gather_groups_isolate(groups, subsets) -> bool:
    """Non-barrier collectives (gathers, all-to-alls) leak operand data
    between their group's members, so each group must stay WITHIN one
    subset (or within the leftover engines) — weaker than the psum
    rule, which additionally demands a sandwich per subset."""
    if groups is None:
        return len(subsets) <= 1
    for g in groups:
        owners = {_subset_of(int(i), subsets) for i in g}
        if len(owners) > 1:
            return False
    return True


def _eqn_respects_subsets(eqn, subsets) -> bool:
    name = eqn.primitive.name
    if "ppermute" in name:
        perm = eqn.params.get("perm") or ()
        return all(_subset_of(int(s), subsets)
                   == _subset_of(int(d), subsets) for s, d in perm)
    if any(c in name for c in _GROUPED_COLLECTIVES):
        groups = eqn.params.get("axis_index_groups")
        if "psum" in name:
            return _psum_groups_isolate(groups, subsets)
        return _gather_groups_isolate(groups, subsets)
    return True


def _collectives_respect_subsets(jaxpr, subsets) -> bool:
    for eqn in jaxpr.eqns:
        if not _eqn_respects_subsets(eqn, subsets):
            return False
        for inner in _sub_jaxprs(eqn.params):
            if not _collectives_respect_subsets(inner, subsets):
                return False
    return True
