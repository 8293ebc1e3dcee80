"""apexlint layer 2b: semantic jaxpr analyzers APXJ101-APXJ105.

The AST layer sees syntax and the collective-axis check sees axis
*names*; this module sees the *dataflow* of traced programs — the layer
where the bugs that review rounds kept catching by hand actually live.
Each detector encodes one of them:

- **APXJ101 unreduced-output** — a ``shard_map`` output whose out-spec
  replicates a mesh axis the value still *varies* over. Under SPMD every
  rank holds a different value and the "replicated" output silently
  records rank 0's shard (the PR-4 ``out_specs=P()`` bench bug). Found
  by a conservative variance analysis over the body: sharded inputs and
  ``axis_index`` introduce per-axis variance, ``psum``/``pmax``/
  ``pmin``/``all_gather`` remove it, ``psum_scatter``/``all_to_all``
  keep it, everything else propagates the union of its operands.
- **APXJ102 loop-invariant collective under scan** — a collective inside
  a ``scan`` body whose operands derive only from the scan's invariant
  inputs (consts): every iteration reduces the same value, so the
  collective is hoistable and the program pays trip-count times the
  wire cost. The trip count in the message multiplies through nested
  scans exactly like the ``monitor.attribution`` analytic walk.
- **APXJ103 unbalanced ppermute ring** — a ring-decomposed gather or
  scatter (``parallel/overlap.py``'s unrolled collective-matmul hops)
  whose hop count is not a multiple of ``axis_size - 1``: one dropped or
  doubled hop exchanges shards with the wrong neighbours and traces
  clean. Rings are recognised as same-``(axis, perm)`` groups of
  full-cycle-shift ppermutes within one jaxpr; scan bodies are excluded
  (pipeline p2p legitimately sends one carried hop per tick).
- **APXJ104 donated-buffer aliasing** — ``pjit`` donation read from the
  jaxpr truth (``donated_invars``), not the AST heuristic: a donated
  invar that is returned un-updated (the caller's "new" state aliases a
  deleted buffer), has no shape/dtype-matching output to alias (the
  donation can never be used), or is referenced after the equation that
  produces its aliasing write (XLA must insert a copy, defeating the
  donation).
- **APXJ105 large undonated state** — a ``pjit`` with no donations
  threading a state-shaped argument (one with a shape/dtype-matching
  output — batch data has no round trip and stays silent) of at least
  ``tune.vmem.DONATION_BYTES_MIN`` bytes: the undonated round trip
  doubles that much HBM. The ``donate_argnums=()`` conscious opt-out is
  invisible at jaxpr level (it lowers identically to "no donation"), so
  the opt-out path is the per-entrypoint ``disable=`` registration with
  a rationale string (mirroring the APX007 convention).

Findings flow through the exact schema the AST layer uses
(:class:`apex_tpu.lint.core.Finding`): ``path`` is the pseudo-path
``<entrypoint:NAME>``, codes select with ``--select``, and the CLI's
``--baseline`` differential gate treats them like any other finding.
"""

from __future__ import annotations

from typing import Iterable, Optional

from apex_tpu.lint.core import Finding

# codes this module can emit (the CLI catalog lists them from here)
CODES = ("APXJ101", "APXJ102", "APXJ103", "APXJ104", "APXJ105")

_VARIANCE_REMOVING = ("psum", "pmax", "pmin")      # full-axis reductions
_VARIANCE_KEEPING = ("psum_scatter", "reduce_scatter", "all_to_all")
_SCAN_COLLECTIVES = frozenset({
    "psum", "pmax", "pmin", "all_gather", "psum_scatter", "reduce_scatter",
    "all_to_all", "ppermute",
})


def _finding(code: str, label: str, message: str) -> Finding:
    return Finding(code=code, path=label, line=0, col=0, message=message)


def _as_jaxpr(obj):
    # ClosedJaxpr proxies .eqns, so unwrap .jaxpr FIRST — the analyzers
    # need the raw Jaxpr's invars/outvars
    inner = getattr(obj, "jaxpr", None)
    if hasattr(inner, "eqns"):
        return inner
    return obj if hasattr(obj, "eqns") else None


def _sub_jaxprs(eqn):
    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            j = _as_jaxpr(x)
            if j is not None:
                out.append(j)
    return out


def _str_axes(axes) -> tuple:
    """String mesh-axis names out of a psum-style ``axes`` param (which
    may mix positional ints in)."""
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


# ---------------------------------------------------------------------------
# APXJ101 — variance analysis over shard_map bodies
# ---------------------------------------------------------------------------

def _propagate(jaxpr, in_var: list) -> list:
    """Per-outvar variance sets for ``jaxpr`` given per-invar variance
    sets. Variance = the set of mesh axes the value may differ over
    across ranks; the analysis is conservative (may over-report
    variance, never under-reports removal is only credited to full-axis
    reductions)."""
    var: dict = {}

    def get(v):
        if hasattr(v, "val"):                      # Literal
            return frozenset()
        return var.get(v, frozenset())

    for v, s in zip(jaxpr.invars, in_var):
        var[v] = frozenset(s)
    for v in jaxpr.constvars:
        var[v] = frozenset()

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ins = frozenset().union(*[get(v) for v in eqn.invars]) \
            if eqn.invars else frozenset()
        if name in _VARIANCE_REMOVING \
                and eqn.params.get("axis_index_groups") is None:
            out = ins - set(_str_axes(eqn.params.get("axes")))
            outs = [out] * len(eqn.outvars)
        elif name in ("all_gather", "pbroadcast") \
                and eqn.params.get("axis_index_groups") is None:
            out = ins - set(_str_axes(eqn.params.get("axis_name")))
            outs = [out] * len(eqn.outvars)
        elif name in _VARIANCE_KEEPING:
            out = ins | set(_str_axes(eqn.params.get("axis_name")))
            outs = [out] * len(eqn.outvars)
        elif name == "axis_index":
            out = ins | set(_str_axes(eqn.params.get("axis_name")))
            outs = [out] * len(eqn.outvars)
        elif name == "scan":
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            body = _as_jaxpr(eqn.params["jaxpr"])
            op = [get(v) for v in eqn.invars]
            carry = list(op[nc:nc + ncar])
            # fixpoint over the carry: variance sets only grow, so this
            # terminates in at most |axes| iterations
            for _ in range(8):
                res = _propagate(body, op[:nc] + carry + op[nc + ncar:])
                new_carry = [c | r for c, r in zip(carry, res[:ncar])]
                if new_carry == carry:
                    break
                carry = new_carry
            res = _propagate(body, op[:nc] + carry + op[nc + ncar:])
            outs = [c | r for c, r in zip(carry, res[:ncar])] + res[ncar:]
        elif name == "while":
            body = _as_jaxpr(eqn.params["body_jaxpr"])
            nb = eqn.params.get("body_nconsts", 0)
            ncc = eqn.params.get("cond_nconsts", 0)
            op = [get(v) for v in eqn.invars]
            carry = list(op[ncc + nb:])
            for _ in range(8):
                res = _propagate(body, op[ncc:ncc + nb] + carry)
                new_carry = [c | r for c, r in zip(carry, res)]
                if new_carry == carry:
                    break
                carry = new_carry
            outs = carry
        elif name == "cond":
            branches = [_as_jaxpr(b) for b in eqn.params["branches"]]
            pred = get(eqn.invars[0])
            op = [get(v) for v in eqn.invars[1:]]
            outs = None
            for b in branches:
                res = [pred | r for r in _propagate(b, op)]
                outs = res if outs is None else \
                    [a | b_ for a, b_ in zip(outs, res)]
        else:
            subs = _sub_jaxprs(eqn)
            body = next((s for s in subs
                         if len(s.invars) == len(eqn.invars)), None)
            if body is not None and name != "pallas_call":
                op = [get(v) for v in eqn.invars]
                res = _propagate(body, op)
                outs = (res if len(res) == len(eqn.outvars)
                        else [ins] * len(eqn.outvars))
            else:
                outs = [ins] * len(eqn.outvars)
        for v, s in zip(eqn.outvars, outs):
            if type(v).__name__ != "DropVar":
                var[v] = frozenset(s)
    return [get(v) for v in jaxpr.outvars]


def _axes_in_spec(spec) -> set:
    """Mesh axes named by one ``PartitionSpec`` of a ``shard_map``
    equation's ``in_specs`` / ``out_specs`` (entries are None, an axis
    name, or a tuple of names)."""
    out: set = set()
    for axes in spec:
        axes = axes if isinstance(axes, (tuple, list)) else (axes,)
        out.update(a for a in axes if isinstance(a, str))
    return out


def _shard_map_axes(eqn) -> tuple:
    """``(manual axes, per-input axis sets, per-output axis sets)`` of a
    ``shard_map`` equation."""
    manual = set(eqn.params["manual_axes"])
    return (manual,
            [_axes_in_spec(sp) & manual for sp in eqn.params["in_specs"]],
            [_axes_in_spec(sp) for sp in eqn.params["out_specs"]])


def check_unreduced_outputs(closed, *, label: str = "<jaxpr>") -> list:
    """APXJ101 over every shard_map equation reachable from ``closed``."""
    findings: list = []
    for eqn, _ in _walk_eqns(_as_jaxpr(closed)):
        if eqn.primitive.name != "shard_map":
            continue
        body = _as_jaxpr(eqn.params["jaxpr"])
        manual, in_var, out_axes = _shard_map_axes(eqn)
        out_var = _propagate(body, in_var)
        for j, (named, varies) in enumerate(zip(out_axes, out_var)):
            leaked = (varies & manual) - named
            if leaked:
                ax = ", ".join(sorted(leaked))
                findings.append(_finding(
                    "APXJ101", label,
                    f"shard_map output {j} replicates axis {ax} in its "
                    f"out_specs but the value still varies over {ax}: "
                    "under SPMD each rank holds a different value and the "
                    "output silently records rank 0's shard (the "
                    "out_specs=P() bug class) — psum/all_gather it before "
                    "returning, or shard the out_spec"))
    return findings


# ---------------------------------------------------------------------------
# shared walker: every eqn with its (multiplier, in_scan) context
# ---------------------------------------------------------------------------

def _walk_eqns(jaxpr, mult: int = 1, in_scan: bool = False):
    """Yield ``(eqn, ctx)`` for every equation reachable from ``jaxpr``;
    ``ctx`` is ``(trip_multiplier, in_scan_body, owner_jaxpr)``. Scan
    bodies multiply the trip count through, the monitor.attribution
    convention."""
    for eqn in jaxpr.eqns:
        yield eqn, (mult, in_scan, jaxpr)
        if eqn.primitive.name == "scan":
            body = _as_jaxpr(eqn.params["jaxpr"])
            trips = int(eqn.params.get("length", 1))
            yield from _walk_eqns(body, mult * trips, True)
            continue
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub, mult, in_scan)


# ---------------------------------------------------------------------------
# APXJ102 — loop-invariant collectives under scan
# ---------------------------------------------------------------------------

def _invariant_collectives(body, invariant_in: list, mult: int,
                           label: str, findings: Optional[list]) -> list:
    """Scan-body walk: track which vars derive only from loop-invariant
    inputs, flag collectives whose every operand is invariant. Returns
    the per-outvar invariance (so while/cond carries can fixpoint);
    ``findings=None`` computes invariance without emitting (the
    fixpoint pre-passes)."""
    inv: dict = {}
    for v, flag in zip(body.invars, invariant_in):
        inv[v] = flag
    for v in body.constvars:
        inv[v] = True

    def is_inv(v):
        if hasattr(v, "val"):                       # Literal
            return True
        return inv.get(v, False)

    for eqn in body.eqns:
        name = eqn.primitive.name
        all_inv = all(is_inv(v) for v in eqn.invars)
        outs = [all_inv] * len(eqn.outvars)
        if name in _SCAN_COLLECTIVES and all_inv and eqn.invars \
                and findings is not None:
            axes = (_str_axes(eqn.params.get("axes"))
                    or _str_axes(eqn.params.get("axis_name")))
            findings.append(_finding(
                "APXJ102", label,
                f"{name} over {'/'.join(axes) or '?'} inside a scan of "
                f"trip count {mult} is loop-invariant (its operands "
                "derive only from the scan's invariant inputs): every "
                "iteration reduces the same value — hoist the collective "
                f"out of the loop and stop paying {mult}x the wire cost"))
        if name == "scan":
            sub = _as_jaxpr(eqn.params["jaxpr"])
            nc = eqn.params["num_consts"]
            trips = mult * int(eqn.params.get("length", 1))
            sub_inv = ([is_inv(v) for v in eqn.invars[:nc]]
                       + [False] * (len(sub.invars) - nc))
            _invariant_collectives(sub, sub_inv, trips, label, findings)
        elif name == "while":
            # invariance here is w.r.t. the ENCLOSING scan: a while
            # whose consts and init carry are scan-invariant produces
            # the same result every scan trip. The carry needs a
            # fixpoint — a variant const can poison a carry slot only
            # on the second while iteration.
            wbody = _as_jaxpr(eqn.params["body_jaxpr"])
            wcond = _as_jaxpr(eqn.params["cond_jaxpr"])
            ncc = eqn.params.get("cond_nconsts", 0)
            nb = eqn.params.get("body_nconsts", 0)
            op = [is_inv(v) for v in eqn.invars]
            carry = list(op[ncc + nb:])
            for _ in range(8):
                res = _invariant_collectives(
                    wbody, op[ncc:ncc + nb] + carry, mult, label, None)
                new_carry = [c and r for c, r in zip(carry, res)]
                if new_carry == carry:
                    break
                carry = new_carry
            _invariant_collectives(wbody, op[ncc:ncc + nb] + carry,
                                   mult, label, findings)
            _invariant_collectives(wcond, op[:ncc] + carry, mult,
                                   label, findings)
            outs = carry
        elif name == "cond":
            op = [is_inv(v) for v in eqn.invars[1:]]
            branch_outs = None
            for b in eqn.params["branches"]:
                res = _invariant_collectives(_as_jaxpr(b), op, mult,
                                             label, findings)
                branch_outs = res if branch_outs is None else \
                    [a and r for a, r in zip(branch_outs, res)]
            if branch_outs is not None:
                pred_inv = is_inv(eqn.invars[0])
                outs = [pred_inv and r for r in branch_outs]
        else:
            sub = next((s for s in _sub_jaxprs(eqn)
                        if len(s.invars) == len(eqn.invars)), None)
            if sub is not None:
                res = _invariant_collectives(
                    sub, [is_inv(v) for v in eqn.invars], mult, label,
                    findings)
                if len(res) == len(eqn.outvars):
                    outs = res
        for v, flag in zip(eqn.outvars, outs):
            if type(v).__name__ != "DropVar":
                inv[v] = flag
    return [is_inv(v) for v in body.outvars]


def check_scan_collectives(closed, *, label: str = "<jaxpr>") -> list:
    """APXJ102 over every scan reachable from ``closed``."""
    findings: list = []
    for eqn, (mult, _, _) in _walk_eqns(_as_jaxpr(closed)):
        if eqn.primitive.name != "scan":
            continue
        body = _as_jaxpr(eqn.params["jaxpr"])
        nc = eqn.params["num_consts"]
        trips = mult * int(eqn.params.get("length", 1))
        invariant_in = ([True] * nc
                        + [False] * (len(body.invars) - nc))
        _invariant_collectives(body, invariant_in, trips, label, findings)
    return findings


# ---------------------------------------------------------------------------
# APXJ103 — ring-decomposed ppermute balance
# ---------------------------------------------------------------------------

def _is_full_cycle(perm, n: int) -> bool:
    """perm is a single n-cycle over axis indices 0..n-1 (the ring-shift
    shape every decomposed gather/scatter hop uses)."""
    if n < 2 or len(perm) != n:
        return False
    step = dict(perm)
    if set(step) != set(range(n)) or set(step.values()) != set(range(n)):
        return False
    seen, cur = set(), 0
    while cur not in seen:
        seen.add(cur)
        cur = step[cur]
    return len(seen) == n


def check_ppermute_rings(closed, *, label: str = "<jaxpr>",
                         axis_sizes: Optional[dict] = None) -> list:
    """APXJ103: group full-cycle ppermutes by ``(owning jaxpr, axis,
    perm)`` outside scan bodies; a ring-decomposed gather/scatter does
    ``axis_size - 1`` hops per ring, so any group whose count is not a
    multiple of that dropped or doubled a hop. ``axis_sizes`` may name
    sizes explicitly; otherwise they come from the enclosing shard_map
    meshes."""
    sizes = dict(axis_sizes or {})
    top = _as_jaxpr(closed)
    for eqn, _ in _walk_eqns(top):
        if eqn.primitive.name == "shard_map":
            mesh = eqn.params.get("mesh")
            shape = getattr(mesh, "shape", None)
            if shape:
                sizes.update({k: int(v) for k, v in dict(shape).items()})
    groups: dict = {}
    for eqn, (_, in_scan, owner) in _walk_eqns(top):
        if in_scan or eqn.primitive.name != "ppermute":
            continue
        axes = _str_axes(eqn.params.get("axis_name"))
        if len(axes) != 1:
            continue
        axis = axes[0]
        n = sizes.get(axis)
        if n is None or n < 2:
            continue
        perm = tuple(tuple(p) for p in eqn.params.get("perm", ()))
        if not _is_full_cycle(perm, n):
            continue
        groups.setdefault((id(owner), axis, perm, n), []).append(eqn)
    findings = []
    for (_, axis, perm, n), eqns in sorted(
            groups.items(), key=lambda kv: (kv[0][1], kv[0][2])):
        if len(eqns) % (n - 1) != 0:
            findings.append(_finding(
                "APXJ103", label,
                f"{len(eqns)} ring-shift ppermute hop(s) over axis "
                f"'{axis}' (size {n}) in one program body: a "
                f"ring-decomposed gather/scatter does exactly "
                f"{n - 1} hops per ring, so this ring dropped or doubled "
                "a hop — shards will be exchanged with the wrong "
                "neighbours and the program traces clean"))
    return findings


# ---------------------------------------------------------------------------
# APXJ104 / APXJ105 — donation truth from pjit eqns
# ---------------------------------------------------------------------------

def _same_aval(a, b) -> bool:
    aa, ab = getattr(a, "aval", None), getattr(b, "aval", None)
    return (aa is not None and ab is not None
            and getattr(aa, "shape", None) == getattr(ab, "shape", None)
            and getattr(aa, "dtype", None) == getattr(ab, "dtype", None))


def check_donation(closed, *, label: str = "<jaxpr>") -> list:
    """APXJ104 (donated-buffer aliasing) + APXJ105 (large undonated
    state) over every pjit equation reachable from ``closed``."""
    from apex_tpu.tune import vmem

    findings: list = []
    for eqn, (_, _, owner) in _walk_eqns(_as_jaxpr(closed)):
        if eqn.primitive.name != "jit":    # the jax.jit equation (ex-pjit)
            continue
        donated = eqn.params.get("donated_invars")
        if donated is None:
            continue
        body = _as_jaxpr(eqn.params["jaxpr"])
        jit_name = eqn.params.get("name", "<jit>")
        outset = {id(v) for v in body.outvars}
        owner_outs = {id(v) for v in owner.outvars}
        for i, (v, outer_v, don) in enumerate(
                zip(body.invars, eqn.invars, donated)):
            nbytes = vmem.aval_nbytes(getattr(v, "aval", None))
            alias_outs = [o for o in body.outvars if _same_aval(v, o)]
            if don:
                # jax hoists an identity output OUT of the pjit body, so
                # "returned un-updated" shows up as the eqn's operand
                # reappearing in the enclosing jaxpr's outputs (checked
                # first), or — when not hoisted — as the body invar in
                # the body outvars
                if id(outer_v) in owner_outs or id(v) in outset:
                    findings.append(_finding(
                        "APXJ104", label,
                        f"jit '{jit_name}': donated argument {i} is "
                        "returned un-updated — the caller's \"new\" "
                        "value aliases a buffer the donation just "
                        "deleted (real-donation backends hand back "
                        "freed memory; XLA silently copies at best) — "
                        "drop the donation or return the updated value"))
                    continue
                if not alias_outs:
                    findings.append(_finding(
                        "APXJ104", label,
                        f"jit '{jit_name}': donated argument {i} has no "
                        "shape/dtype-matching output to alias — the "
                        "donation can never be used as an in-place "
                        "update and only deletes a buffer the caller "
                        "may still hold"))
                    continue
                # the aliasing write: the eqn producing the first
                # matching outvar. References to the donated invar
                # after it force XLA to copy, defeating the donation.
                writer = None
                for k, e in enumerate(body.eqns):
                    if any(o is alias_outs[0] for o in e.outvars):
                        writer = k
                        break
                if writer is not None:
                    late = [k for k, e in enumerate(body.eqns)
                            if k > writer and any(iv is v
                                                  for iv in e.invars)]
                    if late:
                        findings.append(_finding(
                            "APXJ104", label,
                            f"jit '{jit_name}': donated argument {i} is "
                            "read after the equation that produces its "
                            "aliasing output — XLA must copy the buffer "
                            "to honour the read, silently defeating the "
                            "donation; reorder the reads before the "
                            "update or drop the donation"))
            else:
                if (not any(donated) and alias_outs
                        and nbytes >= vmem.DONATION_BYTES_MIN):
                    findings.append(_finding(
                        "APXJ105", label,
                        f"jit '{jit_name}': argument {i} "
                        f"({nbytes / 2 ** 20:.1f} MiB) round-trips "
                        "through the step (a shape/dtype-matching output "
                        "exists) with no donation anywhere in the jit: "
                        "the input buffer stays alive across the step, "
                        "doubling that much HBM (threshold: "
                        f"tune.vmem.DONATION_BYTES_MIN = "
                        f"{vmem.DONATION_BYTES_MIN / 2 ** 20:.0f} MiB) — "
                        "donate it (the make_train_step(donate=True) "
                        "convention) or register the entrypoint with "
                        "disable=('APXJ105',) and a rationale"))
    return findings


# ---------------------------------------------------------------------------
# the combined analyzer + entrypoint gate
# ---------------------------------------------------------------------------

def all_jaxpr_codes() -> tuple:
    """Every code the traced-jaxpr layer can emit (the analyzer roster
    CI asserts against): the APXJ10x semantic detectors plus the
    divergence (APXJ106-107) and precision (APXP30x) analyzers."""
    from apex_tpu.lint import divergence, precision
    return CODES + divergence.CODES + precision.CODES


def analyze_jaxpr(closed, *, label: str = "<jaxpr>",
                  select: Optional[Iterable[str]] = None) -> list:
    """All APXJ + APXP detectors over one traced program. ``select``
    filters by code (None = all)."""
    from apex_tpu.lint import divergence, precision

    wanted = set(select) if select is not None else None
    findings: list = []
    dispatch = (
        (("APXJ101",), check_unreduced_outputs),
        (("APXJ102",), check_scan_collectives),
        (("APXJ103",), check_ppermute_rings),
        # one walker covers both donation codes
        (("APXJ104", "APXJ105"), check_donation),
        (divergence.CODES, divergence.check_divergent_collectives),
        (precision.CODES, precision.analyze_precision),
    )
    for codes, fn in dispatch:
        if wanted is not None and not (set(codes) & wanted):
            continue
        found = fn(closed, label=label)
        if wanted is not None:
            found = [f for f in found if f.code in wanted]
        findings.extend(found)
    return findings


def run_entrypoint_analyses(names: Optional[Iterable[str]] = None,
                            *, include_axis_check: bool = True) -> dict:
    """Trace each registered entrypoint ONCE and run both jaxpr layers
    over it: the collective-axis consistency check and the APXJ semantic
    detectors. Returns ``{"axis_failures": {name: problem},
    "findings": [Finding], "entrypoints": [names analyzed]}``.

    Per-entrypoint ``disable=`` registrations (with their mandatory
    rationale) filter APXJ findings here — the jaxpr-finding analog of
    the inline ``# apexlint: disable=`` comment.
    """
    import jax

    from apex_tpu.lint import entrypoints as _ep  # noqa: F401 (registers)
    from apex_tpu.lint.jaxpr_checks import (
        ENTRYPOINT_META, ENTRYPOINTS, check_collective_axes)
    from apex_tpu.transformer import parallel_state as ps

    axis_failures: dict = {}
    findings: list = []
    analyzed: list = []
    wanted = set(names) if names is not None else None
    if wanted is not None:
        unknown = wanted - set(ENTRYPOINTS)
        if unknown:
            raise KeyError(
                f"unknown entrypoint(s): {sorted(unknown)}; registered: "
                f"{sorted(ENTRYPOINTS)}")
    saved = (ps._MESH, ps._VIRTUAL_PIPELINE_WORLD_SIZE,
             ps._VIRTUAL_PIPELINE_RANK, ps._PIPELINE_SPLIT_RANK)
    try:
        for name, builder in sorted(ENTRYPOINTS.items()):
            if wanted is not None and name not in wanted:
                continue
            analyzed.append(name)
            label = f"<entrypoint:{name}>"
            try:
                fn, args, allowed = builder()
                closed = jax.make_jaxpr(fn)(*args)
            except Exception as e:   # a broken builder IS a finding
                axis_failures[name] = f"{type(e).__name__}: {e}"
                continue
            if include_axis_check:
                bad = check_collective_axes(closed.jaxpr, allowed)
                if bad:
                    axis_failures[name] = bad
            disabled = ENTRYPOINT_META.get(name, {}).get(
                "disable", frozenset())
            for f in analyze_jaxpr(closed, label=label):
                if f.code not in disabled:
                    findings.append(f)
    finally:
        ps.destroy_model_parallel()
        (ps._MESH, ps._VIRTUAL_PIPELINE_WORLD_SIZE,
         ps._VIRTUAL_PIPELINE_RANK, ps._PIPELINE_SPLIT_RANK) = saved
    return {"axis_failures": axis_failures, "findings": findings,
            "entrypoints": analyzed}
