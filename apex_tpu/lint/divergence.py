"""apexlint layer 2d: cross-rank divergence analyzers APXJ106-APXJ107.

The pipeline scheduler's deadlock contract ("no pipeline-axis
collectives under the single-rank embed/head conds",
``transformer/pipeline_parallel/schedules.py``) is enforced at runtime
by ``debug_axis_probe`` — a trace-time probe that only fires when the
debug flag is on. This module is the *static* form, over any traced
program: track which values are rank-dependent per mesh axis (derived
from ``axis_index``, sharded ``shard_map`` inputs, or values computed
from them), and flag collectives executed under control flow whose
predicate diverges on the collective's own axis.

Why that exact rule: a collective is a *group program* — every rank in
the axis group must reach the same collective call site (channel) or
the group hangs. A ``cond`` predicate that varies over axis ``a`` sends
different ``a``-peers down different branches; any collective over
``a`` inside either branch is then entered by only part of its group.
Matching collectives across branches does NOT save you — two call
sites are two channels. A predicate that is *uniform* over the
collective's axes is fine, however many other axes it varies over:
that is exactly why the pipeline embed/head single-rank conds (pred
varies over ``pipeline`` only) may contain tensor-axis collectives
(VocabParallelEmbedding psums) — the known-hard true negatives.

- **APXJ106 collective under divergent control flow** — a collective
  primitive (``psum``/``ppermute``/``all_gather``/...) whose axis set
  intersects the accumulated divergence context: the union of the
  rank-variance of every enclosing ``cond`` predicate and ``while``
  loop condition. Static deadlock: part of the axis group enters the
  collective, the rest never arrives.
- **APXJ107 branch collective-axis mismatch** — a rank-divergent
  ``cond`` where two or more branches each contain collectives but
  over *different* axis sets (after excluding the axes APXJ106 already
  covers). Each branch is group-complete, so nothing hangs — but
  different rank rows now run different collective programs (e.g. a
  gradient sync that only some data rows perform), a rank-dependent
  program mismatch XLA cannot diagnose. One-sided communication
  (collectives in one branch, none in the other) is the guarded-
  collective idiom the pipeline head uses and is deliberately exempt —
  it is judged against the predicate's own axes by APXJ106.

Findings use the standard schema with the ``<entrypoint:NAME>``
pseudo-path; per-entrypoint ``disable=`` + rationale opt-outs apply.
"""

from __future__ import annotations

from apex_tpu.lint.core import Finding
from apex_tpu.lint.jaxpr_checks import (_COLLECTIVE_AXIS_PARAMS,
                                        collective_axis_names)
from apex_tpu.lint.semantic import (_as_jaxpr, _shard_map_axes, _str_axes,
                                    _sub_jaxprs, _VARIANCE_KEEPING,
                                    _VARIANCE_REMOVING)

CODES = ("APXJ106", "APXJ107")


def _finding(code: str, label: str, message: str) -> Finding:
    return Finding(code=code, path=label, line=0, col=0, message=message)


class _State:
    def __init__(self, label: str):
        self.label = label
        self.findings: list = []
        self.seen: set = set()     # (code, id(eqn)) dedupe across re-visits
        self.quiet = 0             # >0 during carry-fixpoint pre-passes

    def emit(self, code: str, eqn, message: str):
        if self.quiet:
            return
        key = (code, id(eqn))
        if key in self.seen:
            return
        self.seen.add(key)
        self.findings.append(_finding(code, self.label, message))


def _collective_axes(eqn):
    key = _COLLECTIVE_AXIS_PARAMS.get(eqn.primitive.name)
    if key is None:
        return None
    return set(_str_axes(eqn.params.get(key)))


def _interp(jaxpr, in_var: list, ctx: frozenset, st: _State) -> list:
    """Variance propagation (same lattice as ``semantic._propagate``)
    plus finding emission; ``ctx`` is the set of mesh axes the enclosing
    control-flow predicates diverge on."""
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

        coll = _collective_axes(eqn)
        if coll is not None:
            bad = coll & ctx
            if bad:
                ax = ", ".join(sorted(bad))
                st.emit(
                    "APXJ106", eqn,
                    f"collective {name} over axis {ax} runs under "
                    f"control flow whose predicate diverges over {ax}: "
                    "different ranks of that axis group take different "
                    "branches, so only part of the group reaches this "
                    "collective and it deadlocks (the pipeline embed/"
                    "head contract, statically); hoist the collective "
                    "out of the branch, or restrict the branch body to "
                    "axes the predicate is uniform over")

        if name in _VARIANCE_REMOVING \
                and eqn.params.get("axis_index_groups") is None:
            out = ins - set(_str_axes(eqn.params.get("axes")))
            outs = [out] * len(eqn.outvars)
        elif name in ("all_gather", "pbroadcast") \
                and eqn.params.get("axis_index_groups") is None:
            out = ins - set(_str_axes(eqn.params.get("axis_name")))
            outs = [out] * len(eqn.outvars)
        elif name in _VARIANCE_KEEPING or name == "axis_index":
            out = ins | set(_str_axes(eqn.params.get("axis_name")))
            outs = [out] * len(eqn.outvars)
        elif name == "scan":
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            body = _as_jaxpr(eqn.params["jaxpr"])
            op = [get(v) for v in eqn.invars]
            carry = list(op[nc:nc + ncar])
            st.quiet += 1
            for _ in range(8):
                res = _interp(body, op[:nc] + carry + op[nc + ncar:],
                              ctx, st)
                new_carry = [c | r for c, r in zip(carry, res[:ncar])]
                if new_carry == carry:
                    break
                carry = new_carry
            st.quiet -= 1
            res = _interp(body, op[:nc] + carry + op[nc + ncar:], ctx, st)
            outs = [c | r for c, r in zip(carry, res[:ncar])] + res[ncar:]
        elif name == "while":
            body = _as_jaxpr(eqn.params["body_jaxpr"])
            cond_j = _as_jaxpr(eqn.params["cond_jaxpr"])
            nb = eqn.params.get("body_nconsts", 0)
            ncc = eqn.params.get("cond_nconsts", 0)
            op = [get(v) for v in eqn.invars]
            carry = list(op[ncc + nb:])
            st.quiet += 1
            for _ in range(8):
                res = _interp(body, op[ncc:ncc + nb] + carry, ctx, st)
                new_carry = [c | r for c, r in zip(carry, res)]
                if new_carry == carry:
                    break
                carry = new_carry
            pred_var = _interp(cond_j, op[:ncc] + carry, ctx, st)[0]
            st.quiet -= 1
            # a rank-divergent loop condition means divergent trip
            # counts: every body/cond collective over those axes hangs
            _interp(cond_j, op[:ncc] + carry, ctx | pred_var, st)
            _interp(body, op[ncc:ncc + nb] + carry, ctx | pred_var, st)
            outs = [c | pred_var for c in carry]
        elif name == "cond":
            branches = [_as_jaxpr(b) for b in eqn.params["branches"]]
            pred = get(eqn.invars[0])
            op = [get(v) for v in eqn.invars[1:]]
            div = ctx | pred
            if div and not st.quiet:
                per_branch = [collective_axis_names(b) - div
                              for b in branches]
                nonempty = [frozenset(s) for s in per_branch if s]
                if len(nonempty) >= 2 and len(set(nonempty)) > 1:
                    desc = "; ".join(
                        f"branch {i}: {{{', '.join(sorted(s)) or '-'}}}"
                        for i, s in enumerate(per_branch))
                    st.emit(
                        "APXJ107", eqn,
                        "branches of a rank-divergent cond communicate "
                        f"over different axis sets ({desc}): each "
                        "branch is group-complete so nothing hangs, "
                        "but ranks that disagree on the predicate now "
                        "run different collective programs — a rank-"
                        "dependent program mismatch XLA cannot "
                        "diagnose; make the branches collective-"
                        "identical or hoist the collectives out")
            outs = None
            for b in branches:
                res = [pred | r for r in _interp(b, op, div, st)]
                outs = res if outs is None else \
                    [a | b_ for a, b_ in zip(outs, res)]
        elif name == "shard_map":
            body = _as_jaxpr(eqn.params["jaxpr"])
            manual, b_in, out_axes = _shard_map_axes(eqn)
            _interp(body, b_in, ctx, st)
            outs = [axes & manual for axes in out_axes]
        else:
            subs = _sub_jaxprs(eqn)
            body = next((s for s in subs
                         if len(s.invars) == len(eqn.invars)), None)
            if body is not None and name != "pallas_call":
                res = _interp(body, [get(v) for v in eqn.invars], ctx, st)
                outs = (res if len(res) == len(eqn.outvars)
                        else [ins] * len(eqn.outvars))
            else:
                outs = [ins] * len(eqn.outvars)
        for v, s in zip(eqn.outvars, outs):
            if type(v).__name__ != "DropVar":
                var[v] = frozenset(s)
    return [get(v) for v in jaxpr.outvars]


def check_divergent_collectives(closed, *, label: str = "<jaxpr>") -> list:
    """APXJ106 + APXJ107 over one traced program. Top-level inputs are
    replicated (rank-variance enters via ``shard_map`` in_specs and
    ``axis_index``), matching ``semantic.check_unreduced_outputs``."""
    jaxpr = _as_jaxpr(closed)
    st = _State(label)
    _interp(jaxpr, [frozenset() for _ in jaxpr.invars], frozenset(), st)
    return st.findings
