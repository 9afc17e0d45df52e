"""CONGEST auditor: jaxpr-level wire-budget verification for the engines.

The paper's efficiency theorems are statements about per-round wire: in
CONGEST every edge carries B = polylog(n) bits per round, and Lemma 1 is
what makes the walk phases fit — counts of anonymous walks are exchanged,
so the payload is bounded by *distinct vertices*, never by the walk
multiplicity W. The engines encode that bound in their lane sizing; this
module machine-checks it against the programs the runtime actually
executes, with no instrumentation of the hot path:

  1. Each engine's `audit_spec(graph, mesh)` rebuilds its jitted stage
     programs through the SAME memoized step makers (identical static
     arguments => identical cache keys => the traced jaxpr IS the runtime
     program) and declares one `ExchangeSite` per expected all_to_all.
  2. `trace_program` closes each program over ShapeDtypeStructs and
     `collect_collectives` walks the jaxpr — recursing through pjit /
     shard_map / scan / while / cond sub-jaxprs — to find every
     collective with its per-shard payload bytes (inside shard_map the
     avals are already per-shard) and loop trip multiplier.
  3. The budget checks: every traced all_to_all matches a declared site,
     runs exactly once per program call (no collective hiding in a loop),
     moves exactly `lane_entries * entry_nbytes` bytes, and its lane count
     fits the declared W-free budget. psums are control-plane and must
     stay under `PSUM_CONTROL_BYTES`; ppermute / all_gather are not used
     by any engine and tracing one is a violation outright.
  4. W-independence: the spec is rebuilt at 2x the walk multiplicity and
     every matched site must declare the identical budget (walk-class
     lanes are auditor-pinned at n_loc, so their checked capacity is
     W-free too).
  5. Telemetry cross-check: each engine runs on a fixture graph and its
     runtime byte counters must equal its runtime entry counters times
     the declared per-entry width — the static widths and the
     `entry_nbytes`-derived runtime accounting agree exactly.

The lint passes (`analysis.lint`: RNG-key discipline + elastic-resume
classification, int->float count funnels, elastic-schema completeness)
run over the same traces, so one trace per program serves every check.
`scripts/audit_engines.py` and `launch --audit` drive `audit_all_engines`
and render `format_wire_table` / AUDIT.json; CI gates on zero violations.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis.lint import (LintFinding, classify_resume, dtype_lint,
                                 iter_subjaxprs, rng_lint, schema_lint)
from repro.core.accounting import EngineAuditSpec, StageProgram

__all__ = [
    "PSUM_CONTROL_BYTES", "CollectiveSite", "AuditViolation",
    "trace_program", "collect_collectives", "audit_program",
    "audit_engine_spec", "check_w_independence", "audit_all_engines",
    "format_wire_table",
]

# psums move O(1) scalars / tiny per-bucket vectors of control state
# (active counters, conservation tripwires, occupancy) — bounded by a
# constant, not by n or W.
PSUM_CONTROL_BYTES = 256

_A2A_PRIMS = frozenset({"all_to_all"})
_CONTROL_PRIMS = frozenset({"psum"})
_UNEXPECTED_PRIMS = frozenset({"ppermute", "all_gather"})
_ALL_PRIMS = _A2A_PRIMS | _CONTROL_PRIMS | _UNEXPECTED_PRIMS


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One collective equation found in a traced program."""

    prim: str            # all_to_all | psum | ppermute | all_gather
    path: str            # jaxpr path, e.g. "pjit/shard_map/all_to_all"
    payload_bytes: int   # per-shard operand bytes (avals inside shard_map
                         # are per-shard already)
    trip_mult: int       # product of enclosing loop trip counts (scan
                         # length; 0 under a while body)


@dataclasses.dataclass(frozen=True)
class AuditViolation:
    engine: str
    kind: str            # "budget/..." | "lint/rng" | "lint/dtype" | ...
    where: str           # "stage/program" (or stage for schema findings)
    message: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _aval_nbytes(aval: Any) -> int:
    try:
        size = int(np.prod(aval.shape, dtype=np.int64)) if aval.shape else 1
        return size * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def trace_program(fn: Any, example_args: Tuple[Any, ...]) -> Any:
    """Close a jitted stage program over its example ShapeDtypeStructs."""
    return jax.make_jaxpr(fn)(*example_args)


def collect_collectives(jaxpr: Any, path: str = "", mult: int = 1,
                        out: Optional[List[CollectiveSite]] = None
                        ) -> List[CollectiveSite]:
    """Every collective equation reachable from `jaxpr`, in program order,
    recursing through pjit / shard_map / scan / while / cond."""
    if out is None:
        out = []
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in _ALL_PRIMS:
            payload = sum(_aval_nbytes(v.aval) for v in eqn.invars
                          if hasattr(v, "aval"))
            out.append(CollectiveSite(prim=prim, path=f"{path}{prim}",
                                      payload_bytes=payload, trip_mult=mult))
            continue
        for inner, _, m in iter_subjaxprs(eqn):
            collect_collectives(inner, f"{path}{prim}/", mult * m, out)
    return out


def audit_program(prog: StageProgram, engine: str
                  ) -> Tuple[Any, List[CollectiveSite], List[AuditViolation]]:
    """Trace one stage program and run the wire-budget checks against its
    declared `ExchangeSite`s. Returns (closed_jaxpr, collectives, violations)
    so the lint passes can reuse the trace."""
    where = f"{prog.stage}/{prog.program}"
    cj = trace_program(prog.fn, prog.example_args)
    colls = collect_collectives(cj.jaxpr)
    violations: List[AuditViolation] = []

    a2a = [c for c in colls if c.prim in _A2A_PRIMS]
    if len(a2a) != len(prog.sites):
        violations.append(AuditViolation(
            engine=engine, kind="budget/site-count", where=where,
            message=(f"traced {len(a2a)} all_to_all launches but "
                     f"{len(prog.sites)} declared "
                     f"({[s.site for s in prog.sites]})")))
    for c, site in zip(a2a, prog.sites):
        if c.trip_mult != 1:
            violations.append(AuditViolation(
                engine=engine, kind="budget/loop", where=where,
                message=(f"site '{site.site}' ({c.path}) executes with loop "
                         f"multiplier {c.trip_mult} — a per-round budget "
                         f"only bounds a collective that runs once per "
                         f"program call")))
        expected = site.lane_entries * site.entry_nbytes
        if c.payload_bytes != expected:
            violations.append(AuditViolation(
                engine=engine, kind="budget/payload", where=where,
                message=(f"site '{site.site}' compiled payload is "
                         f"{c.payload_bytes} B but the declaration says "
                         f"{site.lane_entries} lanes x {site.entry_nbytes} B "
                         f"= {expected} B")))
        if site.lane_entries > site.budget_entries:
            violations.append(AuditViolation(
                engine=engine, kind="budget/exceeded", where=where,
                message=(f"site '{site.site}' lane capacity "
                         f"{site.lane_entries} exceeds its W-free budget "
                         f"{site.budget_entries} ({site.budget_formula})")))
    for c in colls:
        if c.prim in _CONTROL_PRIMS and c.payload_bytes > PSUM_CONTROL_BYTES:
            violations.append(AuditViolation(
                engine=engine, kind="budget/psum", where=where,
                message=(f"{c.path} moves {c.payload_bytes} B — control "
                         f"psums must stay under {PSUM_CONTROL_BYTES} B "
                         f"(data belongs on the counted all_to_all wire)")))
        elif c.prim in _UNEXPECTED_PRIMS:
            violations.append(AuditViolation(
                engine=engine, kind="budget/unexpected-collective",
                where=where,
                message=(f"{c.path}: no engine declares {c.prim} — all data "
                         f"motion must go through declared all_to_all "
                         f"sites")))
    return cj, colls, violations


def _lint_to_violation(engine: str, f: LintFinding) -> AuditViolation:
    return AuditViolation(engine=engine, kind=f"lint/{f.lint}",
                          where=f.where, message=f.message)


def audit_engine_spec(spec: EngineAuditSpec) -> Dict[str, Any]:
    """Full static audit of one engine: budget checks + lints + resume
    classification, from a single trace of each stage program."""
    violations: List[AuditViolation] = []
    notes: List[dict] = []
    site_rows: List[dict] = []
    rng_by_stage: Dict[str, int] = {}
    psum_sites = 0
    psum_max = 0

    for prog in spec.programs:
        where = f"{prog.stage}/{prog.program}"
        cj, colls, vs = audit_program(prog, spec.engine)
        violations.extend(vs)

        rng_findings, consumed = rng_lint(cj, where=where)
        violations.extend(_lint_to_violation(spec.engine, f)
                          for f in rng_findings)
        rng_by_stage[prog.stage] = rng_by_stage.get(prog.stage, 0) + consumed
        for f in dtype_lint(cj, count_bound=prog.count_bound, where=where):
            if f.severity == "violation":
                violations.append(_lint_to_violation(spec.engine, f))
            else:
                notes.append(f.to_dict())

        a2a = [c for c in colls if c.prim in _A2A_PRIMS]
        for c, site in zip(a2a, prog.sites):
            site_rows.append(dict(
                stage=prog.stage, program=prog.program, site=site.site,
                entry_nbytes=site.entry_nbytes,
                lane_entries=site.lane_entries,
                budget_entries=site.budget_entries,
                capacity_bytes=site.capacity_bytes,
                budget_bytes=site.budget_bytes,
                traced_payload_bytes=c.payload_bytes,
                wire_class=site.wire_class,
                budget_formula=site.budget_formula, note=site.note))
        for c in colls:
            if c.prim in _CONTROL_PRIMS:
                psum_sites += 1
                psum_max = max(psum_max, c.payload_bytes)

    violations.extend(_lint_to_violation(spec.engine, f)
                      for f in schema_lint(spec.stage_arrays, spec.layouts))

    resume: Dict[str, str] = {}
    for stage in spec.stage_arrays:
        cls, findings = classify_resume(stage, rng_by_stage.get(stage, 0),
                                        spec.layouts.get(stage, {}))
        resume[stage] = cls
        violations.extend(_lint_to_violation(spec.engine, f)
                          for f in findings)

    return dict(
        engine=spec.engine, sites=site_rows,
        psum_sites=psum_sites, psum_max_bytes=psum_max,
        rng_consumed_by_stage=rng_by_stage, resume=resume, notes=notes,
        violations=[v.to_dict() for v in violations],
        meta={k: (int(v) if isinstance(v, (np.integer,)) else v)
              for k, v in spec.meta.items()})


def check_w_independence(spec_lo: EngineAuditSpec, spec_hi: EngineAuditSpec
                         ) -> List[AuditViolation]:
    """Rebuild the spec at double the walk multiplicity: every matched site
    must declare the identical W-free budget (lane capacities may grow
    toward the budget — e.g. the phase-1 reply lane saturates at
    n_loc*(max_deg+1) — but must stay within it at both multiplicities)."""
    violations: List[AuditViolation] = []
    lo = [(p.stage, p.program, s) for p in spec_lo.programs for s in p.sites]
    hi = [(p.stage, p.program, s) for p in spec_hi.programs for s in p.sites]
    if [(st, pr, s.site) for st, pr, s in lo] != \
       [(st, pr, s.site) for st, pr, s in hi]:
        violations.append(AuditViolation(
            engine=spec_lo.engine, kind="budget/w-dependence", where="*",
            message="site list changes with walk multiplicity"))
        return violations
    for (stage, program, a), (_, _, b) in zip(lo, hi):
        where = f"{stage}/{program}"
        if (a.entry_nbytes, a.budget_entries, a.budget_formula,
                a.wire_class) != (b.entry_nbytes, b.budget_entries,
                                  b.budget_formula, b.wire_class):
            violations.append(AuditViolation(
                engine=spec_lo.engine, kind="budget/w-dependence",
                where=where,
                message=(f"site '{a.site}' budget changes with walk "
                         f"multiplicity: {a.budget_entries} x "
                         f"{a.entry_nbytes} B -> {b.budget_entries} x "
                         f"{b.entry_nbytes} B — budgets must depend on the "
                         f"partition and polylog(n) only, never on W")))
        if b.lane_entries > b.budget_entries:
            violations.append(AuditViolation(
                engine=spec_lo.engine, kind="budget/w-dependence",
                where=where,
                message=(f"site '{a.site}' lane capacity grows past its "
                         f"budget at 2x walks: {b.lane_entries} > "
                         f"{b.budget_entries}")))
    return violations


# ---------------------------------------------------------------------------
# runtime telemetry cross-check — static widths vs entry_nbytes counters
# ---------------------------------------------------------------------------

def _check(name: str, runtime_bytes: int, entries: int, width: int) -> dict:
    return dict(name=name, runtime_bytes=int(runtime_bytes),
                entries=int(entries), entry_nbytes=int(width),
                expected_bytes=int(entries) * int(width),
                ok=int(runtime_bytes) == int(entries) * int(width))


def _site_widths(spec: EngineAuditSpec) -> Dict[str, int]:
    return {s.site: s.entry_nbytes for p in spec.programs for s in p.sites}


def _telemetry_walks(graph, mesh, spec, eps, K):
    from repro.core.distributed import distributed_pagerank
    res = distributed_pagerank(graph, eps, walks_per_node=K,
                               key=jax.random.PRNGKey(0), mesh=mesh)
    w = _site_widths(spec)["route"]
    return [_check("route", res.a2a_bytes_total, res.a2a_entries_total, w)]


def _telemetry_counts(graph, mesh, spec, eps, K):
    from repro.core.distributed_counts import distributed_pagerank_counts
    res = distributed_pagerank_counts(graph, eps, walks_per_node=K,
                                      key=jax.random.PRNGKey(0), mesh=mesh)
    w = _site_widths(spec)["counts"]
    return [_check("counts", res.a2a_bytes_total, res.a2a_entries_total, w)]


def _telemetry_three_phase(graph, mesh, spec, eps, K, *, directed: bool):
    if directed:
        from repro.core.distributed_directed import \
            distributed_directed_pagerank as run
    else:
        from repro.core.distributed_improved import \
            distributed_improved_pagerank as run
    res = run(graph, eps, walks_per_node=K, key=jax.random.PRNGKey(0),
              mesh=mesh)
    w = _site_widths(spec)
    w.setdefault("phase1_rep", 0)   # one shard replies without a wire
    wire, ent = res.a2a_bytes_by_phase, res.a2a_entries_by_site
    checks = [
        dict(name="phase1", runtime_bytes=int(wire.get("phase1", 0)),
             entries=int(ent.get("phase1_req", 0) + ent.get("phase1_rep", 0)),
             entry_nbytes=0,
             expected_bytes=(ent.get("phase1_req", 0) * w["phase1_req"]
                             + ent.get("phase1_rep", 0) * w["phase1_rep"]),
             ok=int(wire.get("phase1", 0)) ==
                (ent.get("phase1_req", 0) * w["phase1_req"]
                 + ent.get("phase1_rep", 0) * w["phase1_rep"])),
        _check("phase2", wire.get("phase2", 0), ent.get("phase2", 0),
               w["phase2"]),
        _check("phase3", wire.get("phase3", 0), ent.get("phase3", 0),
               w["phase3"]),
        _check("tail", wire.get("tail", 0), ent.get("tail", 0), w["tail"]),
        dict(name="report", runtime_bytes=int(wire.get("report", 0)),
             entries=0, entry_nbytes=0, expected_bytes=0,
             ok=int(wire.get("report", 0)) == 0),
    ]
    return checks


def _telemetry_ppr(graph, mesh, spec, eps, K):
    from repro.core.personalized_batch import batched_personalized_pagerank
    res = batched_personalized_pagerank(
        graph, eps, queries=[([0], None), ([1, 2], None)],
        walks_per_query=spec.meta["walks_per_query"],
        key=jax.random.PRNGKey(1), mesh=mesh)
    w = _site_widths(spec)["ppr"]
    return [_check("ppr", res.a2a_bytes, res.a2a_entries, w)]


# ---------------------------------------------------------------------------
# the full audit
# ---------------------------------------------------------------------------

ENGINES = ("walks", "counts", "improved", "directed", "ppr")


def _fixture_for(engine: str):
    from repro.graphs import directed_web, erdos_renyi
    if engine == "directed":
        return directed_web(96, 5.0, seed=3), "directed_web(96, 5.0, seed=3)"
    return erdos_renyi(96, 5.0, seed=1), "erdos_renyi(96, 5.0, seed=1)"


def _spec_for(engine: str, graph, mesh, *, eps: float,
              K: int) -> EngineAuditSpec:
    if engine == "walks":
        from repro.core.distributed import audit_spec
        return audit_spec(graph, mesh, eps=eps, walks_per_node=K)
    if engine == "counts":
        from repro.core.distributed_counts import audit_spec
        return audit_spec(graph, mesh, eps=eps, walks_per_node=K)
    if engine == "improved":
        from repro.core.distributed_improved import audit_spec
        return audit_spec(graph, mesh, eps=eps, walks_per_node=K)
    if engine == "directed":
        from repro.core.distributed_directed import audit_spec
        return audit_spec(graph, mesh, eps=eps, walks_per_node=K)
    if engine == "ppr":
        from repro.core.personalized_batch import audit_spec
        return audit_spec(graph, mesh, eps=eps, walks_per_query=4 * K)
    raise ValueError(f"unknown engine '{engine}' (one of {ENGINES})")


def audit_all_engines(mesh=None, *, run_telemetry: bool = True,
                      eps: float = 0.2, walks_per_node: int = 2,
                      engines: Optional[Tuple[str, ...]] = None
                      ) -> Dict[str, Any]:
    """Audit every distributed engine; returns the AUDIT.json dict.

    Static checks trace the engines' own memoized stage programs; with
    `run_telemetry` the engines also execute on small fixture graphs and
    their runtime byte counters are checked against the runtime entry
    counters times the declared per-entry widths.
    """
    from jax.sharding import Mesh

    from repro.core.distributed import AXIS
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), (AXIS,))
    shards = int(mesh.devices.size)
    K = walks_per_node
    report: Dict[str, Any] = dict(devices=shards, eps=eps,
                                  walks_per_node=K, engines={})
    total = 0
    for engine in (engines or ENGINES):
        graph, fixture = _fixture_for(engine)
        spec = _spec_for(engine, graph, mesh, eps=eps, K=K)
        entry = audit_engine_spec(spec)
        entry["fixture"] = fixture

        spec_hi = _spec_for(engine, graph, mesh, eps=eps, K=2 * K)
        w_violations = check_w_independence(spec, spec_hi)
        entry["w_independent"] = not w_violations
        entry["violations"].extend(v.to_dict() for v in w_violations)

        if run_telemetry:
            if engine == "walks":
                checks = _telemetry_walks(graph, mesh, spec, eps, K)
            elif engine == "counts":
                checks = _telemetry_counts(graph, mesh, spec, eps, K)
            elif engine in ("improved", "directed"):
                checks = _telemetry_three_phase(
                    graph, mesh, spec, eps, K,
                    directed=engine == "directed")
            else:
                checks = _telemetry_ppr(graph, mesh, spec, eps, K)
            entry["telemetry"] = dict(checks=checks,
                                      ok=all(c["ok"] for c in checks))
            for c in checks:
                if not c["ok"]:
                    entry["violations"].append(AuditViolation(
                        engine=engine, kind="telemetry/mismatch",
                        where=c["name"],
                        message=(f"runtime wire {c['runtime_bytes']} B != "
                                 f"{c['entries']} entries x declared width "
                                 f"(expected {c['expected_bytes']} B)")
                    ).to_dict())
        total += len(entry["violations"])
        report["engines"][engine] = entry
    report["violations_total"] = total
    report["ok"] = total == 0
    return report


def format_wire_table(report: Dict[str, Any]) -> str:
    """Render the per-engine wire-budget table for --audit / CI logs."""
    hdr = (f"{'engine':<9} {'stage/site':<22} {'B/ent':>5} {'lanes':>7} "
           f"{'budget':>7} {'cap B':>8} {'traced B':>8} {'class':<6} "
           f"{'resume':<16}")
    lines = [f"CONGEST wire audit — {report['devices']} shards, "
             f"eps={report['eps']}, K={report['walks_per_node']}",
             hdr, "-" * len(hdr)]
    for name, e in report["engines"].items():
        for row in e["sites"]:
            resume = e["resume"].get(row["stage"], "?").split(" (")[0]
            lines.append(
                f"{name:<9} {row['stage'] + '/' + row['site']:<22} "
                f"{row['entry_nbytes']:>5} {row['lane_entries']:>7} "
                f"{row['budget_entries']:>7} {row['capacity_bytes']:>8} "
                f"{row['traced_payload_bytes']:>8} {row['wire_class']:<6} "
                f"{resume:<16}")
        tele = e.get("telemetry", {}).get("ok")
        tele_s = "-" if tele is None else ("ok" if tele else "MISMATCH")
        lines.append(
            f"{'':<9} {'psums: ' + str(e['psum_sites']):<22} "
            f"max {e['psum_max_bytes']:>3} B   telemetry {tele_s}   "
            f"w-free {'yes' if e['w_independent'] else 'NO'}   "
            f"violations {len(e['violations'])}")
    lines.append("-" * len(hdr))
    lines.append(f"total violations: {report['violations_total']} — "
                 f"{'PASS' if report['ok'] else 'FAIL'}")
    return "\n".join(lines)
