#!/usr/bin/env python
"""Static graph + memory-plan auditor and seam lint CLI
(docs/STATIC_ANALYSIS.md).

Runs the ``deepspeed_tpu/analysis`` auditors over the bench-row step
configs on a virtual 8-device CPU mesh (``--rows`` for the collective/
donation graph audit, ``--memory`` for the HBM memory-plan audit — both
families share ONE lowering per target) and/or the AST-level
jax-version-seam lint over the production tree (``--seam``); with no
flags, everything runs.  Exit status 1 when any HIGH-severity finding is
not suppressed by the baseline file.

Usage::

    python tools/graft_lint.py                   # everything
    python tools/graft_lint.py --rows train_zero3 v2_decode
    python tools/graft_lint.py --memory          # memory audits, all rows
    python tools/graft_lint.py --memory --target train_zero3
    python tools/graft_lint.py --seam            # AST lint only
    python tools/graft_lint.py --plan            # audit planner output:
                                                 # top-ranked config per
                                                 # bench-row query must
                                                 # lower clean
    python tools/graft_lint.py --list            # show row targets
    python tools/graft_lint.py --json out.json   # machine-readable dump
    python tools/graft_lint.py --write-baseline  # accept current highs
                                                 # + freeze peak budgets

Two baselines gate the lint:

* ``tools/graft_lint_baseline.json`` — finding fingerprints (stable
  hashes of kind|where|stable-key, never byte counts): a deliberately
  accepted finding stays suppressed while anything NEW fails.
* ``tools/memory_baseline.json`` — frozen per-target peak budgets
  (``{"budgets": {target: {backend: bucketed_bytes}}}``, bytes bucketed
  so CPU-vs-TPU layout jitter never churns the file) plus the
  ``model_drift`` calibration ratios the autotuner consumes.  A >10%
  peak growth past the budget is a high ``peak_regression`` finding;
  ``--write-baseline`` (with memory audits running) re-freezes budgets
  for the current backend.  Review both files' diffs like code.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO, "tools", "graft_lint_baseline.json")
DEFAULT_MEMORY_BASELINE = os.path.join(REPO, "tools",
                                       "memory_baseline.json")


def _setup_mesh_backend() -> None:
    """Pin the virtual 8-device CPU mesh BEFORE any backend touch
    (audits check graph *structure*, which the CPU mesh lowers
    identically, and need no chip)."""
    flags = os.environ.get("XLA_FLAGS", "")
    for flag in ("--xla_force_host_platform_device_count=8",
                 "--xla_backend_optimization_level=0"):
        if flag.split("=")[0] not in flags:
            flags = f"{flags} {flag}".strip()
    os.environ["XLA_FLAGS"] = flags
    import jax

    jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="graft_lint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--rows", nargs="*", default=None, metavar="ROW",
                   help="graph-audit bench-row step configs (all when no "
                        "names are given)")
    p.add_argument("--memory", nargs="*", default=None, metavar="ROW",
                   help="memory-plan-audit bench-row step configs (all "
                        "when no names are given); shares one lowering "
                        "per target with --rows")
    p.add_argument("--target", action="append", default=None,
                   metavar="ROW",
                   help="restrict --rows/--memory to these targets "
                        "(repeatable)")
    p.add_argument("--seam", action="store_true",
                   help="run the AST jax-version-seam lint")
    p.add_argument("--plan", action="store_true",
                   help="audit the planner's top-ranked config per "
                        "registered bench-row query (planner/audit.py): "
                        "each must lower with 0 unbaselined graph/memory "
                        "highs — a plan the auditors reject must not "
                        "ship")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="finding-fingerprint suppression file")
    p.add_argument("--memory-baseline", default=DEFAULT_MEMORY_BASELINE,
                   help="frozen per-target peak-budget file")
    p.add_argument("--write-baseline", action="store_true",
                   help="append every currently-unsuppressed high "
                        "finding to the baseline; with memory audits "
                        "running, also freeze peak budgets + calibration "
                        "for the current backend")
    p.add_argument("--json", dest="json_path", default=None,
                   help="write full reports + findings as JSON")
    p.add_argument("--list", action="store_true",
                   help="list bench-row audit targets and exit")
    args = p.parse_args(argv)

    sys.path.insert(0, REPO)
    from deepspeed_tpu.analysis.report import (load_baseline,
                                               load_memory_baseline)

    all_default = (args.rows is None and args.memory is None
                   and not args.seam and not args.plan)
    run_rows = args.rows is not None or all_default
    run_memory = args.memory is not None or all_default
    run_seam = args.seam or all_default

    if args.list:
        from deepspeed_tpu.analysis.targets import TARGET_PREPARERS
        for name in sorted(TARGET_PREPARERS):
            print(name)
        return 0

    findings = []
    reports = []
    mem_reports = []
    if run_rows or run_memory:
        _setup_mesh_backend()
        import jax

        from deepspeed_tpu.analysis.targets import (TARGET_PREPARERS,
                                                    run_target_audits)
        backend = jax.default_backend()
        mem_base = load_memory_baseline(args.memory_baseline)
        row_names = set(args.rows or sorted(TARGET_PREPARERS)) \
            if run_rows else set()
        mem_names = set(args.memory or sorted(TARGET_PREPARERS)) \
            if run_memory else set()
        names = sorted(row_names | mem_names)
        if args.target:
            # a misspelled --target must fail loudly, never shrink the
            # audit set to nothing and exit 0 (a green gate that
            # verified nothing)
            unknown = sorted(set(args.target) - set(TARGET_PREPARERS))
            if unknown:
                p.error(f"unknown --target {unknown}; known targets: "
                        f"{sorted(TARGET_PREPARERS)}")
            names = [n for n in names if n in set(args.target)]
        for name in names:
            budget = mem_base["budgets"].get(name, {}).get(backend)
            rep, mem = run_target_audits(name, memory=name in mem_names,
                                         budget=budget,
                                         graph=name in row_names)
            if name in row_names:
                reports.append(rep)
                findings.extend(rep.findings)
                census = ", ".join(
                    f"{k}×{v['count']}"
                    for k, v in rep.census_summary().items()
                    if k != "fused_collective")
                print(f"row {name}: {len(rep.findings)} finding(s); "
                      f"donation {rep.donation['aliased']}/"
                      f"{rep.donation['declared']} aliased; "
                      f"census [{census or 'no collectives'}]")
            if mem is not None:
                mem_reports.append(mem)
                findings.extend(mem.findings)
                peak = mem.totals["peak_bytes"]
                print(f"memory {name}: peak {peak / (1 << 20):.2f} "
                      f"MiB/device (budget "
                      f"{'—' if budget is None else budget}); "
                      f"{len(mem.findings)} finding(s)")
    if run_seam:
        from deepspeed_tpu.analysis.seam import lint_repo
        seam = lint_repo(REPO)
        findings.extend(seam)
        print(f"seam: {len(seam)} violation(s)")

    plan_reports = []
    if args.plan:
        _setup_mesh_backend()
        from deepspeed_tpu.planner.audit import (PLAN_AUDIT_ROWS,
                                                 audit_planned_config)
        for name in PLAN_AUDIT_ROWS:
            frag, rep, mem = audit_planned_config(name)
            # plan twins join the finding gate but NOT mem_reports —
            # --write-baseline must never freeze budgets for the
            # synthetic plan:* labels
            findings.extend(rep.findings)
            findings.extend(mem.findings)
            plan_reports.append({"name": name, "fragment": frag,
                                 "graph": rep.to_dict(),
                                 "memory": mem.to_dict()})
            mesh = frag.get("mesh") or {}
            mesh_s = "x".join(f"{k}{v}"
                              for k, v in sorted(mesh.items())) or "data1"
            stage = (frag.get("zero_optimization") or {}).get("stage", 0)
            print(f"plan {name}: top-ranked zero{stage} mesh {mesh_s} "
                  f"lowered; {len(rep.findings) + len(mem.findings)} "
                  f"finding(s)")

    baseline = load_baseline(args.baseline)
    highs: List = [f for f in findings if f.severity == "high"]
    new_highs = [f for f in highs if f.fingerprint() not in baseline]
    suppressed = len(highs) - len(new_highs)

    for f in findings:
        mark = ("BASELINED" if f.severity == "high"
                and f.fingerprint() in baseline else f.severity.upper())
        print(f"[{mark}] {f.kind} @ {f.where} ({f.fingerprint()})\n"
              f"    {f.message}")

    if args.write_baseline and mem_reports:
        _write_memory_baseline(args.memory_baseline, mem_reports)
        # budgets just froze: drop the now-stale no-budget warnings and
        # peak regressions from this run's gate — the next run audits
        # against the frozen numbers
        new_highs = [f for f in new_highs if f.kind != "peak_regression"]
    if args.write_baseline and new_highs:
        data = {"comment": "graft_lint accepted findings — every entry "
                           "is a Finding.fingerprint(); review changes "
                           "to this file like code",
                "suppress": sorted(baseline.union(
                    f.fingerprint() for f in new_highs))}
        with open(args.baseline, "w", encoding="utf-8") as fh:
            json.dump(data, fh, indent=2)
            fh.write("\n")
        print(f"baseline: wrote {len(new_highs)} new fingerprint(s) to "
              f"{args.baseline}")
        new_highs = []

    if args.json_path:
        with open(args.json_path, "w", encoding="utf-8") as fh:
            json.dump({"reports": [r.to_dict() for r in reports],
                       "memory_reports": [r.to_dict()
                                          for r in mem_reports],
                       "plan_reports": plan_reports,
                       "findings": [f.to_dict() for f in findings],
                       "unbaselined_high": [f.to_dict()
                                            for f in new_highs]},
                      fh, indent=2, sort_keys=True)

    print(f"graft_lint: {len(findings)} finding(s), {len(new_highs)} "
          f"unbaselined high ({suppressed} baselined)")
    return 1 if new_highs else 0


def _write_memory_baseline(path: str, mem_reports) -> None:
    """Freeze peak budgets (bucketed) + the median model-drift
    calibration ratio for the audited backend, preserving other
    backends' entries (the TPU budgets survive a CPU re-freeze)."""
    from deepspeed_tpu.analysis.report import load_memory_baseline

    data = load_memory_baseline(path)
    ratios = []
    backend = mem_reports[0].backend if mem_reports else "cpu"
    for rep in mem_reports:
        data["budgets"].setdefault(rep.label, {})[rep.backend] = \
            rep.budget["bucketed_peak_bytes"]
        if rep.calibration.get("ratio"):
            ratios.append(float(rep.calibration["ratio"]))
    if ratios:
        ratios.sort()
        data["calibration"][backend] = round(
            ratios[len(ratios) // 2], 4)
    out = {"comment": "frozen per-target static-peak budgets (bytes, "
                      "bucketed via analysis.report.bucket_bytes) + "
                      "model_drift calibration ratios per backend — "
                      "written by graft_lint --memory --write-baseline; "
                      "review changes like code (docs/STATIC_ANALYSIS.md)",
           "budgets": {k: dict(sorted(v.items()))
                       for k, v in sorted(data["budgets"].items())},
           "calibration": dict(sorted(data["calibration"].items()))}
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(out, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"memory baseline: froze {len(mem_reports)} budget(s) to "
          f"{path}")


if __name__ == "__main__":
    sys.exit(main())
