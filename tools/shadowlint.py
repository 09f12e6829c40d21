#!/usr/bin/env python3
"""shadowlint CLI: device-purity, determinism & contract static analysis.

Four passes over the tree (default scope: shadow_tpu/, tools/, bench.py;
docs/ and tests/ for the contract pass):

  (default)     the STL0xx AST rule set (shadow_tpu/analysis/rules.py)
  --contracts   the SLC0xx cross-plane contract auditor (contracts.py):
                metric-namespace table vs emit sites, fault-op registries
                vs injector arms and docs tables, schema-version literals,
                config_spec.md vs the loader, supervisor policy sets
  --threads     the STH0xx host-thread race lint (threads.py): declared-
                guard discipline over the thread-bearing host modules
  --hlo         the HLO budget ledger (hlo_audit.py): per-variant
                collective/sort/gather/byte budgets vs the checked-in
                shadow_tpu/analysis/hlo_baseline.json

Findings that are neither ``# noqa``-suppressed nor grandfathered by the
baseline file (.shadowlint_baseline.json) fail the run.

Usage:
  python tools/shadowlint.py                      # STL text report
  python tools/shadowlint.py --contracts --threads --format json
  python tools/shadowlint.py --hlo                # ledger check (compiles)
  python tools/shadowlint.py --hlo --write-hlo-baseline --virtual-devices 8
  python tools/shadowlint.py --select STH001      # one rule, any pass
  python tools/shadowlint.py --write-baseline     # grandfather the rest

Exit status: 0 when no non-baselined findings, 1 otherwise, 2 on an
environment/usage failure (unparseable source, missing/corrupt HLO
baseline, unknown rule code) — each exit-2 path prints a one-line
remediation hint.  ``bench.py --lint-smoke`` is the schema'd smoke
gate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

DEFAULT_SCOPE = ("shadow_tpu", "tools", "bench.py")


def _fail2(msg: str, hint: str) -> int:
    print(f"shadowlint: {msg}", file=sys.stderr)
    print(f"hint: {hint}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", metavar="PATH",
                    help=f"files/dirs for the STL pass "
                         f"(default: {' '.join(DEFAULT_SCOPE)})")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--select", action="append", metavar="CODE",
                    help="restrict to these rule codes (repeatable; "
                         "STL/SLC/STH)")
    ap.add_argument("--contracts", action="store_true",
                    help="run the cross-plane contract auditor (SLC0xx)")
    ap.add_argument("--threads", action="store_true",
                    help="run the host-thread race lint (STH0xx)")
    ap.add_argument("--hlo", action="store_true",
                    help="check the HLO budget ledger against "
                         "shadow_tpu/analysis/hlo_baseline.json "
                         "(compiles every kernel variant — slow)")
    ap.add_argument("--write-hlo-baseline", action="store_true",
                    help="with --hlo: regenerate the ledger baseline "
                         "from the current lowerings and exit 0")
    ap.add_argument("--virtual-devices", type=int, default=0,
                    metavar="N",
                    help="force N virtual CPU devices before jax "
                         "initializes (lets the mesh/shard_map ledger "
                         "cells lower on a 1-chip box)")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help="baseline file (default: "
                         "<repo>/.shadowlint_baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: report grandfathered "
                         "findings too")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write every current finding to the baseline "
                         "file and exit 0")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="suppress the per-finding lines (summary only)")
    args = ap.parse_args(argv)

    if args.virtual_devices:
        from shadow_tpu.parallel.virtualize import force_cpu_devices

        force_cpu_devices(args.virtual_devices)

    from shadow_tpu.analysis import contracts, linter, threads
    from shadow_tpu.analysis.rules import RULE_INDEX

    all_codes = (
        set(RULE_INDEX) | set(contracts.CONTRACT_RULES)
        | set(threads.THREAD_RULES) | {"SLH001"}
    )
    select = (
        {c.strip().upper() for c in args.select} if args.select else None
    )
    if select is not None:
        unknown = select - all_codes
        if unknown:
            return _fail2(
                f"unknown rule code(s): {sorted(unknown)}",
                f"known codes: {', '.join(sorted(all_codes))}",
            )

    passes: dict[str, int] = {}
    findings = []
    stl_select = (
        None if select is None else select & set(RULE_INDEX)
    )
    run_stl = select is None or bool(stl_select)
    paths = args.paths or [os.path.join(_REPO, p) for p in DEFAULT_SCOPE]
    if run_stl:
        try:
            stl = linter.lint_paths(paths, _REPO, select=stl_select)
        except SyntaxError as e:
            return _fail2(
                f"cannot parse {e.filename}:{e.lineno}: {e.msg}",
                "fix the syntax error (shadowlint needs ast-parseable "
                "sources) or narrow the PATH arguments around the file",
            )
        except OSError as e:
            return _fail2(str(e), "check the PATH arguments exist and "
                                  "are readable")
        findings += stl
        passes["lint"] = len(stl)

    def _want(codes) -> bool:
        return select is None or bool(select & set(codes))

    if args.contracts and _want(contracts.CONTRACT_RULES):
        slc = contracts.audit_tree(_REPO)
        if select is not None:
            slc = [f for f in slc if f.code in select]
        findings += slc
        passes["contracts"] = len(slc)
    if args.threads and _want(threads.THREAD_RULES):
        try:
            sth = threads.lint_threads_paths(_REPO)
        except SyntaxError as e:
            return _fail2(
                f"cannot parse a thread-lint module: {e}",
                "fix the syntax error; the race lint walks "
                "analysis/threads.THREAD_MODULES",
            )
        if select is not None:
            sth = [f for f in sth if f.code in select]
        findings += sth
        passes["threads"] = len(sth)

    hlo_findings: list[linter.Finding] = []
    if args.hlo:
        from shadow_tpu.analysis import hlo_audit

        bpath = hlo_audit.baseline_path(_REPO)
        if not args.write_hlo_baseline:
            # fail BEFORE paying the compiles when the baseline is bad
            try:
                baseline = hlo_audit.load_hlo_baseline(bpath)
            except hlo_audit.HloBaselineError as e:
                return _fail2(str(e).split(" — ")[0],
                              str(e).split(" — ")[-1])
        ledger = hlo_audit.budget_ledger(
            hlo_audit.default_ledger_variants()
        )
        if args.write_hlo_baseline:
            hlo_audit.write_hlo_baseline(ledger, bpath)
            print(
                f"wrote {len(ledger)} HLO ledger entr"
                f"{'y' if len(ledger) == 1 else 'ies'} to {bpath}"
            )
            return 0
        for problem in hlo_audit.check_ledger(ledger, baseline):
            hlo_findings.append(linter.Finding(
                path="shadow_tpu/analysis/hlo_baseline.json", line=1,
                col=0, code="SLH001", message=problem,
                text=problem.split(":", 1)[0],
            ))
        if select is not None:
            hlo_findings = [f for f in hlo_findings if f.code in select]
        findings += hlo_findings
        passes["hlo"] = len(hlo_findings)

    findings.sort(key=lambda f: (f.path, f.line, f.col, f.code))
    baseline_path = args.baseline or os.path.join(
        _REPO, linter.BASELINE_NAME
    )
    if args.write_baseline:
        doc = linter.write_baseline(findings, baseline_path)
        print(
            f"wrote {len(doc['entries'])} baseline entr"
            f"{'y' if len(doc['entries']) == 1 else 'ies'} to "
            f"{baseline_path}"
        )
        return 0

    try:
        baseline = (
            {} if args.no_baseline else linter.load_baseline(baseline_path)
        )
    except ValueError as e:
        return _fail2(str(e), "regenerate with `python "
                              "tools/shadowlint.py --write-baseline`")
    new, old = linter.split_baselined(findings, baseline)
    # per-pass counts are post-baseline: grandfathered findings drop out
    code_pass = {"STL": "lint", "SLC": "contracts", "STH": "threads",
                 "SLH": "hlo"}
    for name in list(passes):
        passes[name] = 0
    for f in new:
        name = code_pass.get(f.code[:3])
        if name is not None:
            passes[name] = passes.get(name, 0) + 1
    scanned = list(linter.iter_python_files(paths)) if run_stl else []
    doc = linter.findings_doc(new, old, scanned, passes=passes)

    if args.format == "json":
        # one line: log scrapers capture stage output line-wise
        json.dump(doc, sys.stdout)
        sys.stdout.write("\n")
    else:
        if not args.quiet:
            for f in new:
                print(f.render())
        per_pass = ", ".join(
            f"{k}={v}" for k, v in sorted(passes.items())
        )
        print(
            f"shadowlint: {len(new)} finding(s) [{per_pass}], "
            f"{len(old)} grandfathered, {len(scanned)} file(s) scanned"
        )
    return 0 if not new else 1


if __name__ == "__main__":
    sys.exit(main())
