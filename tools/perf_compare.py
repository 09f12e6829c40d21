#!/usr/bin/env python3
"""Diff two shadow_tpu.metrics artifacts with per-key thresholds.

    tools/perf_compare.py BASELINE.json CANDIDATE.json [--json]
    tools/perf_compare.py a.json b.json --thresholds rules.json

Compares every counter/gauge key the two documents share (plus
``meta.wall_s``) under a direction-aware threshold table:

  * ``eq``   — determinism keys (committed events, audit chain): any
               difference is a regression;
  * ``down`` — lower-is-better keys (wall-time percentiles): candidate
               exceeding baseline by more than ``rel_tol`` regresses;
  * ``up``   — higher-is-better keys: candidate falling short of
               baseline by more than ``rel_tol`` regresses.

Unmatched shared keys are reported as drift but never gate. A custom
table (JSON list of ``[pattern, direction, rel_tol]`` rows, first match
wins) replaces the default. Documents whose ``meta.ok`` is false are
SKIPPED (exit 0): a failed producing gate is that stage's failure, not
a perf regression to double-report. Mismatched schema_versions also
skip — cross-schema numbers are not comparable.

Exit status: 0 no regression (or skipped, with the reason printed);
1 at least one thresholded key regressed; 2 unreadable/malformed input.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# first match wins; keys with no row are informational only
DEFAULT_THRESHOLDS: list[tuple[str, str, float]] = [
    ("engine.events_committed", "eq", 0.0),
    ("engine.events_emitted", "eq", 0.0),
    ("audit.chain", "eq", 0.0),
    # wall-time latency percentiles (profiling plane): generous relative
    # bounds — CI boxes are noisy, a real regression is not 10%
    ("prof.*_p50", "down", 0.50),
    ("prof.*_p90", "down", 0.50),
    ("prof.*_p99", "down", 0.75),
    ("prof.blocked_frac", "down", 0.50),
    ("meta.wall_s", "down", 0.50),
]


def _flatten(doc: dict) -> dict[str, float]:
    out: dict[str, float] = {}
    for sect in ("counters", "gauges"):
        for k, v in (doc.get(sect) or {}).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[k] = float(v)
    w = (doc.get("meta") or {}).get("wall_s")
    if isinstance(w, (int, float)) and not isinstance(w, bool):
        out["meta.wall_s"] = float(w)
    return out


def _rule_for(key: str, rules) -> tuple[str, float] | None:
    for pat, direction, tol in rules:
        if fnmatch.fnmatchcase(key, pat):
            return direction, float(tol)
    return None


def compare_docs(base: dict, cand: dict, rules=None) -> dict:
    """Pure comparison: {regressions: [...], drift: [...], compared: N}.
    Each row: {key, base, cand, rel, direction, rel_tol}."""
    rules = DEFAULT_THRESHOLDS if rules is None else rules
    b, c = _flatten(base), _flatten(cand)
    regressions, drift = [], []
    shared = sorted(set(b) & set(c))
    for key in shared:
        bv, cv = b[key], c[key]
        rel = (cv - bv) / abs(bv) if bv else (0.0 if cv == bv else 1.0)
        rule = _rule_for(key, rules)
        row = {"key": key, "base": bv, "cand": cv, "rel": round(rel, 4)}
        if rule is None:
            if cv != bv:
                drift.append(row)
            continue
        direction, tol = rule
        row["direction"], row["rel_tol"] = direction, tol
        regressed = (
            (direction == "eq" and cv != bv)
            or (direction == "down" and rel > tol)
            or (direction == "up" and rel < -tol)
        )
        if regressed:
            regressions.append(row)
        elif cv != bv:
            drift.append(row)
    return {
        "compared": len(shared),
        "regressions": regressions,
        "drift": drift,
    }


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _skip_reason(name: str, doc: dict) -> str | None:
    if doc.get("kind") != "shadow_tpu.metrics":
        return f"{name} is not a shadow_tpu.metrics document"
    if (doc.get("meta") or {}).get("ok") is False:
        return (f"{name} records ok:false — its producing gate already "
                f"failed; not double-reporting as a perf regression")
    return None


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="perf_compare", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("baseline", help="reference metrics artifact")
    p.add_argument("candidate", help="metrics artifact under test")
    p.add_argument("--thresholds", metavar="JSON",
                   help="replace the default threshold table "
                        "(list of [pattern, direction, rel_tol] rows)")
    p.add_argument("--json", action="store_true",
                   help="print the full comparison dict")
    args = p.parse_args(argv)

    try:
        base = _load(args.baseline)
        cand = _load(args.candidate)
        rules = None
        if args.thresholds:
            rules = [
                (str(r[0]), str(r[1]), float(r[2]))
                for r in _load(args.thresholds)
            ]
    except (OSError, json.JSONDecodeError, ValueError,
            IndexError, TypeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    for name, doc in ((args.baseline, base), (args.candidate, cand)):
        reason = _skip_reason(name, doc)
        if reason:
            print(f"perf_compare: skipped — {reason}")
            return 0
    if base.get("schema_version") != cand.get("schema_version"):
        print(
            f"perf_compare: skipped — schema_version "
            f"{base.get('schema_version')} vs "
            f"{cand.get('schema_version')}: cross-schema numbers are "
            f"not comparable"
        )
        return 0
    result = compare_docs(base, cand, rules)
    result["baseline"] = args.baseline
    result["candidate"] = args.candidate
    if args.json:
        # one line so log scrapers capture it whole
        print(json.dumps(result))
    else:
        for row in result["regressions"]:
            print(
                f"REGRESSION {row['key']}: {row['base']:g} -> "
                f"{row['cand']:g} ({row['rel']:+.1%}, "
                f"{row['direction']} tol {row['rel_tol']:.0%})"
            )
        for row in result["drift"]:
            print(
                f"drift      {row['key']}: {row['base']:g} -> "
                f"{row['cand']:g} ({row['rel']:+.1%})"
            )
        print(
            f"perf_compare: {result['compared']} shared key(s), "
            f"{len(result['regressions'])} regression(s), "
            f"{len(result['drift'])} drifted"
        )
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
