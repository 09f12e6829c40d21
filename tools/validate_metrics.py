#!/usr/bin/env python3
"""Validate metrics JSON documents against the reference schema.

A standalone CLI wrapper over `obs.metrics.validate_metrics_doc`
(docs/observability.md; the schema version and per-namespace rules —
including `--strict-namespaces` membership of the closed
KNOWN_METRIC_NAMESPACES table, `qdisc.*` since schema v17 —
come from obs/metrics.py, so this tool tracks every schema bump
automatically): CI gates every
captured metrics artifact with this at capture time, so a schema
regression is caught on the line that produced it, not months later by a
consumer.

Usage:  python tools/validate_metrics.py run.metrics.json [...]

Exit status: 0 when every document validates, 1 otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+", metavar="METRICS_JSON",
                    help="metrics documents written by --metrics-out")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="suppress per-file ok lines (errors still print)")
    ap.add_argument("--strict-namespaces", action="store_true",
                    help="additionally require every dotted metric key to "
                         "live in KNOWN_METRIC_NAMESPACES (obs/metrics.py) "
                         "— the runtime twin of shadowlint STL008")
    args = ap.parse_args(argv)

    from shadow_tpu.obs.metrics import validate_metrics_doc

    rc = 0
    for path in args.paths:
        try:
            with open(path) as f:
                doc = json.load(f)
            validate_metrics_doc(
                doc, strict_namespaces=args.strict_namespaces
            )
        except (OSError, ValueError) as e:
            print(f"{path}: INVALID: {e}", file=sys.stderr)
            rc = 1
            continue
        if not args.quiet:
            print(f"{path}: ok (schema v{doc['schema_version']})")
    return rc


if __name__ == "__main__":
    sys.exit(main())
