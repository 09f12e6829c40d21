"""The `serve` CLI subcommand: ``python -m shadow_tpu serve``.

Starts the resident sim-as-a-service daemon (serve/daemon.py): journaled
sweep queue, AOT-cached fleet kernels, graceful SIGTERM drain, admission
quotas. Operators talk to it with tools/shadowctl.py over the unix
socket. Exit status 0 on a graceful drain; a SIGKILL needs no goodbye —
the next start replays the journal (docs/serving.md).
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="shadow_tpu serve",
        description="crash-safe sim-as-a-service daemon (docs/serving.md)",
    )
    p.add_argument(
        "--state-dir", required=True, metavar="DIR",
        help="daemon state root: journal.wal, per-sweep checkpoint "
             "directories, serve.metrics.json; restart with the same DIR "
             "to replay the journal and finish accepted sweeps",
    )
    p.add_argument(
        "--socket", metavar="PATH",
        help="unix socket for the HTTP API (default <state-dir>/serve.sock)",
    )
    p.add_argument(
        "--lanes", type=int, metavar="N",
        help="device lanes per fleet (default: the sweep's own "
             "fleet.lanes / sweep.lanes)",
    )
    p.add_argument(
        "--max-queue", type=int, default=16, metavar="N",
        help="queue-depth backpressure: submissions beyond N queued+"
             "running sweeps are shed with HTTP 429 (default 16)",
    )
    p.add_argument(
        "--default-quota", type=int, default=8, metavar="N",
        help="per-tenant admission quota: max unfinished sweeps a tenant "
             "may hold (default 8)",
    )
    p.add_argument(
        "--quota", action="append", default=[], metavar="TENANT=N",
        help="per-tenant quota override (repeatable)",
    )
    p.add_argument(
        "--checkpoint-every-dispatches", type=int, default=4, metavar="K",
        help="flush the running fleet's slices + manifest every K "
             "dispatch slices (default 4); smaller = tighter recovery "
             "point, more I/O",
    )
    p.add_argument(
        "--cache-dir", metavar="DIR",
        help="compile-cache root shared with bench.py (default "
             "$SHADOW_TPU_CACHE_DIR or <repo>/.jax_cache); AOT window-"
             "kernel exports live under <DIR>/aot, XLA's cache in <DIR> "
             "unless $JAX_COMPILATION_CACHE_DIR places it",
    )
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    quotas = {}
    for q in args.quota:
        if "=" not in q:
            print(f"error: --quota wants TENANT=N, got {q!r}",
                  file=sys.stderr)
            return 2
        tenant, _, n = q.partition("=")
        try:
            quotas[tenant] = int(n)
        except ValueError:
            print(f"error: --quota {q!r}: {n!r} is not an integer",
                  file=sys.stderr)
            return 2
    from shadow_tpu.serve.daemon import ServeOptions, ShadowDaemon

    opts = ServeOptions(
        state_dir=args.state_dir,
        socket_path=args.socket,
        lanes=args.lanes,
        max_queue_depth=args.max_queue,
        default_quota=args.default_quota,
        tenant_quotas=quotas,
        checkpoint_every_dispatches=args.checkpoint_every_dispatches,
        cache_dir=args.cache_dir,
    )
    return ShadowDaemon(opts).serve_forever()
