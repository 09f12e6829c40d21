#!/usr/bin/env python3
"""tgen-class scaled e2e runner (reference analog: src/test/tor/minimal —
run a network of real transfer processes under the simulator, then
grep-verify stream successes like verify.sh:7-22).

Builds a <hosts>-host network (servers + clients running the real
tests/apps/tgen_like binary), runs it under `python -m shadow_tpu` with
device TCP, then counts stream-success lines across every client's stdout
file and reports PASS/FAIL.

    python tools/run_tgen.py --hosts 1024 --servers 32 --streams 2 \
        --bytes 8192 --data-dir /tmp/tgen1k.data

This launcher never imports JAX: the simulation runs in a
`python -m shadow_tpu` child, and a chip belongs to one process — a parent
that touched JAX would hold it and the child would fail or hang.
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hosts", type=int, default=1024)
    ap.add_argument("--servers", type=int, default=32)
    ap.add_argument("--streams", type=int, default=2)
    ap.add_argument("--bytes", type=int, default=8192)
    ap.add_argument("--stop", type=int, default=15, help="sim seconds")
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--latency-ms", type=int, default=50)
    ap.add_argument("--cpu-plane", action="store_true",
                    help="stage-A CPU network model (no device bridge): "
                    "isolates driver-plane scaling from the chip")
    args = ap.parse_args()

    n_cli = args.hosts - args.servers
    data_dir = args.data_dir or tempfile.mkdtemp(prefix="tgen_run_")
    if os.path.exists(data_dir):
        shutil.rmtree(data_dir)

    cc = shutil.which("cc") or shutil.which("gcc")
    app = os.path.join(tempfile.gettempdir(), "tgen_like_bin")
    subprocess.run(
        [cc, "-O1", "-o", app,
         os.path.join(REPO, "tests", "apps", "tgen_like.c")],
        check=True,
    )

    yaml = f"""
general:
  stop_time: {args.stop} s
  seed: 11
network:
  graph:
    type: gml
    inline: |
      graph [
        node [ id 0 bandwidth_down "100 Mbit" bandwidth_up "100 Mbit" ]
        edge [ source 0 target 0 latency "{args.latency_ms} ms" packet_loss 0.001 ]
      ]
experimental:
  use_device_network: {str(not args.cpu_plane).lower()}
  use_device_tcp: {str(not args.cpu_plane).lower()}
  event_capacity: {1 << 17}
  events_per_host_per_window: 8
  sockets_per_host: 160
hosts:
  srv:
    quantity: {args.servers}
    processes:
      - path: {app}
        args: --server 9100 0
        stop_time: {args.stop - 2} s
  cli:
    quantity: {n_cli}
    processes:
      - path: {app}
        args: srv {args.servers} 9100 {args.streams} {args.bytes}
        start_time: 1 s
"""
    cfg = os.path.join(tempfile.gettempdir(), "tgen_run.yaml")
    with open(cfg, "w") as f:
        f.write(yaml)

    print(f"running {args.hosts} hosts ({n_cli} clients x {args.streams} "
          f"streams x {args.bytes} B) ...", flush=True)
    r = subprocess.run(
        [sys.executable, "-m", "shadow_tpu", cfg,
         "--data-directory", data_dir],
        cwd=REPO,
    )

    # verify.sh-style grep across the per-process stdout files
    want = n_cli * args.streams
    got = complete = 0
    for root, _dirs, files in os.walk(data_dir):
        for fn in files:
            if fn.endswith(".stdout"):
                with open(os.path.join(root, fn)) as f:
                    txt = f.read()
                got += txt.count("stream-success")
                complete += txt.count(f"transfers-complete {args.streams}")
    print(f"stream-success {got}/{want}; clients complete "
          f"{complete}/{n_cli}; sim rc={r.returncode}")
    ok = got == want and complete == n_cli
    print("PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
