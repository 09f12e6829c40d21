"""shadowscope: the host-side profiling plane (time series + attribution).

The third observability plane (counters → audit → profiling): a
fixed-capacity ring of per-handoff interval records plus the mergeable
log-bucketed histograms of obs/hist.py, cheap enough to leave on in
production (a few dict writes per dispatch boundary — the driver already
synced there, so nothing here forces a device round-trip, and nothing
here touches simulation state: profiler-on runs keep bit-identical audit
chains).

Each ``tick_from(sim)`` at a handoff boundary records the DELTAS since
the previous tick — committed events, windows, async supersteps / yields
/ blocked-on-neighbor — stamped with both wall time (``wall_s`` since the
recorder's ``t0_unix``) and committed virtual time (``vt_ns``). Async
islands runs additionally contribute the per-shard frontier surface and
per-shard blocked deltas: frontier spread is the virtual-time roughness
of cond-mat/0302050 and ``blocked`` the desynchronization stall of
cs/0409032 — per interval, those name the shard the whole mesh is
waiting on (``critical_path`` below).

The ring dumps as a schema-versioned ``shadow_tpu.profile`` document
(``--profile-out``, the daemon's ``/timez``); histograms are pure int64
counts so the router can merge N peers' documents exactly
(``merge_profile_docs``), and ``align_series`` puts their rings on one
wall clock via each document's ``t0_unix``.
"""

from __future__ import annotations

import time

from shadow_tpu.obs.counters import committed_counters
from shadow_tpu.obs.hist import LogHistogram

PROFILE_SCHEMA_VERSION = 1
PROFILE_DOC_KIND = "shadow_tpu.profile"

# ring capacity bounds (experimental.profiler_ring)
MIN_RING = 8
DEFAULT_RING = 512

# the driver-plane histograms every recorder carries (ns values); the
# serve plane adds request-latency histograms via hist() on demand
_DRIVER_HISTS = ("dispatch_wall_ns", "host_drain_wall_ns",
                 "window_width_ns")


class ProfRecorder:
    """Fixed-capacity interval ring + mergeable histograms.

    ``base_vt_ns`` seeds the virtual-time baseline: a resumed run passes
    the checkpoint's committed frontier so its first interval's width is
    the width the uninterrupted run would have recorded — the
    resume-then-merge equality the profile smoke gates on.
    """

    def __init__(self, capacity: int = DEFAULT_RING, *,
                 base_vt_ns: int = 0):
        if capacity < MIN_RING:
            raise ValueError(
                f"profiler ring capacity must be >= {MIN_RING}, "
                f"got {capacity}"
            )
        self.capacity = int(capacity)
        self.t0_unix = time.time()
        self._t0 = time.perf_counter()
        self._ring: list[dict] = []
        self._head = 0          # next write slot once the ring is full
        self.recorded = 0       # total intervals ever recorded
        self._hists: dict[str, LogHistogram] = {
            name: LogHistogram() for name in _DRIVER_HISTS
        }
        self._last_wall = self._t0
        self._last = {"events": 0, "windows": 0, "supersteps": 0,
                      "yields": 0, "blocked": 0, "vt_ns": int(base_vt_ns)}
        self._last_shard_blocked: list[int] | None = None
        self._lookahead_in: list[list[int]] | None = None
        self._shards = 0

    # -- histograms ----------------------------------------------------

    def hist(self, name: str) -> LogHistogram:
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = LogHistogram()
        return h

    def observe_wall(self, name: str, dt_s: float) -> None:
        """Wall-span observation in seconds, binned as integer ns."""
        self.hist(name).observe(int(dt_s * 1e9))

    # -- the interval ring ---------------------------------------------

    def _push(self, rec: dict) -> None:
        if len(self._ring) < self.capacity:
            self._ring.append(rec)
        else:
            self._ring[self._head] = rec
            self._head = (self._head + 1) % self.capacity
        self.recorded += 1

    def intervals(self) -> list[dict]:
        """Ring contents, oldest first."""
        return self._ring[self._head:] + self._ring[:self._head]

    @property
    def dropped(self) -> int:
        """Intervals overwritten by ring wraparound."""
        return max(0, self.recorded - self.capacity)

    def tick_from(self, sim, frontier_ns: int | None = None) -> None:
        """Record one interval at a handoff boundary: read-only against
        the sim (host-cached counters + the already-fetched frontier),
        so the profiled schedule is the unprofiled schedule."""
        c = committed_counters(sim)
        events = int(c.get("events_committed", 0))
        windows = int(getattr(sim, "windows_run", 0) or 0)
        shard = None
        ap = getattr(sim, "async_shard_profile", None)
        if ap is not None:
            shard = ap()
        astats = {}
        ast = getattr(sim, "async_stats", None)
        if ast is not None:
            astats = ast() or {}
        vt = int(frontier_ns or 0)
        if shard is not None and shard.get("frontier_ns"):
            vt = max(vt, min(shard["frontier_ns"]))
        self.tick(
            vt_ns=vt, events=events, windows=windows,
            supersteps=int(astats.get("supersteps", 0)),
            yields=int(astats.get("yields", 0)),
            blocked=int(astats.get("blocked_on_neighbor", 0)),
            frontier_ns=(shard or {}).get("frontier_ns"),
            shard_blocked=(shard or {}).get("blocked"),
            lookahead_in=(shard or {}).get("lookahead_in"),
        )

    def tick(self, *, vt_ns: int, events: int, windows: int,
             supersteps: int = 0, yields: int = 0, blocked: int = 0,
             frontier_ns=None, shard_blocked=None,
             lookahead_in=None) -> None:
        """Record one interval from CUMULATIVE inputs; deltas against the
        previous tick are what lands in the ring."""
        now = time.perf_counter()
        last = self._last
        vt_ns = int(vt_ns)
        if vt_ns >= (1 << 62):
            # a drained pool reports NEVER as its frontier (the run's
            # final boundary): record the interval, not a 2^62 "width"
            vt_ns = last["vt_ns"]
        vt_ns = max(vt_ns, last["vt_ns"])  # committed vt is monotonic
        rec = {
            "wall_s": round(now - self._t0, 6),
            "d_wall_s": round(now - self._last_wall, 6),
            "vt_ns": vt_ns,
            "d_vt_ns": vt_ns - last["vt_ns"],
            "d_events": max(0, int(events) - last["events"]),
            "d_windows": max(0, int(windows) - last["windows"]),
            "d_supersteps": max(0, int(supersteps) - last["supersteps"]),
            "d_yields": max(0, int(yields) - last["yields"]),
            "d_blocked": max(0, int(blocked) - last["blocked"]),
        }
        if frontier_ns is not None:
            rec["frontier_ns"] = [int(x) for x in frontier_ns]
        if shard_blocked is not None:
            cur = [int(x) for x in shard_blocked]
            prev = self._last_shard_blocked
            if prev is not None and len(prev) == len(cur):
                rec["d_shard_blocked"] = [
                    max(0, a - b) for a, b in zip(cur, prev)
                ]
            else:
                rec["d_shard_blocked"] = cur
            self._last_shard_blocked = cur
            self._shards = len(cur)
        if lookahead_in is not None and self._lookahead_in is None:
            self._lookahead_in = [[int(x) for x in row]
                                  for row in lookahead_in]
        self._hists["window_width_ns"].observe(rec["d_vt_ns"])
        self._push(rec)
        self._last = {"events": int(events), "windows": int(windows),
                      "supersteps": int(supersteps), "yields": int(yields),
                      "blocked": int(blocked), "vt_ns": vt_ns}
        self._last_wall = now

    @property
    def last_vt_ns(self) -> int:
        """Committed virtual time at the last tick — the ``base_vt_ns``
        a resumed continuation recorder seeds from."""
        return self._last["vt_ns"]

    # -- documents -----------------------------------------------------

    def to_doc(self, meta: dict | None = None) -> dict:
        return {
            "kind": PROFILE_DOC_KIND,
            "schema_version": PROFILE_SCHEMA_VERSION,
            "created_unix": time.time(),
            "t0_unix": self.t0_unix,
            "meta": dict(meta or {}),
            "capacity": self.capacity,
            "recorded": self.recorded,
            "dropped": self.dropped,
            "intervals": self.intervals(),
            "hists": {k: h.to_doc()
                      for k, h in sorted(self._hists.items())
                      if h.count},
            **({"lookahead_in": self._lookahead_in}
               if self._lookahead_in is not None else {}),
        }


def validate_profile_doc(doc: dict) -> None:
    """Reference validator for shadow_tpu.profile documents."""
    if not isinstance(doc, dict):
        raise ValueError("profile doc must be a JSON object")
    if doc.get("kind") != PROFILE_DOC_KIND:
        raise ValueError(
            f"profile doc kind {doc.get('kind')!r} != {PROFILE_DOC_KIND!r}"
        )
    if doc.get("schema_version") != PROFILE_SCHEMA_VERSION:
        raise ValueError(
            f"profile schema_version {doc.get('schema_version')!r} != "
            f"{PROFILE_SCHEMA_VERSION}"
        )
    if not isinstance(doc.get("intervals"), list):
        raise ValueError("profile doc needs an intervals list")
    for i, rec in enumerate(doc["intervals"]):
        if not isinstance(rec, dict) or "wall_s" not in rec \
                or "vt_ns" not in rec:
            raise ValueError(
                f"intervals[{i}] must be an object stamped with wall_s "
                f"and vt_ns"
            )
    hists = doc.get("hists", {})
    if not isinstance(hists, dict):
        raise ValueError("profile doc hists must be an object")
    for k, h in hists.items():
        LogHistogram.from_doc(h)  # layout + shape check
    for k in ("capacity", "recorded", "dropped"):
        v = doc.get(k)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            raise ValueError(f"profile doc {k!r} must be a count, got {v!r}")


def merge_profile_docs(docs: dict[str, dict]) -> dict:
    """Federation roll-up (router /timez): merge named peers' profile
    documents — histograms fold exactly (int64 adds), rings align onto
    one wall clock (``align_series``). Raises ValueError on a layout or
    schema mismatch so a stale peer can't silently poison the fold."""
    hists: dict[str, LogHistogram] = {}
    peers = {}
    for name, doc in sorted(docs.items()):
        validate_profile_doc(doc)
        for k, h in doc.get("hists", {}).items():
            cur = hists.setdefault(k, LogHistogram())
            cur.merge(LogHistogram.from_doc(h))
        peers[name] = {
            "t0_unix": float(doc.get("t0_unix", 0.0)),
            "recorded": int(doc.get("recorded", 0)),
            "dropped": int(doc.get("dropped", 0)),
        }
    return {
        "kind": PROFILE_DOC_KIND,
        "schema_version": PROFILE_SCHEMA_VERSION,
        "created_unix": time.time(),
        "merged": True,
        "peers": peers,
        "hists": {k: h.to_doc() for k, h in sorted(hists.items())
                  if h.count},
        "series": align_series(docs),
    }


def align_series(docs: dict[str, dict]) -> list[dict]:
    """One interleaved time series from N peers' rings: every interval
    re-stamped onto the unix clock (``t0_unix + wall_s``) and tagged with
    its peer, sorted by absolute time — one timeline, N producers."""
    out = []
    for name, doc in sorted(docs.items()):
        t0 = float(doc.get("t0_unix", 0.0))
        for rec in doc.get("intervals", []):
            r = dict(rec)
            r["peer"] = name
            r["t_unix"] = round(t0 + float(rec.get("wall_s", 0.0)), 6)
            out.append(r)
    out.sort(key=lambda r: (r["t_unix"], r["peer"]))
    return out


def critical_path(doc: dict) -> dict | None:
    """Critical-path attribution from a profile document's per-shard
    interval data.

    Per interval the laggard is the shard holding the minimum frontier —
    under conservative sync every other shard's horizon is bounded by
    that frontier plus its in-edge lookahead, so when anyone is blocked,
    the minimum-frontier shard is what they are waiting on. Wall time of
    intervals that saw blocking is attributed to that interval's laggard;
    the report names the shard with the largest attribution, the in-edge
    link it throttles hardest (its most-blocked victim, with the baked
    lookahead bound when the document carries the matrix), and the
    blocked fraction of all shard-supersteps. Returns None when the
    document carries no per-shard intervals (barrier or global-engine
    runs)."""
    rows = [r for r in doc.get("intervals", [])
            if r.get("frontier_ns")]
    if not rows:
        return None
    S = len(rows[0]["frontier_ns"])
    attr_wall = [0.0] * S       # wall attributed to shard as laggard
    victim_blk = [[0] * S for _ in range(S)]  # [laggard][victim]
    tot_blocked = tot_steps = tot_yields = 0
    total_wall = 0.0
    for r in rows:
        fr = r["frontier_ns"]
        if len(fr) != S:
            continue  # elastic relayout changed the mesh mid-ring
        dw = float(r.get("d_wall_s", 0.0))
        total_wall += dw
        lag = min(range(S), key=lambda i: (fr[i], i))
        blk = r.get("d_shard_blocked")
        d_blocked = int(r.get("d_blocked", 0)) if blk is None \
            else int(sum(blk))
        tot_blocked += d_blocked
        tot_steps += int(r.get("d_supersteps", 0))
        tot_yields += int(r.get("d_yields", 0))
        if d_blocked > 0:
            attr_wall[lag] += dw
            if blk is not None and len(blk) == S:
                for v in range(S):
                    if v != lag:
                        victim_blk[lag][v] += blk[v]
    critical = max(range(S), key=lambda i: (attr_wall[i], -i))
    denom = tot_blocked + tot_steps + tot_yields
    result = {
        "shards": S,
        "intervals": len(rows),
        "critical_shard": int(critical),
        "wall_s": round(total_wall, 6),
        "attributed_wall_s": round(attr_wall[critical], 6),
        "wall_frac": (attr_wall[critical] / total_wall)
        if total_wall > 0 else 0.0,
        "blocked_frac": (tot_blocked / denom) if denom else 0.0,
        "per_shard_wall_s": [round(w, 6) for w in attr_wall],
    }
    vrow = victim_blk[critical]
    if any(vrow):
        victim = max(range(S), key=lambda v: (vrow[v], -v))
        link = {"src": int(critical), "dst": int(victim),
                "blocked": int(vrow[victim])}
        la = doc.get("lookahead_in")
        if la is not None and len(la) == S:
            bound = int(la[victim][critical])
            if bound < (1 << 62):  # NEVER-masked rows mean "no edge"
                link["lookahead_ns"] = bound
        result["link"] = link
    return result
