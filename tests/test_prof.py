"""shadowscope profiling plane: histograms, the interval ring, merging.

The plane's two contracts (docs/observability.md §Profiling):

  * mergeability — a histogram accumulated across fleet lanes, federation
    peers, or a checkpoint-resume boundary is EXACTLY the histogram one
    uninterrupted observer would have built (int64 counts on a fixed
    bucket layout, merge = elementwise add);
  * read-only observation — the recorder never touches simulation state,
    so profiler-on runs keep bit-identical audit chains (gated end to end
    by bench.py --profile-smoke; asserted here on a small islands run).
"""

import json

import pytest

from shadow_tpu.obs import prof as prof_mod
from shadow_tpu.obs.hist import (
    NUM_BINS, SUB_BITS, LogHistogram, bucket_hi, bucket_index, bucket_lo,
)
from shadow_tpu.obs.prof import (
    ProfRecorder, align_series, critical_path, merge_profile_docs,
    validate_profile_doc,
)

from _contracts import assert_current_metrics_schema

NEVER = (1 << 63) - 1


# ---------------------------------------------------------------------------
# bucket layout
# ---------------------------------------------------------------------------


def test_bucket_bounds_cover_every_value():
    import random

    rng = random.Random(7)
    for v in [0, 1, 2, 3, 4, 5, 7, 8, 1023, 1024] + [
        rng.randrange(0, 1 << 60) for _ in range(5000)
    ]:
        i = bucket_index(v)
        lo, hi = bucket_lo(i), bucket_hi(i)
        assert lo <= v, (v, i, lo)
        assert hi is None or v <= hi, (v, i, hi)


def test_bucket_relative_error_bound():
    # log-linear with SUB_BITS sub-buckets per octave: bucket width is
    # at most 2**-SUB_BITS of its lower bound (the HDR error contract)
    for i in range(1 << SUB_BITS, NUM_BINS - 1):
        lo, hi = bucket_lo(i), bucket_hi(i)
        assert (hi - lo + 1) <= max(1, lo >> SUB_BITS), (i, lo, hi)


def test_overflow_bucket_catches_huge_values():
    # every int64 has a bounded bucket; the overflow bin starts at the
    # first value whose index would pass NUM_BINS - 1 (7 * 2**62 with
    # the default layout) and is unbounded above
    h = LogHistogram()
    h.observe(7 << 62)
    h.observe(1 << 70)
    assert h.buckets == {NUM_BINS - 1: 2}
    # percentile clamps to the observed max, never an invented bound
    assert h.percentile(50) == h.max == 1 << 70
    # just below the overflow threshold still lands in a bounded bucket
    assert bucket_index((7 << 62) - 1) < NUM_BINS - 1


def test_empty_histogram_percentile_is_zero():
    h = LogHistogram()
    assert h.percentile(50) == 0
    assert h.percentile(99) == 0
    s = h.summary()
    assert s["count"] == 0 and s["p99"] == 0 and s["mean"] == 0.0


def test_percentiles_nearest_rank():
    h = LogHistogram()
    for v in range(1, 101):  # 1..100, exact buckets only up to 3
        h.observe(v)
    assert h.summary()["count"] == 100
    # p50 falls in the bucket holding rank 50; bounds are quantized but
    # must bracket the true value within the layout's relative error
    p50 = h.percentile(50)
    assert 50 <= p50 <= 63
    assert h.percentile(100) == 100


# ---------------------------------------------------------------------------
# merge algebra
# ---------------------------------------------------------------------------


def _hist_of(values):
    h = LogHistogram()
    for v in values:
        h.observe(v)
    return h


def test_merge_commutative_and_associative():
    a = _hist_of([1, 5, 9000, 1 << 40])
    b = _hist_of([0, 2, 77, 77, 123456789])
    c = _hist_of([3, 3, 3, 1 << 55])

    ab = _hist_of([]) ; ab.merge(a); ab.merge(b)
    ba = _hist_of([]) ; ba.merge(b); ba.merge(a)
    assert ab == ba  # commutative

    ab_c = _hist_of([]); ab_c.merge(ab); ab_c.merge(c)
    bc = _hist_of([]); bc.merge(b); bc.merge(c)
    a_bc = _hist_of([]); a_bc.merge(a); a_bc.merge(bc)
    assert ab_c == a_bc  # associative


def test_merge_equals_uninterrupted_observer():
    vals = [0, 1, 4, 4, 999, 10**7, 1 << 45]
    full = _hist_of(vals)
    split = _hist_of(vals[:3])
    split.merge(_hist_of(vals[3:]))
    assert split == full
    assert split.summary() == full.summary()


def test_doc_roundtrip_and_layout_refusal():
    h = _hist_of([5, 500, 1 << 30])
    assert LogHistogram.from_doc(h.to_doc()) == h
    bad = h.to_doc()
    bad["sub_bits"] = SUB_BITS + 1
    with pytest.raises(ValueError, match="layout mismatch"):
        LogHistogram.from_doc(bad)


# ---------------------------------------------------------------------------
# the interval ring
# ---------------------------------------------------------------------------


def _tick_n(rec, n, *, start=0, step_vt=1000, step_ev=10):
    for k in range(start, start + n):
        rec.tick(vt_ns=(k + 1) * step_vt, events=(k + 1) * step_ev,
                 windows=k + 1)


def test_ring_wraparound_keeps_newest():
    r = ProfRecorder(8)
    _tick_n(r, 20)
    assert r.recorded == 20
    assert r.dropped == 12
    ivs = r.intervals()
    assert len(ivs) == 8
    # oldest-first, and the survivors are the NEWEST 8 intervals
    assert [iv["vt_ns"] for iv in ivs] == [
        (k + 1) * 1000 for k in range(12, 20)
    ]
    assert all(iv["d_vt_ns"] == 1000 for iv in ivs)


def test_ring_capacity_floor():
    with pytest.raises(ValueError, match=">= 8"):
        ProfRecorder(4)


def test_never_frontier_clamps_final_interval():
    r = ProfRecorder(8)
    r.tick(vt_ns=5000, events=10, windows=1)
    r.tick(vt_ns=NEVER, events=20, windows=2)  # drained-pool frontier
    last = r.intervals()[-1]
    assert last["vt_ns"] == 5000 and last["d_vt_ns"] == 0


def test_resume_then_merge_equals_uninterrupted():
    """A run profiled across a checkpoint-resume boundary merges into
    the profile one uninterrupted run would have produced: the resumed
    recorder seeds base_vt_ns from the checkpointed frontier, so the
    first post-resume interval has the width the uninterrupted run saw,
    and the merged histograms are equal by int64 fold."""
    full = ProfRecorder(64)
    _tick_n(full, 10)

    first = ProfRecorder(64)
    _tick_n(first, 6)
    resumed = ProfRecorder(64, base_vt_ns=first.last_vt_ns)
    _tick_n(resumed, 4, start=6)

    merged = merge_profile_docs(
        {"a": first.to_doc(), "b": resumed.to_doc()}
    )
    want = full.to_doc()["hists"]["window_width_ns"]
    got = merged["hists"]["window_width_ns"]
    assert LogHistogram.from_doc(got) == LogHistogram.from_doc(want)
    # and the interleaved series carries every interval exactly once
    assert len(merged["series"]) == 10


def test_profile_doc_validates_and_rejects():
    r = ProfRecorder(8)
    _tick_n(r, 3)
    doc = r.to_doc(meta={"run": "t"})
    validate_profile_doc(doc)
    assert doc["kind"] == prof_mod.PROFILE_DOC_KIND
    assert doc["schema_version"] == prof_mod.PROFILE_SCHEMA_VERSION
    bad = dict(doc)
    bad["schema_version"] = doc["schema_version"] + 1
    with pytest.raises(ValueError, match="schema_version"):
        validate_profile_doc(bad)
    with pytest.raises(ValueError, match="intervals"):
        validate_profile_doc({**doc, "intervals": "nope"})


def test_align_series_orders_across_peers():
    a = ProfRecorder(8)
    _tick_n(a, 2)
    b = ProfRecorder(8)
    _tick_n(b, 2)
    da, db = a.to_doc(), b.to_doc()
    da["t0_unix"], db["t0_unix"] = 100.0, 100.5
    rows = align_series({"p1": da, "p2": db})
    assert len(rows) == 4
    assert [r["t_unix"] for r in rows] == sorted(r["t_unix"] for r in rows)
    assert {r["peer"] for r in rows} == {"p1", "p2"}


# ---------------------------------------------------------------------------
# critical-path attribution
# ---------------------------------------------------------------------------


def _skewed_doc(shards=3, laggard=1, n=6):
    """Synthetic profile: `laggard` always holds the minimum frontier and
    the other shards rack up blocked deltas."""
    import time

    r = ProfRecorder(64)
    look = [[NEVER] * shards for _ in range(shards)]
    for dst in range(shards):
        for src in range(shards):
            if src != dst:
                look[dst][src] = 5000 + dst
    for k in range(1, n + 1):
        time.sleep(0.001)  # keep d_wall_s above the 1 us rounding floor
        fr = [k * 1000 + 500 * s for s in range(shards)]
        fr[laggard] = k * 1000 - 999  # strictly the minimum
        blocked = [k * 3 if s != laggard else 0 for s in range(shards)]
        r.tick(vt_ns=k * 1000, events=k * 10, windows=k,
               supersteps=k * shards, blocked=sum(blocked),
               frontier_ns=fr, shard_blocked=blocked, lookahead_in=look)
    return r.to_doc()


def test_critical_path_names_laggard_and_link():
    cp = critical_path(_skewed_doc(shards=3, laggard=1))
    assert cp is not None
    assert cp["shards"] == 3
    assert cp["critical_shard"] == 1
    assert cp["wall_frac"] > 0
    link = cp["link"]
    assert link["src"] == 1 and link["dst"] != 1
    # the in-edge bound L[laggard -> victim] travels with the report
    assert link["lookahead_ns"] == 5000 + link["dst"]
    assert 0.0 < cp["blocked_frac"] < 1.0


def test_critical_path_none_without_shard_data():
    r = ProfRecorder(8)
    _tick_n(r, 4)
    assert critical_path(r.to_doc()) is None


# ---------------------------------------------------------------------------
# metrics integration (schema-current prof.* namespace)
# ---------------------------------------------------------------------------


def test_snapshot_prof_emits_namespace_and_validates(tmp_path):
    from shadow_tpu.obs import metrics as obs_metrics

    r = ProfRecorder(8)
    r.observe_wall("dispatch_wall_ns", 0.001)
    r.observe_wall("host_drain_wall_ns", 0.002)
    _tick_n(r, 3)
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.snapshot_prof(r, reg)
    path = str(tmp_path / "m.json")
    doc = reg.dump(path)
    assert_current_metrics_schema(doc)
    obs_metrics.validate_metrics_doc(doc, strict_namespaces=True)
    assert doc["counters"]["prof.intervals"] == 3
    assert doc["gauges"]["prof.dispatch_wall_ns_p50"] >= 1_000_000
    # atomic dump landed the final file, no tmp litter
    with open(path) as f:
        assert json.load(f) == doc
    assert list(tmp_path.iterdir()) == [tmp_path / "m.json"]


def _islands_cfg(shards=2, per=2, stop=6, seed=11):
    """Tiny async-islands config (the test_async_sync.py shape): one
    vertex per host, distinct cross-shard latencies for lookahead."""
    import numpy as np

    rng = np.random.RandomState(7)
    n = shards * per
    lines = ["graph ["]
    for v in range(n):
        lines.append(f"  node [ id {v} ]")
    for a in range(n):
        for b in range(a, n):
            lo, hi = ((700000, 900000) if a // per != b // per
                      else (30000, 250000))
            lines.append(
                f'  edge [ source {a} target {b} latency '
                f'"{int(rng.randint(lo, hi))} us" ]'
            )
    lines.append("]")
    hosts = {
        f"h{v:02d}": {
            "quantity": 1, "network_node_id": v, "app_model": "phold",
            "app_options": {"msgload": 1, "runtime": stop - 1,
                            "local_span": 1},
        }
        for v in range(n)
    }
    return {
        "general": {"stop_time": stop, "seed": seed},
        "network": {"graph": {"type": "gml", "inline": "\n".join(lines)}},
        "experimental": {
            "event_capacity": 1024, "events_per_host_per_window": 8,
            "outbox_slots": 8, "inbox_slots": 4,
            "num_shards": shards, "exchange_slots": 16,
        },
        "hosts": hosts,
    }


def test_profiled_run_keeps_chain_and_records():
    """The read-only contract on a real (tiny) islands run: attaching a
    profiling session changes NO simulation outcome, and the recorder
    sees handoff boundaries with a monotone committed frontier."""
    from shadow_tpu.obs import metrics as obs_metrics
    from shadow_tpu.sim import build_simulation

    plain = build_simulation(_islands_cfg())
    assert plain._async is True
    plain.run(windows_per_dispatch=64)

    prof = ProfRecorder(16)
    profiled = build_simulation(_islands_cfg())
    profiled.obs_session = obs_metrics.ObsSession(prof=prof)
    profiled.run(windows_per_dispatch=64)

    assert profiled.audit_chain() == plain.audit_chain()
    assert (profiled.counters()["events_committed"]
            == plain.counters()["events_committed"])
    assert prof.recorded > 0
    vts = [iv["vt_ns"] for iv in prof.intervals()]
    assert vts == sorted(vts)
    assert vts[-1] < NEVER  # the drained-pool NEVER frontier clamped
    validate_profile_doc(prof.to_doc())


def test_config_profiler_knobs():
    from shadow_tpu.core.config import ConfigError, load_config

    def cfg(**exp):
        return {
            "general": {"stop_time": 1},
            "network": {"graph": {"type": "1_gbit_switch"}},
            "experimental": exp,
            "hosts": {"h": {"quantity": 1}},
        }

    c = load_config(cfg(profiler=True, profiler_ring=64))
    assert c.experimental.profiler is True
    assert c.experimental.profiler_ring == 64
    assert load_config(cfg()).experimental.profiler is False
    with pytest.raises(ConfigError, match="profiler_ring"):
        load_config(cfg(profiler_ring=4))


# ---------------------------------------------------------------------------
# tools (loaded the way a log scraper invokes them)
# ---------------------------------------------------------------------------


def _load_tool(name):
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        name, pathlib.Path(__file__).parent.parent / "tools" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metrics_doc(tmp_path, fname, counters=None, gauges=None, meta=None):
    from shadow_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.MetricsRegistry()
    for k, v in (counters or {}).items():
        reg.counter_set(k, v)
    for k, v in (gauges or {}).items():
        reg.gauge_set(k, v)
    path = tmp_path / fname
    reg.dump(str(path), meta=meta)
    return str(path)


def test_perf_compare_directions_and_rc(tmp_path):
    pc = _load_tool("perf_compare")

    base = {"counters": {"engine.events_committed": 100},
            "gauges": {"prof.dispatch_wall_ns_p50": 1000,
                       "free.key": 7},
            "meta": {"wall_s": 10.0}}
    cand_ok = {"counters": {"engine.events_committed": 100},
               "gauges": {"prof.dispatch_wall_ns_p50": 1400,  # +40% < 50%
                          "free.key": 9},
               "meta": {"wall_s": 11.0}}
    r = pc.compare_docs(base, cand_ok)
    assert r["regressions"] == []
    assert {row["key"] for row in r["drift"]} == {
        "prof.dispatch_wall_ns_p50", "free.key", "meta.wall_s"
    }

    cand_bad = {"counters": {"engine.events_committed": 99},  # eq breach
                "gauges": {"prof.dispatch_wall_ns_p50": 1600},  # +60%
                "meta": {"wall_s": 20.0}}  # +100% > 50%
    r = pc.compare_docs(base, cand_bad)
    assert {row["key"] for row in r["regressions"]} == {
        "engine.events_committed", "prof.dispatch_wall_ns_p50",
        "meta.wall_s",
    }

    # end to end: identical docs exit 0, a determinism breach exits 1,
    # and --json emits ONE parseable line (log scrapers read per-line)
    a = _metrics_doc(tmp_path, "a.json",
                     counters={"engine.events_committed": 5})
    b = _metrics_doc(tmp_path, "b.json",
                     counters={"engine.events_committed": 5})
    c = _metrics_doc(tmp_path, "c.json",
                     counters={"engine.events_committed": 6})
    assert pc.main([a, b]) == 0
    assert pc.main([a, c]) == 1
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert pc.main([a, c, "--json"]) == 1
    out = buf.getvalue().strip()
    assert "\n" not in out
    parsed = json.loads(out)
    assert parsed["regressions"][0]["key"] == "engine.events_committed"


def test_perf_compare_skips_failed_and_cross_schema(tmp_path):
    pc = _load_tool("perf_compare")

    good = _metrics_doc(tmp_path, "g.json",
                        counters={"engine.events_committed": 5})
    # ok:false — the producing gate already failed; not a perf signal
    failed = _metrics_doc(tmp_path, "f.json",
                          counters={"engine.events_committed": 1},
                          meta={"ok": False})
    assert pc.main([good, failed]) == 0

    # stale schema artifact: numbers are not comparable, skip not gate
    stale = json.loads((tmp_path / "g.json").read_text())
    stale["schema_version"] -= 1
    (tmp_path / "stale.json").write_text(json.dumps(stale))
    assert pc.main([str(tmp_path / "stale.json"), good]) == 0

    # not a metrics doc at all
    (tmp_path / "junk.json").write_text('{"kind": "other"}')
    assert pc.main([str(tmp_path / "junk.json"), good]) == 0
    (tmp_path / "broken.json").write_text("{not json")
    assert pc.main([str(tmp_path / "broken.json"), good]) == 2


def test_trace_merge_aligns_peer_clocks(tmp_path):
    tm = _load_tool("trace_merge")

    def trace(t0, names):
        return {
            "metadata": {"format": "chrome-trace-events", "t0_unix": t0},
            "traceEvents": [
                {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                 "args": {"name": "shadow_tpu"}},
            ] + [
                {"name": n, "ph": "X", "pid": 0, "tid": 0,
                 "ts": 100.0 * i, "dur": 50.0}
                for i, n in enumerate(names)
            ],
        }

    docs = {"a": trace(100.0, ["dispatch", "host_drain"]),
            "b": trace(101.5, ["dispatch"])}
    fused = tm.merge_traces(docs)
    peers = fused["metadata"]["peers"]
    assert peers["a"]["pid"] == 1 and peers["b"]["pid"] == 2
    assert peers["a"]["offset_us"] == 0.0
    assert peers["b"]["offset_us"] == 1.5e6  # +1.5 s behind the anchor
    b_spans = [e for e in fused["traceEvents"]
               if e.get("ph") == "X" and e["pid"] == 2]
    assert b_spans[0]["ts"] == 1.5e6  # shifted onto the shared clock
    # original process_name rows replaced by peer-named ones
    names = {e["args"]["name"] for e in fused["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert names == {"a", "b"}

    # end to end through the CLI, stem-named inputs
    pa, pb = tmp_path / "pa.trace.json", tmp_path / "pb.trace.json"
    pa.write_text(json.dumps(docs["a"]))
    pb.write_text(json.dumps(docs["b"]))
    out = tmp_path / "fused.json"
    assert tm.main([str(pa), str(pb), "-o", str(out)]) == 0
    assert json.loads(out.read_text())["metadata"]["merged"] is True
    (tmp_path / "bad.json").write_text('{"no": "traceEvents"}')
    assert tm.main([str(tmp_path / "bad.json"), "-o", str(out)]) == 2


def test_trace_summary_percentiles():
    ts = _load_tool("trace_summary")

    doc = {"traceEvents": [
        {"name": "dispatch", "ph": "X", "ts": 0, "dur": d}
        for d in (1000.0, 2000.0, 3000.0, 4000.0)  # us
    ] + [
        {"name": "host_drain", "ph": "X", "ts": 0, "dur": 10000.0},
        {"name": "meta", "ph": "M"},
    ]}
    rows = ts.percentiles(doc)
    assert [r["name"] for r in rows] == ["host_drain", "dispatch"]
    d = rows[1]
    assert d["count"] == 4
    assert d["p50_ms"] == 2.0  # nearest rank: 2nd of 4
    assert d["p99_ms"] == 4.0
    assert ts.percentiles({"traceEvents": []}) == []


def test_shadowctl_render_top():
    ctl = _load_tool("shadowctl")

    assert ctl._fmt_ns(512) == "512ns"
    assert ctl._fmt_ns(1_500) == "1.5us"
    assert ctl._fmt_ns(2_500_000) == "2.5ms"
    assert ctl._fmt_ns(3_000_000_000) == "3.00s"

    frame = ctl.render_top(_skewed_doc())
    assert "shadowscope top" in frame
    assert "window_width_ns" in frame
    assert "critical" in frame

    # the router's merged document renders with the peer header
    a, b = ProfRecorder(8), ProfRecorder(8)
    _tick_n(a, 2)
    _tick_n(b, 3)
    merged = merge_profile_docs({"pa": a.to_doc(), "pb": b.to_doc()})
    frame = ctl.render_top(merged)
    assert "2 peer(s)" in frame
    assert "pa(2iv)" in frame and "pb(3iv)" in frame
