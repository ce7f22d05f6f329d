"""Observability plane (repro.obs): metrics registry, tracer, stage
profiler, TracingSink, self-monitoring — units plus the pipeline-level
acceptance paths (one pushed document = one cross-plane trace; a
dead-letter flood fires a __health__ alert through the ordinary rule
engine; replay_status() itemizes the batch chain)."""
import json
import math
import os

import pytest
from _hyp import given, settings, st

from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Observability,
    StageProfiler,
    TraceExporter,
    Tracer,
    TracingSink,
)


# ---------------------------------------------------------------- registry
def test_counter_inc_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("fetches_total", "fetches")
    c.inc(1, connector="sim")
    c.inc(2, connector="sim")
    c.inc(5, connector="push")
    assert c.value(connector="sim") == 3
    assert c.value(connector="push") == 5
    assert c.total() == 8
    with pytest.raises(ValueError):
        c.inc(-1, connector="sim")


def test_counter_sync_is_monotonic_set_to_max():
    c = Counter("adopted_total")
    c.sync(10)
    c.sync(7)          # stale read must not regress the series
    assert c.value() == 10
    c.sync(12)
    assert c.value() == 12


def test_gauge_set_add():
    g = Gauge("depth")
    g.set(4, backend="es")
    g.add(2, backend="es")
    assert g.value(backend="es") == 6


def test_histogram_quantiles_and_summary():
    h = Histogram("lat", min_bound=1e-3, base=2.0, num_buckets=20)
    for v in [0.001, 0.002, 0.004, 0.008, 0.1]:
        h.observe(v)
    assert h.count() == 5
    assert h.sum() == pytest.approx(0.115)
    # p50 resolves to a bucket upper bound >= the true median
    assert 0.002 <= h.quantile(0.5) <= 0.008
    # the max caps the top quantile (never reports +Inf)
    assert h.quantile(1.0) <= 0.1 + 1e-9
    s = h.summary()
    assert s["count"] == 5 and s["min"] == 0.001 and s["max"] == 0.1
    assert Histogram("empty").quantile(0.99) == 0.0


def test_histogram_quantile_log_bucket_relative_error():
    """Log buckets (base b) report a quantile as the containing bucket's
    upper bound: true <= reported <= b * true, across magnitudes."""
    for mag in (1e-5, 1e-3, 1e-1, 10.0, 1e3):
        h = Histogram("lat")                   # defaults: 1e-6, base 2
        vals = [mag * (1.0 + i / 100.0) for i in range(100)]
        for v in vals:
            h.observe(v)
        ref = sorted(vals)
        for q in (0.1, 0.5, 0.9, 0.99):
            true = ref[max(0, -(-int(q * 100) // 1) - 1)]
            got = h.quantile(q)
            assert true <= got * (1 + 1e-9), (mag, q)
            assert got <= 2.0 * true * (1 + 1e-9), (mag, q)


def test_histogram_quantile_edge_cases():
    # a value exactly on a bucket bound stays in that bucket (le
    # semantics): the reported quantile is exact
    h = Histogram("lat", min_bound=1e-3, base=2.0, num_buckets=10)
    h.observe(0.004)                           # == bounds[2]
    assert h.quantile(0.5) == 0.004
    # single observation: every quantile is that observation (max-cap)
    h2 = Histogram("one")
    h2.observe(0.37)
    for q in (0.01, 0.5, 0.99, 1.0):
        assert h2.quantile(q) == pytest.approx(0.37)
    # q=1.0 is the observed max, never a bucket bound above it
    h3 = Histogram("many")
    for v in (0.1, 0.2, 0.9):
        h3.observe(v)
    assert h3.quantile(1.0) == pytest.approx(0.9)
    # values below min_bound land in bucket 0; max still caps
    h4 = Histogram("tiny", min_bound=1e-3)
    h4.observe(1e-9)
    assert h4.quantile(0.5) == pytest.approx(1e-9)
    with pytest.raises(ValueError):
        h3.quantile(0.0)
    with pytest.raises(ValueError):
        h3.quantile(1.1)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(min_value=1e-6, max_value=1e5,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=200),
       st.floats(min_value=0.01, max_value=1.0))
def test_histogram_quantile_hypothesis_roundtrip(vals, q):
    h = Histogram("lat")
    for v in vals:
        h.observe(v)
    ref = sorted(vals)
    true = ref[max(0, math.ceil(q * len(vals)) - 1)]
    got = h.quantile(q)
    # containing-bucket upper bound, capped by the observed max: never
    # under-reports, never over by more than one bucket ratio
    assert got * (1 + 1e-9) >= true
    assert got <= max(2.0 * true, 1e-6) * (1 + 1e-9)
    assert got <= ref[-1] * (1 + 1e-9)


def test_histogram_observe_batch_matches_sequential():
    a = Histogram("a")
    b = Histogram("b")
    vals = [0.001, 0.5, 3.0, 3.0, 120.0, 1e-9]
    for v in vals:
        a.observe(v, plane="x")
    b.observe_batch(vals, plane="x")
    assert a.summary(plane="x") == b.summary(plane="x")
    assert b.count(plane="x") == len(vals)
    b.observe_batch([], plane="x")            # no-op
    assert b.count(plane="x") == len(vals)


def test_registry_kind_conflict_and_get_or_create():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")
    assert "x" in reg and "y" not in reg


def test_registry_collector_runs_before_snapshot():
    reg = MetricsRegistry()
    external = {"total": 0}
    reg.add_collector(
        lambda: reg.counter("ext_total").sync(external["total"]))
    external["total"] = 42
    snap = reg.snapshot()
    assert snap["counters"]["ext_total"]["series"][0]["value"] == 42


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests").inc(3, route="/a")
    reg.gauge("depth").set(2)
    reg.histogram("lat", "latency", min_bound=1e-3,
                  num_buckets=4).observe(0.002)
    text = reg.render_prometheus()
    assert "# HELP reqs_total requests" in text
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{route="/a"} 3' in text
    assert "# TYPE depth gauge" in text and "depth 2" in text
    assert "# TYPE lat histogram" in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_count 1" in text and "lat_sum 0.002" in text
    # cumulative buckets: counts never decrease down the ladder
    counts = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("lat_bucket")]
    assert counts == sorted(counts)


def test_snapshot_is_json_safe():
    reg = MetricsRegistry()
    reg.counter("a").inc(1)
    reg.gauge("b").set(2, k="v")
    reg.histogram("c").observe(0.5)
    json.dumps(reg.snapshot())      # must not raise


# ---------------------------------------------------------------- tracer
def test_tracer_disabled_is_noop():
    tr = Tracer(sample_rate=0.0)
    with tr.span("work") as sp:
        assert sp.trace_id is None
        sp.set("k", "v")            # no-op, no raise
    assert tr.spans() == [] and not tr.enabled


def test_tracer_sampling_all_and_nesting():
    tr = Tracer(sample_rate=1.0)
    with tr.span("root") as root:
        assert root.sampled and root.trace_id
        with tr.span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
    spans = tr.trace(root.trace_id)
    assert [s.name for s in spans] == ["root", "child"]
    assert all(s.duration_ms >= 0.0 for s in spans)


def test_tracer_partial_sampling_is_deterministic():
    a = Tracer(sample_rate=0.5, seed=7)
    b = Tracer(sample_rate=0.5, seed=7)
    hits_a = []
    hits_b = []
    for _ in range(50):
        with a.span("r") as sa:
            hits_a.append(sa.sampled)
        with b.span("r") as sb:
            hits_b.append(sb.sampled)
    assert hits_a == hits_b
    assert 0 < sum(hits_a) < 50
    # children of an unsampled root stay unsampled (no orphan spans)
    assert all(s.parent_id is None for s in a.spans())


def test_tracer_flight_recorder_is_bounded():
    tr = Tracer(sample_rate=1.0, capacity=8)
    for _ in range(50):
        with tr.span("w"):
            pass
    assert len(tr.spans()) == 8
    st = tr.status()
    assert st["finished_spans"] == 50 and st["flight_spans"] == 8


def test_tracer_error_capture():
    tr = Tracer(sample_rate=1.0)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("nope")
    assert "RuntimeError" in tr.spans()[-1].error


def test_trace_exporter_roundtrip_and_roll(tmp_path):
    d = str(tmp_path / "spans")
    exp = TraceExporter(d, max_bytes=200)    # force rolls
    tr = Tracer(sample_rate=1.0, exporter=exp)
    for i in range(10):
        with tr.span("w") as sp:
            sp.set("i", i)
    exp.close()
    back = list(exp.scan())
    assert len(back) == 10
    assert [s["attrs"]["i"] for s in back] == list(range(10))
    assert len(os.listdir(d)) > 1            # rolled at least once


def test_trace_exporter_scan_across_rolled_files_in_order():
    """scan() stitches multiple size-rolled files (and files from a
    previous exporter generation) back in append order."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        exp = TraceExporter(d, max_bytes=150)
        tr = Tracer(sample_rate=1.0, exporter=exp)
        for i in range(20):
            with tr.span("w") as sp:
                sp.set("i", i)
        exp.close()
        assert len(os.listdir(d)) >= 3
        # reopen: a NEW file continues the sequence
        exp2 = TraceExporter(d, max_bytes=150)
        tr2 = Tracer(sample_rate=1.0, exporter=exp2)
        with tr2.span("w") as sp:
            sp.set("i", 20)
        exp2.close()
        assert [s["attrs"]["i"] for s in exp2.scan()] == list(range(21))


def test_trace_exporter_skips_torn_final_line(tmp_path):
    """Crash mid-append leaves a torn final line; reopen + scan skip it
    (reopen always starts a new file, so a torn line is only ever a
    file's tail) — the store plane's crash-tolerance standard."""
    d = str(tmp_path / "spans")
    exp = TraceExporter(d)
    tr = Tracer(sample_rate=1.0, exporter=exp)
    for i in range(3):
        with tr.span("w") as sp:
            sp.set("i", i)
    exp.close()
    fname = sorted(os.listdir(d))[-1]
    with open(os.path.join(d, fname), "a", encoding="utf-8") as fh:
        fh.write('{"trace_id": "t-torn", "na')     # torn mid-record
    exp2 = TraceExporter(d)                        # reopen after "crash"
    tr2 = Tracer(sample_rate=1.0, exporter=exp2)
    with tr2.span("w") as sp:
        sp.set("i", 3)
    exp2.close()
    back = list(exp2.scan())
    assert [s["attrs"]["i"] for s in back] == [0, 1, 2, 3]
    assert exp2.torn_skipped == 1


def test_trace_exporter_corrupt_middle_line_still_raises(tmp_path):
    """Only a file's FINAL line can be a crash artifact; corruption in
    the middle is real damage and must not be silently skipped."""
    d = str(tmp_path / "spans")
    exp = TraceExporter(d)
    tr = Tracer(sample_rate=1.0, exporter=exp)
    for _ in range(2):
        with tr.span("w"):
            pass
    exp.close()
    fname = sorted(os.listdir(d))[-1]
    path = os.path.join(d, fname)
    lines = open(path, encoding="utf-8").read().splitlines()
    lines[0] = '{"broken'
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("\n".join(lines) + "\n")
    with pytest.raises(ValueError):
        list(TraceExporter(d).scan())


# ---------------------------------------------------------------- profiler
def test_stage_profiler_breakdown():
    prof = StageProfiler()
    for _ in range(3):
        with prof.stage("pack"):
            pass
    prof.record("kernel", 0.5)
    snap = prof.snapshot()
    assert snap["pack"]["calls"] == 3
    assert snap["kernel"]["total_ms"] == pytest.approx(500.0)
    assert sum(s["share"] for s in snap.values()) == pytest.approx(1.0)
    prof.reset()
    assert prof.snapshot() == {}


def test_nested_stages_keep_top_level_shares():
    """A dotted stage is a sub-stage: it is timed inside its parent,
    whose total still covers it, and ``share`` stays a share of the
    top-level stages only."""
    import time

    prof = StageProfiler("replay")
    for _ in range(2):
        with prof.stage("pack_events"):
            for sub in ("assign", "unique", "slots"):
                with prof.stage(f"pack_events.{sub}"):
                    time.sleep(0.001)
        with prof.stage("kernel"):
            for sub in ("dispatch", "wait", "fetch"):
                with prof.stage(f"kernel.{sub}"):
                    time.sleep(0.001)
    snap = prof.snapshot()
    assert sum(s["share"] for name, s in snap.items()
               if "." not in name) == pytest.approx(1.0)
    for parent in ("pack_events", "kernel"):
        subs = [s for name, s in snap.items()
                if name.startswith(parent + ".")]
        assert len(subs) == 3 and all(s["calls"] == 2 for s in subs)
        assert sum(s["total_ms"] for s in subs) <= snap[parent]["total_ms"]
        assert sum(s["share"] for s in subs) <= snap[parent]["share"]


def test_recent_passes_are_a_bounded_ring_on_perf_counter(monkeypatch):
    import collections
    import time

    from repro.obs import profiler

    assert profiler.RECENT_PASSES >= 16384
    assert profiler._RING.maxlen == profiler.RECENT_PASSES
    monkeypatch.setattr(profiler, "_RING", collections.deque(maxlen=8))
    prof = StageProfiler("ring")
    t_before = time.perf_counter()
    for i in range(20):
        with prof.stage(f"s{i}"):
            pass
    prof.record("ext", 0.25)
    t_after = time.perf_counter()
    passes = profiler.recent_passes()
    assert len(passes) == 8
    assert [st for _, st, _, _ in passes] == [
        "s13", "s14", "s15", "s16", "s17", "s18", "s19", "ext"]
    for name, _, start, secs in passes[:-1]:
        assert name == "ring"
        assert t_before <= start <= start + secs <= t_after
    _, _, start, secs = passes[-1]            # externally timed: ends now
    assert secs == 0.25 and start + secs <= t_after


def test_importing_obs_does_not_import_jax():
    """Observability stays JAX-free: a stage pass before JAX is loaded
    opens no annotation and still lands in the ring."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from repro.obs import StageProfiler, recent_passes\n"
            "with StageProfiler('p').stage('s'):\n"
            "    pass\n"
            "assert [p[:2] for p in recent_passes()] == [('p', 's')]\n"
            "assert 'jax' not in sys.modules, 'repro.obs imported jax'\n")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert res.returncode == 0, res.stderr


def test_sampled_spans_are_host_events_on_the_profiler_trace(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.obs.trace import _DISABLED_CTX

    off = Tracer(sample_rate=0.0)
    assert off.span("query.cold_scan") is _DISABLED_CTX   # shared no-op
    tr = Tracer(sample_rate=1.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("query.cold_scan"):
            with tr.span("replay.late_events"):
                pass
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for pl in ProfileData.from_file(files[-1]).planes
             if pl.name.startswith("/host:") for ln in pl.lines
             for e in ln.events}
    assert {"query.cold_scan", "replay.late_events"} <= names


# ---------------------------------------------------------------- sink
def test_tracing_sink_joins_record_traces():
    from repro.delivery import CollectingSink

    tr = Tracer(sample_rate=1.0)
    term = CollectingSink("es")
    sink = TracingSink(term, tr, name=term.name)
    sink.emit([("d1", {"title": "x", "trace": "t-abc"}),
               ("d2", {"title": "y"})])          # untraced rides along
    assert len(term) == 2
    spans = [s for s in tr.spans() if s.name == "delivery.write"]
    assert len(spans) == 1
    assert spans[0].trace_id == "t-abc"
    assert spans[0].attrs == {"backend": "es", "records": 1, "batch": 2}


# ----------------------------------------------------- pipeline integration
from repro.core.pipeline import AlertMixPipeline, Metrics, PipelineConfig


def test_tracing_off_by_default_no_doc_mutation():
    from repro.delivery import CollectingSink

    term = CollectingSink("docs")
    p = AlertMixPipeline(PipelineConfig(num_sources=0), seed=0,
                         sinks=[term])
    sid = p.add_source("news", connector="push")
    p.push(sid, [{"title": "t", "body": "b", "published_at": 1.0}])
    p.run_for(30)
    assert len(term) == 1
    _, doc = term.records[0]
    assert "trace" not in doc
    assert p.tracer.status()["finished_spans"] == 0


def test_single_document_trace_covers_all_planes(tmp_path):
    """Acceptance: one pushed document yields one trace whose spans
    cover ingest, pipeline, store, and delivery, joined by trace_id."""
    p = AlertMixPipeline(
        PipelineConfig(num_sources=0, trace_sample_rate=1.0,
                       store_dir=str(tmp_path / "store")), seed=0)
    sid = p.add_source("news", connector="push")
    p.push(sid, [{"title": "t", "body": "b", "published_at": 1.0}])
    p.run_for(30)
    doc_traces = [spans for spans in p.tracer.traces().values()
                  if any(s.name == "ingest.fetch" for s in spans)
                  and any(s.attrs.get("status") == "ok" for s in spans)]
    assert len(doc_traces) == 1
    names = [s.name for s in doc_traces[0]]
    for plane_span in ("ingest.fetch", "pipeline.process", "store.append",
                       "delivery.write"):
        assert plane_span in names, f"missing {plane_span} in {names}"
    assert len({s.trace_id for s in doc_traces[0]}) == 1
    p.close()


def test_trace_export_dir_persists_spans(tmp_path):
    export = str(tmp_path / "traces")
    p = AlertMixPipeline(
        PipelineConfig(num_sources=0, trace_sample_rate=1.0,
                       trace_export_dir=export), seed=0)
    sid = p.add_source("news", connector="push")
    p.push(sid, [{"title": "t", "body": "b", "published_at": 1.0}])
    p.run_for(30)
    p.close()
    exported = list(p.tracer.exporter.scan())
    assert any(s["name"] == "delivery.write" for s in exported)


def test_metrics_series_ring_is_bounded():
    m = Metrics(history=4)
    for i in range(10):
        m.sent.append((float(i), 1))
    assert len(m.sent) == 4
    assert list(m.sent)[0] == (6.0, 1)       # oldest dropped, newest kept
    # pipeline wires the config bound through
    p = AlertMixPipeline(
        PipelineConfig(num_sources=5, metrics_history=3), seed=0)
    p.run_for(1200)
    assert len(p.metrics.sent) <= 3
    assert len(p.metrics.received) <= 3
    # unbounded stays a plain list (seed behaviour)
    assert isinstance(Metrics().sent, list)


def test_connector_stats_is_registry_view():
    """Satellite: the old dict-of-dicts is gone; connector_stats() is
    assembled from the registry counters and keeps its exact shape."""
    p = AlertMixPipeline(PipelineConfig(num_sources=20), seed=1)
    p.run_for(600)
    st = p.connector_stats()
    assert set(st) == {"sim"}
    assert set(st["sim"]) == {"fetches", "items", "not_modified", "errors",
                              "backoffs", "deferred_s"}
    reg = p.obs.metrics
    assert st["sim"]["fetches"] == reg.counter(
        "ingest_fetches_total").value(connector="sim")
    assert st["sim"]["items"] == reg.counter(
        "ingest_items_total").value(connector="sim")
    assert not hasattr(p, "_connector_stats")
    assert not hasattr(p, "_cstats_lock")
    # the fetch-latency histogram saw every fetch
    assert reg.histogram("ingest_fetch_seconds").count(
        connector="sim") == st["sim"]["fetches"]


def test_pipeline_exposition_covers_every_plane():
    p = AlertMixPipeline(PipelineConfig(num_sources=10), seed=0)
    p.run_for(600)
    text = p.metrics_text()
    for name in ("ingest_fetches_total", "docs_indexed_total",
                 "delivery_emitted_total", "delivery_lag",
                 "scheduler_picked_total", "pool_size",
                 "dead_letters_total", "trace_flight_spans"):
        assert f"# TYPE {name} " in text, f"missing {name}"
    json.dumps(p.metrics_snapshot())


def test_selfmon_dead_letter_flood_fires_health_alert():
    """Acceptance: an injected dead-letter flood fires a __health__
    alert through the ordinary rule engine."""
    p = AlertMixPipeline(
        PipelineConfig(num_sources=0, selfmon_interval_s=60.0,
                       allowed_lateness_s=0.0, watermark_lag_s=0.0,
                       selfmon_dead_letter_threshold=50.0), seed=0)
    for i in range(200):
        p.dead_letters.publish({"i": i}, reason="malformed_item")
    p.run_for(1500)
    fired = [a for a in p.alerts if a.rule == "selfmon_dead_letter_flood"]
    assert fired, f"no flood alert; fired={[a.rule for a in p.alerts]}"
    assert fired[0].key == "__health__.dead_letters_total.malformed_item"
    assert fired[0].value >= 50.0
    assert p.obs_status()["selfmon"]["samples"] > 0


def test_selfmon_counters_publish_deltas_not_totals():
    from repro.obs.selfmon import MetricsConnector

    reg = MetricsRegistry()
    reg.counter("x_total").inc(10)
    conn = MetricsConnector(reg, include=["x_total"])
    first = conn.fetch(None, None, 0.0)
    assert first.items[0].extra["value"] == 10.0
    conn.fetch(None, None, 1.0)      # no growth -> zero delta
    reg.counter("x_total").inc(3)
    third = conn.fetch(None, None, 2.0)
    assert third.items[0].extra["value"] == 3.0
    assert third.items[0].extra["key"] == "__health__.x_total"


def test_selfmon_rules_scoped_off_product_channels():
    """Health rules never fire on product keys and product rules never
    fire on __health__ keys (key_prefix scoping)."""
    from repro.alerts import ThresholdRule

    p = AlertMixPipeline(
        PipelineConfig(num_sources=0, selfmon_interval_s=60.0,
                       allowed_lateness_s=0.0, watermark_lag_s=0.0),
        seed=0,
        analytics_rules=[ThresholdRule("product_vol", metric="count",
                                       op=">=", threshold=1.0,
                                       key_prefix="news")])
    sid = p.add_source("news", connector="push")
    p.push(sid, [{"title": "t", "body": "b", "published_at": 100.0}])
    p.run_for(1200)
    by_rule = {}
    for a in p.alerts:
        by_rule.setdefault(a.rule, []).append(a.key)
    assert all(k.startswith("news") for k in by_rule.get("product_vol", []))
    for rule, keys in by_rule.items():
        if rule.startswith("selfmon_"):
            assert all(k.startswith("__health__.") for k in keys)


def test_replay_status_reports_stage_profile(tmp_path):
    """Acceptance: replay_status() itemizes the batch chain per stage."""
    from repro.alerts import AnalyticsStage, ThresholdRule, WindowSpec
    from repro.store import ReplayEngine

    stage = AnalyticsStage(
        WindowSpec(kind="tumbling", size_s=60.0),
        [ThresholdRule("vol", metric="count", op=">=", threshold=1.0)])
    eng = ReplayEngine(analytics=stage)
    eng.replay_events([("news", 10.0, 1.0), ("news", 20.0, 2.0)],
                      watermark=1e9)
    prof = eng.status()["profile"]
    for stage_name in ("pack_events", "kernel", "unpack", "state_merge"):
        assert stage_name in prof, f"missing stage {stage_name}"
        assert prof[stage_name]["calls"] == 1
        assert prof[stage_name]["total_ms"] >= 0.0
    # the kernel stage is itemized per launch (max lane, then min lane);
    # shares are of the top-level stages, which add up to 1
    for sub in ("kernel.dispatch", "kernel.wait", "kernel.fetch"):
        assert prof[sub]["calls"] == 2
        assert prof[sub]["share"] <= prof["kernel"]["share"]
    assert sum(s["share"] for name, s in prof.items()
               if "." not in name) == pytest.approx(1.0)
    # the pipeline surface carries it too
    p = AlertMixPipeline(
        PipelineConfig(num_sources=0, store_dir=str(tmp_path / "s")),
        seed=0)
    assert "profile" in p.replay_status()
    p.close()


def test_replay_stage_profile_exported_as_registry_gauges(tmp_path):
    """Satellite: the replay StageProfiler breakdown is visible in
    metrics_text() scrapes, not just replay_status()['profile']."""
    p = AlertMixPipeline(
        PipelineConfig(num_sources=0, analytics=True,
                       store_dir=str(tmp_path / "s")), seed=0)
    p.store.replay.replay_events(
        [("news", 10.0, 1.0), ("news", 20.0, 2.0)], watermark=1e9)
    text = p.metrics_text()
    for stage in ("pack_events", "kernel", "unpack", "state_merge"):
        assert f'replay_stage_share{{stage="{stage}"}}' in text, stage
        assert f'replay_stage_calls_total{{stage="{stage}"}}' in text
    reg = p.obs.metrics
    shares = [v for _, v in reg.gauge("replay_stage_share").items()]
    assert sum(shares) == pytest.approx(1.0)
    assert reg.counter("replay_stage_calls_total").value(
        stage="kernel") == 1
    p.close()


def test_pack_slot_index_paths_exported_after_columnar_replay(tmp_path):
    """A columnar log replay packs its columns once; the path its slot
    index took is counted, process-wide, and every scrape exports
    ``pack_slot_index_total{path}`` beside ``kernel_launches_total``."""
    from repro.obs import pack_slot_index

    p = AlertMixPipeline(
        PipelineConfig(num_sources=0, analytics=True, store_columnar=True,
                       store_dir=str(tmp_path / "s")), seed=0)
    try:
        p.store.append_documents(
            [(f"d{i}", {"title": "t", "published_at": float(i % 600),
                        "channel": "news" if i % 3 else "sports"})
             for i in range(120)])
        p.store.log.roll()
        before = pack_slot_index()
        res = p.store.replay.replay_log(0, watermark=1e9)
        after = pack_slot_index()
        assert res["columnar"] and res["events"] == 120
        assert after["dense"] - before["dense"] == 1
        assert after["sort"] == before["sort"]
        text = p.metrics_text()
        for path in ("dense", "sort"):
            assert f'pack_slot_index_total{{path="{path}"}}' in text
        assert 'kernel_launches_total{kernel="window_reduce",' \
               'route="replay"}' in text
        assert p.obs.metrics.counter("pack_slot_index_total").value(
            path="dense") == after["dense"]
    finally:
        p.close()


def test_unpack_order_exported_after_columnar_replay(tmp_path):
    """A columnar log replay unpacks the kernel's lanes once: its keys,
    coded as first seen (``sports`` before ``news``), are not in key
    order, so the slots are counted ``sorted``; every scrape exports
    ``unpack_order_total{order}`` beside ``pack_slot_index_total``."""
    from repro.obs import unpack_order

    p = AlertMixPipeline(
        PipelineConfig(num_sources=0, analytics=True, store_columnar=True,
                       store_dir=str(tmp_path / "s")), seed=0)
    try:
        p.store.append_documents(
            [(f"d{i}", {"title": "t", "published_at": float(i % 600),
                        "channel": "news" if i % 3 else "sports"})
             for i in range(120)])
        p.store.log.roll()
        before = unpack_order()
        p.store.replay.replay_log(0, watermark=1e9)
        after = unpack_order()
        assert after["sorted"] - before["sorted"] == 1
        assert after["slot"] == before["slot"]
        text = p.metrics_text()
        for order in ("slot", "sorted"):
            assert f'unpack_order_total{{order="{order}"}}' in text
        assert 'pack_slot_index_total{path="dense"}' in text
        assert p.obs.metrics.counter("unpack_order_total").value(
            order="sorted") == after["sorted"]
    finally:
        p.close()


def test_rule_engine_add_rule_rejects_duplicates():
    from repro.alerts import RuleEngine, ThresholdRule

    eng = RuleEngine([ThresholdRule("a")])
    eng.add_rule(ThresholdRule("b"))
    with pytest.raises(ValueError):
        eng.add_rule(ThresholdRule("a"))


def test_observability_bundle_status_and_close(tmp_path):
    obs = Observability(sample_rate=1.0, export_dir=str(tmp_path / "t"))
    with obs.tracer.span("w"):
        pass
    st = obs.status()
    assert st["tracer"]["sampled_traces"] == 1
    assert isinstance(st["metrics"], tuple)
    obs.close()


# ---- property: observe_batch ≡ observe loop ---------------------------------

def _assert_batch_equiv(batches):
    """One histogram fed via observe_batch, one via an observe loop:
    bucket counts / count / min / max must match exactly; sum is float
    addition in a different association order, so approximately."""
    h_batch = Histogram("h", "d")
    h_loop = Histogram("h", "d")
    for vals in batches:
        h_batch.observe_batch(vals, plane="p")
        for v in vals:
            h_loop.observe(v, plane="p")
    sa = {k: (s.counts, s.count, s.min, s.max, s.sum)
          for k, s in h_batch._series.items()}
    sb = {k: (s.counts, s.count, s.min, s.max, s.sum)
          for k, s in h_loop._series.items()}
    assert set(sa) == set(sb)
    for k in sa:
        ca, na, mina, maxa, suma = sa[k]
        cb, nb, minb, maxb, sumb = sb[k]
        assert ca == cb and na == nb and mina == minb and maxa == maxb
        assert math.isclose(suma, sumb, rel_tol=1e-9, abs_tol=1e-12)


def test_observe_batch_matches_loop_concrete():
    _assert_batch_equiv([
        [1e-9, 5e-7, 1e-6],        # below/at the first bucket bound
        [0.001, 0.02, 0.5, 3.0],
        [1e9, 7.25],               # beyond the last bound -> inf bucket
        [0.25] * 40,
    ])


from _hyp import given, settings, st  # noqa: E402


@settings(max_examples=50, deadline=None)
@given(st.lists(
    st.lists(st.floats(min_value=1e-9, max_value=1e12,
                       allow_nan=False, allow_infinity=False),
             min_size=1, max_size=50),
    min_size=1, max_size=10))
def test_observe_batch_matches_loop_property(batches):
    _assert_batch_equiv(batches)
