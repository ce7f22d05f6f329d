"""repro.store behaviour: event-log append/scan/truncate + checksums,
kill-and-reopen torn-tail recovery, the dead-letter journal (+ reason
taxonomy contracts), replay parity with the live path THROUGH the
on-disk log, idempotent partial-delivery replay, and pipeline-level
outage -> journal -> recovery -> auto-replay acceptance."""
import json
import os
import threading

import numpy as np
import pytest

from repro.alerts import (
    AnalyticsStage,
    RateOfChangeRule,
    ThresholdRule,
    WindowOperator,
    WindowSpec,
    ZScoreRule,
)
from repro.core import AlertMixPipeline, DeadLettersListener, PipelineConfig
from repro.core.dead_letters import REASON_FAMILIES, reason_in_taxonomy
from repro.core.sinks import IndexSink
from repro.delivery import CollectingSink, RetryingSink, Sink
from repro.store import (
    CorruptSegmentError,
    DeadLetterJournal,
    EventLog,
    ReplayEngine,
    StorePlane,
    json_safe,
)


class OutageSink(Sink):
    """Terminal sink with a switchable outage."""

    def __init__(self, name=None):
        super().__init__(name)
        self.down = False
        self.records = []

    def _write(self, batch):
        if self.down:
            raise IOError("injected outage")
        self.records.extend(batch)


# ---------------------------------------------------------------------------
# EventLog: append / scan / roll / truncate
# ---------------------------------------------------------------------------

def test_log_append_scan_roundtrip(tmp_path):
    log = EventLog(str(tmp_path / "log"))
    first, last = log.append([{"i": i} for i in range(5)])
    assert (first, last) == (0, 4)
    first, last = log.append([{"i": 5}])
    assert (first, last) == (5, 5)
    assert log.append([]) == (6, 5)              # empty batch: no-op sentinel
    recs = list(log.scan(0))
    assert [o for o, _ in recs] == list(range(6))
    assert [p["i"] for _, p in recs] == list(range(6))
    assert [o for o, _ in log.scan(4)] == [4, 5]  # offset filter
    st = log.status()
    assert st["appended_records"] == 6 and st["appended_bytes"] > 0


def test_log_segments_roll_by_size_and_age(tmp_path):
    log = EventLog(str(tmp_path / "log"), segment_bytes=120,
                   segment_age_s=60.0)
    log.append([{"pad": "x" * 100}])             # > 120 bytes: sealed at once
    assert log.stats.sealed_segments == 1
    log.append([{"i": 1}])                       # small: stays active
    assert log.stats.sealed_segments == 1 and log.segments == 2
    log.tick(30.0)
    assert log.stats.sealed_segments == 1        # not old enough
    log.tick(61.0)
    assert log.stats.sealed_segments == 2        # age roll sealed it
    # sealed files + manifest agree and scan still sees everything
    man = json.load(open(tmp_path / "log" / "manifest.json"))
    assert len(man["segments"]) == 2
    assert [o for o, _ in log.scan(0)] == [0, 1]


def test_log_truncate_whole_segments_only(tmp_path):
    log = EventLog(str(tmp_path / "log"), segment_bytes=1)  # seal every batch
    for i in range(4):
        log.append([{"i": 2 * i}, {"i": 2 * i + 1}])        # segments of 2
    assert log.stats.sealed_segments == 4
    freed = log.truncate(3)                      # seg [0,1] fully below 3
    assert freed == 2 and log.truncated_through == 2
    assert [o for o, _ in log.scan(0)] == [2, 3, 4, 5, 6, 7]
    assert len(log) == 6
    # truncate persists across reopen
    log.close()
    log2 = EventLog(str(tmp_path / "log"), segment_bytes=1)
    assert log2.truncated_through == 2 and log2.next_offset == 8
    assert [o for o, _ in log2.scan(0)] == [2, 3, 4, 5, 6, 7]


def test_log_reopen_continues_offsets(tmp_path):
    with EventLog(str(tmp_path / "log")) as log:
        log.append([{"i": i} for i in range(7)])
    log2 = EventLog(str(tmp_path / "log"))
    assert log2.next_offset == 7
    assert log2.append([{"i": 7}]) == (7, 7)
    assert [o for o, _ in log2.scan(0)] == list(range(8))


# ---------------------------------------------------------------------------
# crash tolerance: torn tails + sealed-segment corruption
# ---------------------------------------------------------------------------

def _active_segment(dir_path):
    man = json.load(open(os.path.join(dir_path, "manifest.json"))) \
        if os.path.exists(os.path.join(dir_path, "manifest.json")) \
        else {"segments": []}
    sealed = {s["name"] for s in man["segments"]}
    (active,) = [n for n in os.listdir(dir_path)
                 if n.startswith("seg-") and n not in sealed]
    return os.path.join(dir_path, active)


@pytest.mark.parametrize("tear", [
    '{"o":99,"c":1,"d":{"i"',                    # torn mid-line, no newline
    '{"o":99,"c":123456,"d":{"i":99}}\n',        # full line, wrong checksum
    'garbage not even json\n',                   # corrupt line
])
def test_kill_and_reopen_skips_torn_tail_without_losing_prefix(tmp_path, tear):
    """Acceptance: a kill mid-append leaves a torn final segment; reopen
    must skip the tear and keep EVERY record written before it."""
    d = str(tmp_path / "log")
    log = EventLog(d, segment_bytes=1 << 20)
    log.append([{"i": i} for i in range(20)])
    log.close()
    with open(_active_segment(d), "a", encoding="utf-8") as fh:
        fh.write(tear)                           # the kill's half-written tail

    log2 = EventLog(d, segment_bytes=1 << 20)
    assert log2.stats.torn_records_skipped == 1
    recs = list(log2.scan(0))
    assert [o for o, _ in recs] == list(range(20))       # no data loss
    assert [p["i"] for _, p in recs] == list(range(20))  # payloads intact
    # appends continue cleanly on the truncated boundary
    assert log2.append([{"i": 20}]) == (20, 20)
    assert [o for o, _ in log2.scan(19)] == [19, 20]


def test_torn_tail_does_not_touch_sealed_segments(tmp_path):
    d = str(tmp_path / "log")
    log = EventLog(d, segment_bytes=100)         # several sealed segments
    log.append([{"i": i, "pad": "x" * 40} for i in range(10)])
    log.append([{"i": 10}])                      # small active tail
    sealed_before = log.stats.sealed_segments
    log.close()
    with open(_active_segment(d), "a") as fh:
        fh.write('{"torn')
    log2 = EventLog(d, segment_bytes=100)
    assert log2.stats.sealed_segments == sealed_before
    assert [o for o, _ in log2.scan(0)] == list(range(11))


def test_corrupt_sealed_segment_raises(tmp_path):
    d = str(tmp_path / "log")
    log = EventLog(d, segment_bytes=1)
    log.append([{"i": 0}, {"i": 1}])             # sealed immediately
    log.close()
    man = json.load(open(os.path.join(d, "manifest.json")))
    path = os.path.join(d, man["segments"][0]["name"])
    data = open(path, encoding="utf-8").read()
    open(path, "w", encoding="utf-8").write(data.replace('"i":1', '"i":9'))
    log2 = EventLog(d, segment_bytes=1)
    with pytest.raises(CorruptSegmentError):
        list(log2.scan(0))


def test_lost_manifest_write_adopts_unsealed_segment(tmp_path):
    """Crash between sealing a file and writing the manifest: the orphan
    full segment is re-adopted at reopen, records intact."""
    d = str(tmp_path / "log")
    log = EventLog(d, segment_bytes=80)
    log.append([{"i": i, "pad": "x" * 30} for i in range(6)])
    log.close()
    os.remove(os.path.join(d, "manifest.json"))  # the "lost" manifest write
    log2 = EventLog(d, segment_bytes=80)
    assert [o for o, _ in log2.scan(0)] == list(range(6))
    assert log2.next_offset == 6


# ---------------------------------------------------------------------------
# DeadLetterJournal
# ---------------------------------------------------------------------------

def test_journal_records_scan_and_cursors(tmp_path):
    j = DeadLetterJournal(str(tmp_path / "j"))
    j.record("delivery_failed:es", ("d1", {"t": 1}))
    j.record("late_event", {"key": "a", "event_time": 5.0, "value": 1.0})
    j.record("delivery_failed:es", ("d2", {"t": 2}))
    assert j.reasons() == {"delivery_failed:es": 2, "late_event": 1}
    got = list(j.scan("delivery_failed:es"))
    assert [tuple(r) for _, r in got] == [("d1", {"t": 1}), ("d2", {"t": 2})]
    assert j.pending() == {"delivery_failed:es": 2, "late_event": 1}
    j.advance("delivery_failed:es", got[-1][0] + 1)
    assert j.pending() == {"late_event": 1}
    # cursors survive reopen
    j.close()
    j2 = DeadLetterJournal(str(tmp_path / "j"))
    assert j2.cursor("delivery_failed:es") == got[-1][0] + 1
    assert j2.pending() == {"late_event": 1}
    assert j2.reasons() == {"delivery_failed:es": 2, "late_event": 1}


def test_journal_json_safe_fallback(tmp_path):
    class Opaque:
        def __repr__(self):
            return "Opaque<42>"

    # tuples are already JSON-serializable (as arrays): passed through
    assert json_safe({"k": ("a", 1)}) == {"k": ("a", 1)}
    assert json_safe(Opaque()) == {"_repr": "Opaque<42>"}
    assert json_safe([Opaque(), 3]) == [{"_repr": "Opaque<42>"}, 3]
    j = DeadLetterJournal(str(tmp_path / "j"))
    j.record("mailbox_overflow", Opaque())       # must not raise
    ((_, rec),) = list(j.scan("mailbox_overflow"))
    assert rec == {"_repr": "Opaque<42>"}


def test_listener_journal_hook_persists_every_publish(tmp_path):
    j = DeadLetterJournal(str(tmp_path / "j"))
    dl = DeadLettersListener(journal=j)
    dl.publish(("d1", {"x": 1}), reason="delivery_failed:es")
    dl.publish({"key": "a"}, reason="late_event")
    assert j.reasons() == {"delivery_failed:es": 1, "late_event": 1}
    assert dl.total == 2                         # counting unchanged


# ---------------------------------------------------------------------------
# dead-letter reason taxonomy (satellite)
# ---------------------------------------------------------------------------

def test_reason_taxonomy_grammar():
    for r in ("mailbox_overflow", "malformed_item", "late_event",
              "delivery_failed:es", "delivery_failed:IndexSink[1]",
              "store_cold_unavailable", "compaction_conflict",
              "unknown"):
        assert reason_in_taxonomy(r), r
    for r in ("delivery_failed:", "delivery_failed", "oops", ""):
        assert not reason_in_taxonomy(r), r


def test_dead_letters_recent_stays_bounded_under_flood():
    dl = DeadLettersListener(keep_last=50)
    for i in range(10_000):
        dl.publish({"i": i}, reason="mailbox_overflow")
    assert len(dl.recent) == 50                  # bounded deque, no growth
    assert dl.total == 10_000
    assert dl.by_reason["mailbox_overflow"] == 10_000
    # the survivors are the newest
    assert dl.recent[-1][1]["i"] == 9_999 and dl.recent[0][1]["i"] == 9_950


def test_pipeline_reasons_stay_inside_documented_taxonomy():
    broken = OutageSink(name="down")
    broken.down = True
    cfg = PipelineConfig(num_sources=300, feed_interval_s=120.0,
                         analytics=True, window_size_s=300.0,
                         allowed_lateness_s=100.0, watermark_lag_s=0.0,
                         delivery_retry_attempts=2, mailbox_capacity=8,
                         workers=1)
    p = AlertMixPipeline(cfg, seed=3, sinks=[IndexSink(), broken])
    p.run_for(1800.0)
    assert p.dead_letters.by_reason                  # flood produced reasons
    for reason in p.dead_letters.by_reason:
        assert reason_in_taxonomy(reason), reason


def test_threshold_alert_fires_exactly_once_per_reason():
    fired = []
    dl = DeadLettersListener(alert_threshold=10,
                             alert_hook=lambda r, n: fired.append(r))
    for _ in range(35):
        dl.publish("x", reason="late_event")
    for _ in range(12):
        dl.publish("y", reason="delivery_failed:es")
    dl.publish("z", reason="malformed_item")     # below threshold: no alert
    assert fired == ["late_event", "delivery_failed:es"]
    assert len(dl.alerts) == 2                   # once per reason, not per hit


# ---------------------------------------------------------------------------
# ReplayEngine: batch/live parity THROUGH the on-disk log
# ---------------------------------------------------------------------------

def _mk_stage():
    return AnalyticsStage(
        WindowSpec(kind="tumbling", size_s=60.0),
        [ThresholdRule("vol", metric="count", op=">=", threshold=5.0),
         RateOfChangeRule("surge", metric="count", factor=2.0),
         ZScoreRule("anom", metric="count", z=3.0)])


def test_replay_through_on_disk_log_matches_live_path(tmp_path):
    """Acceptance parity: events persisted to the EventLog, REOPENED from
    disk, and replayed through the kernel batch path yield aggregates
    AND fired alerts identical to the live WindowOperator feeding the
    same rules."""
    rng = np.random.default_rng(7)
    docs = [{"channel": k, "published_at": float(rng.uniform(0, 900)),
             "title": f"doc {i}"}
            for i, k in enumerate(np.repeat(["news", "twitter"], 300))]

    # live path: incremental operator -> rules
    live = _mk_stage()
    for doc in docs:
        live.observe(doc)
    live_alerts = live.advance(1e9)
    live_wm = live.operator.watermark

    # durable path: docs -> EventLog -> close -> reopen -> kernel replay
    d = str(tmp_path / "log")
    with EventLog(d, segment_bytes=4096) as log:
        log.append([{"id": f"d{i}", "doc": doc}
                    for i, doc in enumerate(docs)])
    replay_stage = _mk_stage()
    eng = ReplayEngine(log=EventLog(d, segment_bytes=4096),
                       analytics=replay_stage, interpret=True)
    res = eng.replay_log(0, watermark=live_wm)
    assert res["events"] == len(docs)

    def key(a):
        return (a.rule, a.key, a.window_start, a.window_end, a.metric,
                a.value, a.severity, a.fired_at_watermark)

    assert len(live_alerts) > 0
    assert [key(a) for a in replay_stage.alerts] == \
        [key(a) for a in live_alerts]
    # aggregate-level parity is visible through the fired threshold
    # values; assert the count surface directly too
    live2, batch2 = WindowOperator(WindowSpec(size_s=60.0)), None
    for doc in docs:
        live2.observe(doc["channel"], doc["published_at"])
    live2.advance_watermark(1e9)
    live_aggs = live2.poll_closed()
    from repro.alerts.batch import reduce_events
    batch2 = reduce_events(
        [(doc["channel"], doc["published_at"], 1.0) for doc in docs],
        WindowSpec(size_s=60.0), interpret=True)
    assert [(a.key, a.window_start, a.count) for a in batch2] == \
        [(a.key, a.window_start, a.count) for a in live_aggs]


def test_replay_late_events_feeds_same_rule_engine(tmp_path):
    """Late events dead-lettered by the live operator are journaled and
    batch-replayed into the SAME RuleEngine instance."""
    j = DeadLetterJournal(str(tmp_path / "j"))
    dl = DeadLettersListener(journal=j)
    stage = AnalyticsStage(
        WindowSpec(size_s=60.0),
        [ThresholdRule("vol", metric="count", op=">=", threshold=3.0)],
        dead_letters=dl)
    # on-time traffic closes [0, 60) with the watermark at 1000
    for t in (10.0, 20.0, 30.0):
        stage.observe({"channel": "news", "published_at": t})
    on_time = stage.advance(1000.0)
    assert [a.rule for a in on_time] == ["vol"]
    # stragglers for a long-closed window -> dead letters -> journal
    for t in (90.0, 100.0, 110.0):
        assert not stage.observe({"channel": "news", "published_at": t})
    assert j.pending() == {"late_event": 3}

    eng = ReplayEngine(journal=j, analytics=stage, interpret=True)
    res = eng.replay_late_events()
    assert res == {"events": 3, "aggregates": 1, "alerts": 1}
    # the replayed window's alert landed in the same sink/log
    assert [a.rule for a in stage.alerts] == ["vol", "vol"]
    assert stage.alerts[-1].window_start == 60.0
    assert j.pending() == {}                     # cursor advanced
    assert eng.replay_late_events()["events"] == 0   # idempotent


def test_replay_dead_letters_partial_delivery_is_idempotent(tmp_path):
    """Replay that dies mid-backlog must neither lose nor duplicate: the
    cursor advances only past verifiably landed batches, and dedup skips
    records the terminal already accepted on the next pass."""
    j = DeadLetterJournal(str(tmp_path / "j"))
    for i in range(10):
        j.record("delivery_failed:es", (f"d{i}", {"i": i}))
    term = OutageSink(name="es")
    envelope = RetryingSink(term, max_attempts=2, name="es")

    eng = ReplayEngine(journal=j)
    # batches of 4: first lands, backend dies before the second
    seen = []
    orig = term._write

    def die_after_first(batch):
        if len(seen) >= 1:
            raise IOError("regressed mid-replay")
        seen.append(len(batch))
        orig(batch)

    term._write = die_after_first
    res = eng.replay_dead_letters("delivery_failed:es", envelope, batch=4)
    assert res["replayed"] == 4 and res["stopped_early"]
    assert [r[0] for r in term.records] == ["d0", "d1", "d2", "d3"]
    assert j.pending() == {"delivery_failed:es": 6}
    # the failed batch was NOT parked in the retry envelope: replay goes
    # to the terminal, so a failure surfaces instead of being deferred
    # into a later redelivery the cursor can't see (double delivery)
    assert envelope.pending_records == 0

    # backend recovers; second pass delivers ONLY the remainder
    term._write = orig
    envelope2 = RetryingSink(term, max_attempts=2, name="es")
    res2 = eng.replay_dead_letters("delivery_failed:es", envelope2, batch=4)
    assert res2["replayed"] == 6 and not res2["stopped_early"]
    assert [r[0] for r in term.records] == [f"d{i}" for i in range(10)]
    assert j.pending() == {}

    # a third pass over a (hypothetically) stale cursor is a no-op via
    # dedup: re-scan from 0 by resetting the cursor file
    j2 = DeadLetterJournal(str(tmp_path / "j2"))
    for i in range(10):
        j2.record("delivery_failed:es", (f"d{i}", {"i": i}))
    eng.journal = j2
    res3 = eng.replay_dead_letters("delivery_failed:es", envelope2, batch=4)
    assert res3["replayed"] == 0 and res3["deduped"] == 10
    assert len(term.records) == 10               # still exactly once


def test_replayed_backfill_does_not_corrupt_stateful_rules(tmp_path):
    """An old backlog replayed into the live engine must not clobber
    RateOfChangeRule's 'previous window' state for a key (windows out of
    time order are ignored by the order guard)."""
    j = DeadLetterJournal(str(tmp_path / "j"))
    dl = DeadLettersListener(journal=j)
    stage = AnalyticsStage(
        WindowSpec(size_s=60.0),
        [RateOfChangeRule("surge", metric="count", factor=2.0,
                          min_value=1.0)],
        dead_letters=dl)
    # live: [840,900) count=10, then late stragglers for long-dead [0,60)
    for t in (850.0, 851.0, 852.0, 853.0, 854.0,
              855.0, 856.0, 857.0, 858.0, 859.0):
        stage.observe({"channel": "news", "published_at": t})
    assert stage.advance(2000.0) == []           # first window: no prev
    for t in (10.0, 20.0):
        assert not stage.observe({"channel": "news", "published_at": t})
    ReplayEngine(journal=j, analytics=stage,
                 interpret=True).replay_late_events()
    # the replayed [0,60) count=2 must NOT become the new "prev": a
    # following live window of 12 is only x1.2 vs 10 — no surge
    for t in (1910.0 + i for i in range(12)):
        stage.observe({"channel": "news", "published_at": t})
    fired = stage.advance(3000.0)
    assert fired == [] and stage.alerts == []


def test_log_append_after_close_raises(tmp_path):
    log = EventLog(str(tmp_path / "log"))
    log.append([{"i": 0}])
    log.close()
    with pytest.raises(RuntimeError, match="closed"):
        log.append([{"i": 1}])
    # reopen works and nothing was orphaned
    log2 = EventLog(str(tmp_path / "log"))
    assert [o for o, _ in log2.scan(0)] == [0]
    assert log2.append([{"i": 1}]) == (1, 1)


def test_pipeline_drains_late_events_on_flush(tmp_path):
    """With store + analytics mounted, run_for's cutoff flush replays
    the journaled late_event backlog through the batch path (cursor
    advances -> journal truncation floor unpinned)."""
    cfg = PipelineConfig(num_sources=400, feed_interval_s=120.0,
                         analytics=True, window_size_s=300.0,
                         allowed_lateness_s=100.0, watermark_lag_s=0.0,
                         store_dir=str(tmp_path / "store"))
    p = AlertMixPipeline(cfg, seed=3)
    p.run_for(3600.0)
    late = p.analytics.operator.stats["late_dropped"]
    assert late > 0                              # genuine late traffic
    assert p.store.journal.pending().get("late_event", 0) == 0
    assert p.store.journal.cursor("late_event") > 0
    st = p.replay_status()
    assert st["stats"]["events_replayed"] >= late
    p.close()


def test_replay_same_doc_to_two_failed_backends(tmp_path):
    """Dedup is scoped per reason: when TWO backends dead-letter the
    same document, each backend's recovery replays its own copy — one
    backend's replay must never swallow another's backlog."""
    j = DeadLetterJournal(str(tmp_path / "j"))
    for i in range(5):
        j.record("delivery_failed:es", (f"d{i}", {"i": i}))
        j.record("delivery_failed:jsonl", (f"d{i}", {"i": i}))
    es, jsonl = CollectingSink("es"), CollectingSink("jsonl")
    eng = ReplayEngine(journal=j)
    r1 = eng.replay_dead_letters("delivery_failed:es", es)
    r2 = eng.replay_dead_letters("delivery_failed:jsonl", jsonl)
    assert r1 == {"replayed": 5, "deduped": 0, "stopped_early": False}
    assert r2 == {"replayed": 5, "deduped": 0, "stopped_early": False}
    assert [r[0] for r in es.records] == [f"d{i}" for i in range(5)]
    assert [r[0] for r in jsonl.records] == [f"d{i}" for i in range(5)]
    assert j.pending() == {}


def test_redead_lettered_doc_with_new_content_is_replayed(tmp_path):
    """Dedup keys on full record content: a doc that dead-letters AGAIN
    (new journal record, updated payload) after its earlier version was
    replayed must be delivered too — only identical journal records are
    duplicates."""
    j = DeadLetterJournal(str(tmp_path / "j"))
    sink = CollectingSink("es")
    eng = ReplayEngine(journal=j)
    j.record("delivery_failed:es", ("d1", {"v": 1}))
    assert eng.replay_dead_letters(
        "delivery_failed:es", sink)["replayed"] == 1
    # second outage: the SAME doc id dead-letters with newer content
    j.record("delivery_failed:es", ("d1", {"v": 2}))
    res = eng.replay_dead_letters("delivery_failed:es", sink)
    assert res == {"replayed": 1, "deduped": 0, "stopped_early": False}
    assert [r[1]["v"] for r in sink.records] == [1, 2]
    # empty backlog: index-first early exit, cursor untouched
    assert eng.replay_dead_letters("delivery_failed:es", sink) == \
        {"replayed": 0, "deduped": 0, "stopped_early": False}


def test_replay_stamped_ahead_of_live_does_not_silence_rate_rule():
    """A backlog force-closed past live time (window_end > the stamped
    watermark) must not ratchet RateOfChangeRule's order guard forward
    and mute every later live window."""
    rule = RateOfChangeRule("surge", metric="count", factor=2.0,
                            min_value=1.0)
    stage = AnalyticsStage(WindowSpec(size_s=60.0), [rule])
    eng = ReplayEngine(analytics=stage, interpret=True)
    # replay events from a FUTURE run segment, stamped at live time 0
    eng.replay_events([("news", 955.0, 1.0), ("news", 956.0, 1.0)],
                      watermark=0.0)
    # live traffic proceeds normally from t=0: 2 -> 5 is a genuine surge
    for t in (10.0, 20.0):
        stage.observe({"channel": "news", "published_at": t})
    for t in (70.0, 71.0, 72.0, 73.0, 74.0):
        stage.observe({"channel": "news", "published_at": t})
    fired = stage.advance(1000.0)
    surges = [a for a in fired if a.rule == "surge"]
    assert len(surges) == 1 and surges[0].window_start == 60.0


@pytest.mark.parametrize("route", ["events", "columns"])
def test_replayed_windows_carry_their_min(route):
    """A window made only of replayed events reaches the query plane's
    hot store and the rules as a whole aggregate: its min is the events'
    min, not the +inf of an empty accumulator."""
    from repro.store.columnar import Lanes

    stage = AnalyticsStage(WindowSpec(size_s=60.0), [])
    eng = ReplayEngine(analytics=stage, interpret=True)
    events = [("news", 5.0, 3.0), ("news", 7.0, 2.0), ("news", 65.0, 4.0)]
    if route == "events":
        aggs, _ = eng.replay_events(events, watermark=0.0)
    else:
        aggs, _ = eng.replay_columns(Lanes(
            ts=np.array([t for _, t, _ in events]),
            key_codes=np.zeros(3, np.int64), key_vocab=["news"],
            values=np.array([v for _, _, v in events])), watermark=0.0)
    assert [(a.window_start, a.min, a.max) for a in aggs] == [
        (0.0, 2.0, 3.0), (60.0, 4.0, 4.0)]


def test_log_truncate_crash_between_manifest_and_unlink(tmp_path):
    """truncate() rewrites the manifest BEFORE unlinking: simulate the
    crash window by restoring a doomed segment file after truncation —
    reopen must delete the orphan, not raise or resurrect it."""
    import shutil

    d = str(tmp_path / "log")
    log = EventLog(d, segment_bytes=1)
    for i in range(3):
        log.append([{"i": 2 * i}, {"i": 2 * i + 1}])
    doomed = os.path.join(d, "seg-000000000000.jsonl")
    saved = str(tmp_path / "saved.jsonl")
    shutil.copy(doomed, saved)
    assert log.truncate(2) == 2
    log.close()
    shutil.copy(saved, doomed)                   # the un-unlinked orphan
    log2 = EventLog(d, segment_bytes=1)          # no CorruptSegmentError
    assert [o for o, _ in log2.scan(0)] == [2, 3, 4, 5]
    assert not os.path.exists(doomed)            # orphan cleaned up


def test_log_age_roll_still_works_after_reopen(tmp_path):
    d = str(tmp_path / "log")
    log = EventLog(d, segment_bytes=1 << 20, segment_age_s=60.0)
    log.append([{"i": 0}])
    log.close()
    log2 = EventLog(d, segment_bytes=1 << 20, segment_age_s=60.0)
    assert log2.stats.sealed_segments == 0
    log2.tick(61.0)                              # age clock restarted at
    assert log2.stats.sealed_segments == 1       # reopen, not dead


def test_journal_truncates_despite_monitoring_only_reasons(tmp_path):
    """mailbox_overflow / malformed_item have no replay route; they must
    not pin the truncation floor at 0 forever."""
    j = DeadLetterJournal(str(tmp_path / "j"), segment_bytes=1)
    j.record("malformed_item", {"bad": True})    # monitoring-only, seg 0
    for i in range(4):
        j.record("delivery_failed:es", (f"d{i}", {"i": i}))
    sink = CollectingSink("es")
    ReplayEngine(journal=j).replay_dead_letters("delivery_failed:es", sink)
    assert len(sink.records) == 4
    # replay-driven truncation reclaimed the fully-replayed segments
    assert j.log.truncated_through > 0
    assert j.log.stats.truncated_segments > 0
    # truncated monitoring-only records leave the pending index too:
    # metrics never report records that are no longer on disk
    assert j.pending().get("malformed_item", 0) == 0
    assert j.reasons().get("malformed_item", 0) == 0


# ---------------------------------------------------------------------------
# pipeline acceptance: outage -> journal -> recovery -> auto-replay
# ---------------------------------------------------------------------------

def test_pipeline_outage_journal_and_auto_replay(tmp_path):
    """A backend outage dead-letters records into the durable journal;
    when per-sink health flips back up the pipeline auto-replays the
    backlog through that backend's own envelope until it converges with
    the healthy backend — and a reopened store still sees the log."""
    flaky, good = OutageSink(name="flaky_es"), IndexSink()
    cfg = PipelineConfig(num_sources=300, feed_interval_s=120.0,
                         store_dir=str(tmp_path / "store"),
                         delivery_batch=8, delivery_retry_attempts=2,
                         delivery_retry_backoff_s=2.0)
    p = AlertMixPipeline(cfg, seed=2, sinks=[good, flaky])
    p.run_for(300.0)
    flaky.down = True
    p.run_for(600.0)
    backlog = p.store.journal.pending()["delivery_failed:flaky_es"]
    assert backlog > 0
    assert p.dead_letters.by_reason["delivery_failed:flaky_es"] == backlog
    assert not p._backend_health["flaky_es"]     # outage observed

    flaky.down = False
    p.run_for(600.0)
    m = p.metrics
    assert m.replayed_total == backlog
    assert p.store.journal.pending().get("delivery_failed:flaky_es", 0) == 0
    # the failed backend converged to the healthy one's document set
    assert {i for i, _ in flaky.records} == set(good._docs)
    # observability surfaces
    st = p.replay_status()
    assert st["enabled"] and st["stats"]["replayed_records"] == backlog
    assert m.store["replayed_records"] == backlog
    assert m.store["appended_records"] == m.indexed_total
    assert m.store["journal_records"] >= backlog
    assert m.store["appended_bytes"] > 0 and m.store["segments"] >= 1

    # durable across close/reopen: the log still holds every document
    p.close()
    with EventLog(str(tmp_path / "store" / "documents")) as log:
        assert sum(1 for _ in log.scan(0)) == m.indexed_total


def test_pipeline_without_store_unchanged(tmp_path):
    p = AlertMixPipeline(PipelineConfig(num_sources=50), seed=0)
    assert p.store is None
    p.run_for(60.0)
    assert p.replay_status() == {"enabled": False}
    assert p.metrics.store == {} and p.store_stats() == {}


def test_store_plane_status_shape(tmp_path):
    with StorePlane(str(tmp_path / "s")) as plane:
        plane.append_documents([("a", {"x": 1}), ("b", {"x": 2})])
        plane.journal.record("late_event", {"key": "k", "event_time": 1.0})
        st = plane.status()
        assert st["appended_records"] == 2
        assert st["journal_records"] == 1
        assert st["pending_replay"] == {"late_event": 1}
        assert st["pending_replay_records"] == 1


# ---------------------------------------------------------------------------
# long-poll wait (satellite; lives with the hub but exercised here with
# a producer thread, per the store-plane PR checklist)
# ---------------------------------------------------------------------------

def test_subscription_wait_long_poll_with_producer_thread():
    from repro.delivery import SubscriptionHub

    class Rec:
        def __init__(self, i):
            self.rule, self.i = "r", i

    hub = SubscriptionHub()
    sub = hub.subscribe(capacity=16)
    assert sub.wait(timeout=0.02) is None        # times out, no spin

    def produce():
        hub.emit([Rec(1)])

    t = threading.Thread(target=produce)
    t.start()
    got = sub.wait(timeout=5.0)                  # parked until the push
    t.join()
    assert got is not None and got.i == 1
    # buffered records return immediately, order preserved
    hub.emit([Rec(2), Rec(3)])
    assert sub.wait(timeout=0.0).i == 2 and sub.wait().i == 3

    # hub-level one-shot long-poll: the producer fires only after the
    # waiter's ephemeral subscription is registered
    baseline = hub.subscriber_count

    def produce_when_waiting():
        import time as _time
        deadline = _time.monotonic() + 5.0
        while (hub.subscriber_count <= baseline
               and _time.monotonic() < deadline):
            _time.sleep(0.005)
        hub.emit([Rec(9)])

    t2 = threading.Thread(target=produce_when_waiting)
    t2.start()
    got = hub.wait(timeout=5.0)
    t2.join()
    assert got is not None and got.i == 9
    assert hub.subscriber_count == 1             # ephemeral sub removed
    sub.drain()                                  # Rec(9) also reached sub

    # closing releases a parked waiter
    waiter_result = ["sentinel"]
    t3 = threading.Thread(
        target=lambda: waiter_result.__setitem__(0, sub.wait(timeout=5.0)))
    t3.start()
    import time as _time
    _time.sleep(0.05)
    sub.close()
    t3.join(timeout=2.0)
    assert not t3.is_alive() and waiter_result[0] is None

    # callback-mode subscriptions cannot long-poll
    cb = hub.subscribe(callback=lambda r: None)
    with pytest.raises(RuntimeError):
        cb.wait(0.01)


# ---------------------------------------------------------------------------
# the replay chain on the profiler's timeline, and launches per route
# ---------------------------------------------------------------------------

def _columnar_replay_engine(tmp_path, n=200, **kw):
    from repro.store.columnar.log import ColumnarEventLog

    log = ColumnarEventLog(str(tmp_path / "clog"), segment_bytes=4096,
                           block_rows=16)
    log.append([{"id": f"d{i}",
                 "doc": {"title": "t", "published_at": float(i % 900),
                         "channel": "news" if i % 2 else "sports",
                         "value": float(i % 7)}} for i in range(n)])
    log.roll()
    return ReplayEngine(log=log, analytics=AnalyticsStage(
        WindowSpec(size_s=60.0), []), interpret=True, **kw)


def _host_events(trace_dir):
    import glob

    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "the profiler wrote no trace"
    out = set()
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.update(e.name for e in line.events)
    return out


def test_replay_log_stages_are_host_events_on_the_profiler_trace(tmp_path):
    """A profiler trace of one columnar ``replay_log`` names every stage
    and sub-stage of the chain, and the kernel's launches by route."""
    import jax

    eng = _columnar_replay_engine(tmp_path)
    eng.replay_log(0, watermark=1e9)              # compile outside
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        res = eng.replay_log(0, watermark=1e9)
    finally:
        jax.profiler.stop_trace()
    assert res["columnar"] and res["events"] == 200
    names = _host_events(str(tmp_path / "trace"))
    for stage in ("decode", "pack_events", "pack_events.assign",
                  "pack_events.unique", "pack_events.slots", "kernel",
                  "kernel.dispatch", "kernel.wait", "kernel.fetch",
                  "unpack", "unpack.order", "unpack.records",
                  "state_merge"):
        assert f"replay.{stage}" in names, stage
    assert "window_reduce.replay" in names


def test_replay_sub_stages_nest_inside_their_stages(tmp_path):
    """The pack and kernel stages keep their meaning: each covers its
    sub-stages, and the min lane's launch starts after the max lane's
    fetch ended."""
    from repro.obs.profiler import recent_passes

    eng = _columnar_replay_engine(tmp_path)
    eng.replay_log(0, watermark=1e9)
    n = len(recent_passes())
    eng.replay_log(0, watermark=1e9)
    passes = [(st, t0, t0 + dt) for prof, st, t0, dt in recent_passes()[n:]
              if prof == "replay"]
    span = {st: (a, b) for st, a, b in passes if "." not in st}
    for parent in ("pack_events", "kernel"):
        a, b = span[parent]
        subs = [(x, y) for st, x, y in passes
                if st.startswith(parent + ".")]
        assert len(subs) == (3 if parent == "pack_events" else 6)
        assert all(a <= x <= y <= b for x, y in subs)
    kernel = [(st, x) for st, x, _ in passes if st.startswith("kernel.")]
    assert [st for st, _ in kernel] == [
        "kernel.dispatch", "kernel.wait", "kernel.fetch"] * 2
    assert [x for _, x in kernel] == sorted(x for _, x in kernel)


def test_unpack_sub_stages_nest_inside_unpack_once_a_call(tmp_path):
    """The unpack is timed in two sub-stages, ``unpack.order`` then
    ``unpack.records``, each once per ``reduce_columns`` call and inside
    its ``unpack`` pass; the replay's records carry its watermark."""
    from repro.obs.profiler import recent_passes

    eng = _columnar_replay_engine(tmp_path)
    eng.replay_log(0, watermark=1e9)
    for _ in range(2):
        n = len(recent_passes())
        aggs, _ = eng.replay_columns(eng.log.scan_lanes(0), watermark=77.0)
        passes = [(st, t0, t0 + dt) for prof, st, t0, dt
                  in recent_passes()[n:] if prof == "replay"
                  and st.startswith("unpack")]
        assert [st for st, _, _ in passes] == [
            "unpack.order", "unpack.records", "unpack"]
        (_, a, b), = [p for p in passes if p[0] == "unpack"]
        assert all(a <= x <= y <= b for _, x, y in passes)
        assert passes[0][2] <= passes[1][1]
    assert aggs and {a.closed_at_watermark for a in aggs} == {77.0}


def test_launch_counters_tell_replay_and_drain_apart(tmp_path):
    """A log replay and a late-event drain count their launches under
    their own route; replaying the same log again launches the same
    shape, which is not a new one."""
    from repro.obs import kernel_launches

    def counts():
        return kernel_launches().get("window_reduce", {})

    j = DeadLetterJournal(str(tmp_path / "j"))
    eng = _columnar_replay_engine(tmp_path, journal=j)
    c0 = counts()
    eng.replay_log(0, watermark=1e9)
    c1 = counts()
    eng.replay_log(0, watermark=1e9)
    c2 = counts()

    def grew(a, b, route, key):
        return b.get(route, {}).get(key, 0) - a.get(route, {}).get(key, 0)

    assert grew(c0, c1, "replay", "launches") == 2     # max and min lanes
    assert grew(c0, c1, "replay", "memberships") == 400
    assert grew(c1, c2, "replay", "launches") == 2
    assert grew(c1, c2, "replay", "new_shapes") == 0
    assert grew(c0, c2, "drain", "launches") == 0
    for t in (5.0, 6.0, 7.0):
        j.record("late_event", {"key": "news", "event_time": t,
                                "value": 2.0})
    assert eng.replay_late_events()["events"] == 3
    c3 = counts()
    assert grew(c2, c3, "drain", "launches") == 2
    assert grew(c2, c3, "drain", "slots") == 2          # one slot a lane
    assert grew(c2, c3, "replay", "launches") == 0


def test_pipeline_exports_kernel_launches_per_route(tmp_path):
    cfg = PipelineConfig(num_sources=0, analytics=True,
                         store_dir=str(tmp_path / "store"))
    p = AlertMixPipeline(cfg, seed=0)
    try:
        p.store.replay.replay_events([("news", 10.0, 1.0)], watermark=1e9,
                                     route="drain")
        text = p.metrics_text()
        assert ('kernel_launches_total{kernel="window_reduce",'
                'route="drain"}') in text
        assert ('kernel_new_shapes_total{kernel="window_reduce",'
                'route="drain"}') in text
    finally:
        p.close()
