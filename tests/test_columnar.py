"""Columnar store plane (repro.store.columnar): block format fidelity,
the sealed-scan fast path, keyed compaction, retention, tiered offload,
and the failure matrix the subsystem must survive — corrupt blocks,
torn seals, missing cold objects, compaction racing truncate."""
import json
import math
import os
import zlib

import numpy as np
import pytest

from repro.core.dead_letters import (DeadLettersListener,
                                     reason_in_taxonomy)
from repro.store.columnar import (ColumnarEventLog, LocalDirObjectStore,
                                  encode_block, iter_blocks)
from repro.store.columnar.blocks import CorruptBlockError
from repro.store.segment_log import CorruptSegmentError, EventLog


def _docs(n, start=0, channel_of=lambda i: "news" if i % 2 else "sports"):
    return [{"id": f"d{start + i}",
             "doc": {"title": f"t{start + i}",
                     "published_at": float((start + i) % 900),
                     "channel": channel_of(i),
                     "value": float(i % 7)}}
            for i in range(n)]


def _mk(tmp_path, name="log", **kw):
    kw.setdefault("segment_bytes", 4096)
    kw.setdefault("block_rows", 16)
    return ColumnarEventLog(str(tmp_path / name), **kw)


# ---- block format -----------------------------------------------------------

def test_block_round_trip_is_lossless():
    recs = [(i, d) for i, d in enumerate(_docs(50))]
    # mixed shapes too: a raw (non-document) payload mid-block
    recs[7] = (7, {"weird": [1, 2, {"deep": True}], "n": None})
    data = encode_block(recs)
    blocks = list(iter_blocks(data))
    assert len(blocks) == 1
    assert blocks[0].records() == recs
    # typed lanes decode as numpy with zero per-record work
    ts = blocks[0].lane_ts()
    assert ts.dtype == np.float64
    assert np.isnan(ts[7])                 # raw row has no event time
    codes, vocab = blocks[0].lane_key()
    assert {vocab[c] for i, c in enumerate(codes) if i != 7} == \
        {"news", "sports"}


def test_block_stats_carry_ts_and_key_range():
    recs = [(i, d) for i, d in enumerate(_docs(30))]
    blk = next(iter_blocks(encode_block(recs)))
    st = blk.stats
    assert st["min_ts"] == 0.0 and st["max_ts"] == 29.0
    assert st["min_key"] == "news" and st["max_key"] == "sports"


def test_corrupt_block_checksum_raises(tmp_path):
    log = _mk(tmp_path)
    for i in range(0, 200, 20):
        log.append(_docs(20, start=i))
    assert len(log._sealed) >= 1
    victim = log._sealed[0].name
    log.close()
    path = tmp_path / "log" / victim
    raw = bytearray(path.read_bytes())
    raw[-3] ^= 0xFF                        # flip a payload byte
    path.write_bytes(bytes(raw))
    log2 = _mk(tmp_path)
    with pytest.raises(CorruptSegmentError):
        list(log2.scan())
    with pytest.raises(CorruptSegmentError):
        log2.scan_lanes()
    log2.close()


# ---- sealed fast path -------------------------------------------------------

def test_scan_lanes_matches_record_scan(tmp_path):
    log = _mk(tmp_path)
    for i in range(0, 300, 30):
        log.append(_docs(30, start=i))
    lanes = log.scan_lanes()
    recs = list(log.scan())
    assert lanes.count == len(recs) == 300
    exp_sum = sum(r[1]["doc"]["value"] for r in recs)
    assert abs(lanes.values.sum() - exp_sum) < 1e-9
    keys = [lanes.key_vocab[c] for c in lanes.key_codes]
    assert sorted(set(keys)) == ["news", "sports"]
    # filtered scan: keys + ts range agree with a python fold
    sub = log.scan_lanes(keys=["news"], ts_min=100.0, ts_max=200.0)
    exp = [r for r in recs if r[1]["doc"]["channel"] == "news"
           and 100.0 <= r[1]["doc"]["published_at"] < 200.0]
    assert sub.count == len(exp)


def test_block_stat_pruning_skips_blocks(tmp_path):
    log = _mk(tmp_path, segment_bytes=1 << 20, block_rows=16)
    # ts strictly increasing -> disjoint per-block ts ranges
    log.append([{"id": f"d{i}", "doc": {"published_at": float(i),
                                        "channel": "news"}}
                for i in range(256)])
    log.roll()
    before = log.cstats["blocks_pruned"]
    lanes = log.scan_lanes(ts_min=0.0, ts_max=16.0)
    assert lanes.count == 16
    assert log.cstats["blocks_pruned"] - before >= 10
    log.close()


def test_batch_tail_survives_crash_and_torn_frame(tmp_path):
    log = _mk(tmp_path, segment_bytes=1 << 20)
    log.append(_docs(10))
    log.append(_docs(10, start=10))
    log.close()
    # torn final frame: simulate a partial write of a third batch
    active = [n for n in os.listdir(tmp_path / "log")
              if n.endswith(".jsonl")]
    assert len(active) == 1
    with open(tmp_path / "log" / active[0], "a", encoding="utf-8") as fh:
        fh.write('B|20|5|00000000|[{"id');      # no terminator, bad crc
    log2 = _mk(tmp_path, segment_bytes=1 << 20)
    recs = list(log2.scan())
    assert [o for o, _ in recs] == list(range(20))   # acked batches intact
    assert log2.next_offset == 20
    log2.append(_docs(1, start=20))
    assert len(list(log2.scan())) == 21
    log2.close()


def test_torn_seal_recovers_to_json_tail(tmp_path):
    log = _mk(tmp_path, segment_bytes=1 << 20)
    for i in range(0, 60, 10):
        log.append(_docs(10, start=i))
    log.close()
    d = tmp_path / "log"
    jname = [n for n in os.listdir(d) if n.endswith(".jsonl")][0]
    # crash mid-seal: a PARTIAL .colb twin exists alongside the intact
    # JSON tail (conversion wrote, rename happened, manifest write lost
    # — or the file is simply truncated garbage)
    colb = d / jname.replace(".jsonl", ".colb")
    colb.write_bytes(b"ACB1\x10\x00\x00\x00garbage")
    log2 = _mk(tmp_path, segment_bytes=1 << 20)
    assert log2.cstats["torn_seals_recovered"] == 1
    assert not colb.exists()               # partial product discarded
    recs = list(log2.scan())
    assert [o for o, _ in recs] == list(range(60))   # JSON tail authoritative
    log2.close()


def test_legacy_jsonl_log_adopts_into_columnar(tmp_path):
    d = str(tmp_path / "log")
    with EventLog(d, segment_bytes=1 << 20) as old:
        old.append(_docs(25))
    log = ColumnarEventLog(d, segment_bytes=1 << 20, block_rows=16)
    recs = list(log.scan())
    assert len(recs) == 25 and recs[0][1]["id"] == "d0"
    # per-record legacy tail keeps appending via batch frames
    log.append(_docs(5, start=25))
    assert len(list(log.scan())) == 30
    lanes = log.scan_lanes()               # tail rows ride the lane view
    assert lanes.count == 30
    log.close()


# ---- keyed compaction -------------------------------------------------------

def test_compaction_keeps_last_per_doc_id(tmp_path):
    log = _mk(tmp_path, segment_bytes=2048, compact_head_segments=1)
    # write the same 40 ids three times over; only the last generation
    # (plus whatever lives in the head/tail) must survive compaction
    for gen in range(3):
        for i in range(0, 40, 8):
            log.append([{"id": f"d{i + j}",
                         "doc": {"published_at": float(gen * 100 + i + j),
                                 "channel": "news", "gen": gen}}
                        for j in range(8)])
    assert len(log._sealed) >= 3
    res = log.compact()
    assert res["conflict"] is False and res["dropped"] > 0
    survivors = {}
    for off, p in log.scan():
        assert p["id"] not in survivors or \
            survivors[p["id"]][0] < off      # offsets strictly advance
        survivors[p["id"]] = (off, p["doc"]["gen"])
    assert set(survivors) == {f"d{i}" for i in range(40)}
    # every id's LAST write is still present — compaction dropped only
    # superseded rows (keep-last-per-doc-id)
    by_id = {}
    for off, p in log.scan():
        by_id[p["id"]] = p["doc"]["gen"]
    assert all(g == 2 for g in by_id.values())
    # manifest survives reopen with the compacted generation files
    log.close()
    log2 = _mk(tmp_path, segment_bytes=2048)
    assert {p["id"] for _, p in log2.scan()} == set(survivors)
    log2.close()


def test_compaction_truncate_interleave_keeps_manifest_consistent(tmp_path):
    log = _mk(tmp_path, segment_bytes=2048, compact_head_segments=1)
    dl = DeadLettersListener()
    log.dead_letters = dl
    for gen in range(3):
        for i in range(0, 40, 8):
            log.append([{"id": f"d{i + j}",
                         "doc": {"published_at": float(i + j),
                                 "channel": "news", "gen": gen}}
                        for j in range(8)])
    plan = log._compact_plan()
    assert plan is not None
    built = log._compact_build(plan)
    # a truncate lands between build and commit: the commit must detect
    # the conflict, abandon its output, and dead-letter — never publish
    # a manifest mixing pre- and post-truncate views
    upto = plan["candidates"][0].last + 1
    assert log.truncate(upto) > 0
    assert log._compact_commit(plan, built) is False
    assert log.cstats["compaction_conflicts"] == 1
    assert dl.by_reason["compaction_conflict"] == 1
    assert reason_in_taxonomy("compaction_conflict")
    # manifest + disk agree: every listed segment exists, no stray gens
    man = json.loads((tmp_path / "log" / "manifest.json").read_text())
    listed = {s["name"] for s in man["segments"]}
    on_disk = {n for n in os.listdir(tmp_path / "log")
               if n.startswith("seg-")}
    active = {n for n in on_disk if n.endswith(".jsonl")}
    assert listed == on_disk - active
    # the log still scans cleanly end to end and a retried compaction
    # succeeds on the new shape
    offs = [o for o, _ in log.scan()]
    assert offs == sorted(offs)
    assert log.compact()["conflict"] is False
    log.close()


# ---- retention --------------------------------------------------------------

def test_retention_by_bytes_and_age(tmp_path):
    log = _mk(tmp_path, segment_bytes=2048, retention_max_bytes=4096)
    for i in range(0, 200, 10):
        log.append(_docs(10, start=i))
    sealed_bytes = sum(s.bytes for s in log._sealed)
    assert log.enforce_retention(now=0.0) > 0
    assert sum(s.bytes for s in log._sealed) <= 4096 < sealed_bytes
    assert log.cstats["retention_released_segments"] > 0
    # age-based: everything older than the cutoff (by max event time)
    log.retention_max_bytes = None
    log.retention_max_age_s = 10.0
    first_kept = log._sealed[0]
    cutoff_now = log._seg_ts[first_kept.name][1] + 11.0
    assert log.enforce_retention(now=cutoff_now) > 0
    # scans start at the new floor; offsets never rewind
    offs = [o for o, _ in log.scan()]
    assert offs and offs[0] >= log.truncated_through
    log.close()


# ---- tiered offload ---------------------------------------------------------

def test_offload_round_trip_and_cold_scan(tmp_path):
    store = LocalDirObjectStore(str(tmp_path / "objects"))
    log = _mk(tmp_path, object_store=store, offload_keep_local=1)
    for i in range(0, 200, 10):
        log.append(_docs(10, start=i))
    moved = log.offload()
    assert moved >= 1
    assert set(store.list()) == log._cold
    # offloaded files are gone locally; manifest is the source of truth
    for name in log._cold:
        assert not os.path.exists(tmp_path / "log" / name)
    recs = list(log.scan())                # transparent cold fetch
    assert [o for o, _ in recs] == list(range(200))
    assert log.cstats["cold_fetches"] >= moved
    lanes = log.scan_lanes()
    assert lanes.count == 200
    # reopen: cold segments stay cold, scans still work
    log.close()
    log2 = _mk(tmp_path, object_store=store, offload_keep_local=1)
    assert log2._cold and len(list(log2.scan())) == 200
    log2.close()


def test_missing_cold_object_dead_letters_and_skips(tmp_path):
    store = LocalDirObjectStore(str(tmp_path / "objects"))
    log = _mk(tmp_path, object_store=store, offload_keep_local=1)
    dl = DeadLettersListener()
    log.dead_letters = dl
    for i in range(0, 200, 10):
        log.append(_docs(10, start=i))
    assert log.offload() >= 2
    lost = sorted(log._cold)[0]
    store.delete(lost)                     # the object store lost data
    recs = list(log.scan())                # skips, never wedges
    lost_records = next(s.records for s in log._sealed if s.name == lost)
    assert len(recs) == 200 - lost_records
    assert dl.by_reason["store_cold_unavailable"] == 1
    assert reason_in_taxonomy("store_cold_unavailable")
    assert log.cstats["cold_fetch_failures"] == 1
    # lanes path takes the same detour
    lanes = log.scan_lanes()
    assert lanes.count == 200 - lost_records
    assert dl.by_reason["store_cold_unavailable"] == 2
    log.close()


def test_truncate_deletes_cold_objects(tmp_path):
    store = LocalDirObjectStore(str(tmp_path / "objects"))
    log = _mk(tmp_path, object_store=store, offload_keep_local=0)
    for i in range(0, 100, 10):
        log.append(_docs(10, start=i))
    log.offload()
    assert store.list()
    last = max(s.last for s in log._sealed)
    log.truncate(last + 1)
    assert store.list() == []              # cold objects released too
    log.close()


# ---- pipeline integration ---------------------------------------------------

def test_pipeline_columnar_replay_and_maintenance(tmp_path):
    from repro.core import AlertMixPipeline, PipelineConfig
    p = AlertMixPipeline(PipelineConfig(
        num_sources=40, store_dir=str(tmp_path / "store"),
        store_columnar=True, segment_bytes=1 << 13,
        compact_interval_s=900.0, offload_dir=str(tmp_path / "objects"),
        offload_keep_local=1, analytics=True), seed=0)
    p.run_for(3600, dt=5.0)
    res = p.store.replay.replay_log(0)
    assert res["columnar"] is True
    assert res["events"] == p.store_stats()["appended_records"]
    st = p.store_stats()["columnar"]
    assert st["block_rows"] == 2048
    p.flush_delivery()
    snap = p.metrics_snapshot()
    assert "store_columnar_sealed_segments_total" in snap["counters"]
    p.close()


# ---- property: block round-trip over adversarial payloads -------------------

from _hyp import given, settings, st  # noqa: E402


def _eq(a, b):
    """Structural equality, NaN-aware, tolerant of the ONE documented
    lossy coercion: a mixed int/float column decodes ints as floats."""
    if isinstance(a, dict) and isinstance(b, dict):
        return set(a) == set(b) and all(_eq(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return len(a) == len(b) and all(_eq(x, y) for x, y in zip(a, b))
    if isinstance(a, bool) or isinstance(b, bool):
        return a is b
    if isinstance(a, (int, float)) and isinstance(b, (int, float)):
        fa, fb = float(a), float(b)
        if math.isnan(fa) or math.isnan(fb):
            return math.isnan(fa) and math.isnan(fb)
        return fa == fb
    return a == b


_scalar = st.one_of(
    st.none(),
    st.booleans(),
    st.integers(min_value=-(2 ** 70), max_value=2 ** 70),
    st.floats(allow_nan=True, allow_infinity=True),
    st.text(max_size=20),
)

_payload = st.one_of(
    # conforming {"id", "doc"}: unicode keys, hostile values
    st.fixed_dictionaries({
        "id": st.text(min_size=1, max_size=24),
        "doc": st.dictionaries(
            st.text(min_size=1, max_size=12), _scalar, max_size=6),
    }),
    # non-conforming payloads ride the _raw json lane verbatim
    _scalar,
    st.lists(_scalar, max_size=4),
    st.dictionaries(st.text(min_size=1, max_size=8), _scalar, max_size=4),
)


@settings(max_examples=60, deadline=None)
@given(st.lists(_payload, min_size=1, max_size=40))
def test_block_roundtrip_property(payloads):
    """encode -> decode is identity (modulo the documented int-in-float
    -column coercion) for ANY mix of conforming docs with arbitrary
    unicode keys / NaN / inf values and non-conforming raw payloads."""
    recs = [(i * 3, p) for i, p in enumerate(payloads)]
    blk = next(iter_blocks(encode_block(recs)))
    out = blk.records()
    assert len(out) == len(recs)
    for (off_in, p_in), (off_out, p_out) in zip(recs, out):
        assert off_in == off_out
        assert _eq(p_in, p_out), (p_in, p_out)


@settings(max_examples=30, deadline=None)
@given(st.lists(
    st.fixed_dictionaries({
        "id": st.text(min_size=1, max_size=16),
        "doc": st.fixed_dictionaries({
            "published_at": st.floats(0, 1e9, allow_nan=False),
            "key": st.text(min_size=1, max_size=8)})}),
    min_size=1, max_size=32))
def test_block_stats_bound_every_row(payloads):
    """min/max ts and key-range stats must bound every conforming row —
    they are what pruned scans trust to SKIP blocks."""
    recs = [(i, p) for i, p in enumerate(payloads)]
    blk = next(iter_blocks(encode_block(recs)))
    ts = [p["doc"]["published_at"] for p in payloads]
    keys = [p["doc"]["key"] for p in payloads]
    if blk.stats.get("ts_min") is not None:
        assert blk.stats["ts_min"] <= min(ts)
        assert blk.stats["ts_max"] >= max(ts)
    if blk.stats.get("key_min") is not None:
        assert blk.stats["key_min"] <= min(keys)
        assert blk.stats["key_max"] >= max(keys)


def test_block_roundtrip_hostile_cases_concrete():
    """Deterministic companion to the property test above: the same
    adversarial shapes, runnable without hypothesis installed."""
    cases = [
        {"id": "ü–🦉", "doc": {"价": float("nan"), "b": float("inf"),
                               "c": -float("inf")}},
        {"id": "x", "doc": {"k": None, "m": True, "n": False}},
        {"id": "y", "doc": {"big": 2 ** 70, "neg": -(2 ** 70),
                            "mix_i": 3, "mix_f": 1.5}},
        {"id": "z", "doc": {"mixed_col": 1}},     # int half of a column
        {"id": "w", "doc": {"mixed_col": 2.5}},   # float half -> f8 lane
        "raw-string",
        ["raw", {"nested": float("nan")}],
        {"not": "conforming"},
        {"id": 5, "doc": {}},                     # non-str id -> raw lane
        {"id": "t", "doc": {"s": "текст", "li": [1, "a", None]}},
    ]
    recs = [(i * 2, p) for i, p in enumerate(cases)]
    out = next(iter_blocks(encode_block(recs))).records()
    assert len(out) == len(recs)
    for (off_in, p_in), (off_out, p_out) in zip(recs, out):
        assert off_in == off_out
        assert _eq(p_in, p_out), (p_in, p_out)
