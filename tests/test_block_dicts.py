"""Columnar blocks keep their string dictionaries in the checksummed
payload: hostile strings round-trip, the header carries no dictionary,
a flipped dictionary byte fails the checksum, blocks of the older layout
(magic ``ACB1``, dictionaries as json in the header) still decode, alone
and mixed with new ones in one log, an unknown magic is refused, and
``scan_lanes`` gives the same lanes and vocabulary as the older decoder
on the same records."""
import json
import os
import zlib
from typing import Callable, List, Optional, Sequence

import numpy as np
import pytest

from repro.obs import columnar_blocks_decoded
from repro.store.columnar import ColumnarEventLog, encode_block, iter_blocks
from repro.store.columnar.blocks import (LEGACY_MAGIC, MAGIC,
                                         CorruptBlockError, Record,
                                         _classify, default_key)
from repro.store.segment_log import CorruptSegmentError

HOSTILE = ["", "\0", "a\0b", "ab\0", "\0\0", "текст", "🦉 ключ", "k\ud800",
           "news"]


# ---- a frozen copy of encode_block as it was before dictionaries moved
# ---- into the payload: each dictionary is a json list in the header

def legacy_encode_block(records: Sequence[Record], *,
                        key_of: Callable[[dict], str] = default_key
                        ) -> bytes:
    rows = len(records)
    offs = np.array([o for o, _ in records], dtype="<i8")
    docs: List[Optional[dict]] = []
    ids: List[Optional[str]] = []
    raws: List[object] = [None] * rows
    raw_mask = np.zeros(rows, dtype=np.uint8)
    for i, (_, p) in enumerate(records):
        if (isinstance(p, dict) and set(p) == {"id", "doc"}
                and isinstance(p["doc"], dict)
                and isinstance(p["id"], str)):
            docs.append(p["doc"])
            ids.append(p["id"])
        else:
            docs.append(None)
            ids.append(None)
            raws[i] = p
            raw_mask[i] = 1
    fields: dict = {}
    for i, doc in enumerate(docs):
        if doc is None:
            continue
        for k, v in doc.items():
            col = fields.get(k)
            if col is None:
                col = ([None] * rows, np.zeros(rows, dtype=np.uint8))
                fields[k] = col
            col[0][i] = v
            col[1][i] = 1
    keys = ["" if d is None else key_of(d) for d in docs]
    payload = bytearray()
    cols: List[dict] = []

    def emit(name, kind, data, *, mask=None, extra=None):
        desc = {"name": name, "kind": kind, "off": len(payload),
                "n": len(data)}
        payload.extend(data)
        if mask is not None and int(mask.sum()) != rows:
            desc["mask"] = len(payload)
            payload.extend(mask.tobytes())
        if extra:
            desc.update(extra)
        cols.append(desc)

    def emit_dict(name, values, mask):
        vocab: List[str] = []
        index: dict = {}
        codes = np.empty(rows, dtype="<u4")
        for i, s in enumerate(values):
            c = index.get(s)
            if c is None:
                c = index[s] = len(vocab)
                vocab.append(s)
            codes[i] = c
        emit(name, "dict", codes.tobytes(), mask=mask,
             extra={"vocab": vocab})

    emit("_off", "i8", offs.tobytes())
    emit_dict("_key", keys, None)
    if int(raw_mask.sum()):
        emit("_raw", "json",
             json.dumps(raws, separators=(",", ":")).encode("utf-8"),
             mask=raw_mask)
    if any(i is not None for i in ids):
        id_vals = ["" if s is None else s for s in ids]
        lens = np.array([len(s.encode("utf-8")) for s in id_vals],
                        dtype="<u4")
        data = lens.tobytes() + "".join(id_vals).encode("utf-8")
        emit("id", "str", data, mask=1 - raw_mask)
    for name in sorted(fields):
        values, mask = fields[name]
        present = [v for v, m in zip(values, mask) if m]
        kind = _classify(present)
        if kind == "i8":
            arr = np.array([0 if v is None else v for v in values],
                           dtype="<i8")
            emit("d:" + name, "i8", arr.tobytes(), mask=mask)
        elif kind == "f8":
            arr = np.array([0.0 if v is None else float(v) for v in values],
                           dtype="<f8")
            emit("d:" + name, "f8", arr.tobytes(), mask=mask)
        elif kind == "dict":
            emit_dict("d:" + name, ["" if v is None else v for v in values],
                      mask)
        else:
            emit("d:" + name, "json",
                 json.dumps(values, separators=(",", ":")).encode("utf-8"),
                 mask=mask)
    ts_vals = [d["published_at"] for d in docs
               if d is not None and isinstance(d.get("published_at"),
                                               (int, float))
               and not isinstance(d.get("published_at"), bool)]
    real_keys = [k for k, d in zip(keys, docs) if d is not None]
    stats = {
        "min_ts": float(min(ts_vals)) if ts_vals else None,
        "max_ts": float(max(ts_vals)) if ts_vals else None,
        "min_key": min(real_keys) if real_keys else None,
        "max_key": max(real_keys) if real_keys else None,
    }
    body = bytes(payload)
    header = {"rows": rows, "first": int(offs[0]), "last": int(offs[-1]),
              "plen": len(body), "crc": zlib.crc32(body),
              "stats": stats, "cols": cols}
    hjson = json.dumps(header, separators=(",", ":")).encode("utf-8")
    return b"ACB1" + len(hjson).to_bytes(4, "little") + hjson + body


# ---- a frozen copy of scan_lanes as it was: one intern call per entry
# ---- of each block's str vocabulary

def legacy_scan_lanes(log, from_offset=0, *, ts_min=None, ts_max=None,
                      keys=None):
    keyset = None if keys is None else set(keys)
    vocab: List[str] = []
    vindex: dict = {}
    ts_parts, code_parts, val_parts = [], [], []

    def intern(key):
        c = vindex.get(key)
        if c is None:
            c = vindex[key] = len(vocab)
            vocab.append(key)
        return c

    for blk in log.scan_columns(from_offset, ts_min=ts_min, ts_max=ts_max,
                                keys=keys):
        bts = blk.lane_ts()
        mask = ~np.isnan(bts)
        if from_offset > blk.first:
            mask &= blk.offsets() >= from_offset
        if ts_min is not None:
            mask &= bts >= ts_min
        if ts_max is not None:
            mask &= bts < ts_max
        codes, bvocab = blk.lane_key()
        if keyset is not None:
            allowed = np.array([s in keyset for s in bvocab], dtype=bool)
            mask &= allowed[codes]
        if not mask.any():
            continue
        remap = np.array([intern(s) for s in bvocab], dtype=np.int64)
        ts_parts.append(bts[mask])
        code_parts.append(remap[codes[mask]])
        val_parts.append(blk.lane_value()[mask])
    tail_rows = []
    for off, payload in _tail_records(log):
        if off < from_offset or not (isinstance(payload, dict) and
                                     isinstance(payload.get("doc"), dict)):
            continue
        doc = payload["doc"]
        ts = doc.get("published_at")
        if isinstance(ts, bool) or not isinstance(ts, (int, float)):
            continue
        ts = float(ts)
        if (ts_min is not None and ts < ts_min) or \
                (ts_max is not None and ts >= ts_max):
            continue
        key = default_key(doc)
        if keyset is not None and key not in keyset:
            continue
        v = doc.get("value", 1.0)
        v = float(v) if isinstance(v, (int, float)) \
            and not isinstance(v, bool) else 1.0
        tail_rows.append((ts, intern(key), v))
    if tail_rows:
        arr = np.array(tail_rows, dtype=np.float64)
        ts_parts.append(arr[:, 0])
        code_parts.append(arr[:, 1].astype(np.int64))
        val_parts.append(arr[:, 2])
    if not ts_parts:
        return np.empty(0), np.empty(0, dtype=np.int64), [], np.empty(0)
    return (np.concatenate(ts_parts), np.concatenate(code_parts), vocab,
            np.concatenate(val_parts))


def _tail_records(log):
    if log._active_name is None:
        return []
    log._fh.flush()
    return log._scan_file(log._active_name)[0]


# ---- records and logs -------------------------------------------------------

def _records(n, start=0, keys=HOSTILE):
    """Documents with hostile keys, plus rows the lanes drop: a raw
    payload, a document with no event time, one with a bool value."""
    out = []
    for i in range(start, start + n):
        doc = {"title": f"t{i % 5}", "published_at": float(i % 97),
               "key": keys[(i * 7) % len(keys)], "value": float(i % 11)}
        if i % 13 == 0:
            doc = {"title": "late", "channel": "rss"}
        elif i % 17 == 0:
            doc["value"] = True
        out.append({"id": f"d{i}", "doc": doc})
    out[len(out) // 2] = ["raw", {"n": start}]
    return out


def _log(tmp_path, name="log"):
    return ColumnarEventLog(str(tmp_path / name), segment_bytes=6000,
                            block_rows=16)


def _to_legacy(log, every=1):
    """Rewrite every ``every``-th sealed segment in the older layout, block
    for block, from its own records."""
    for seg in log._sealed[::every]:
        path = os.path.join(log.dir, seg.name)
        with open(path, "rb") as fh:
            blocks = list(iter_blocks(fh.read()))
        with open(path, "wb") as fh:
            for blk in blocks:
                fh.write(legacy_encode_block(blk.records()))


def _filled(tmp_path, name="log", tail=True):
    log = _log(tmp_path, name)
    for i in range(0, 400, 40):
        log.append(_records(40, start=i))
    log.roll()
    if tail:
        log.append(_records(25, start=400))
    return log


def _payload_dict_desc(block_bytes, name="_key"):
    blk = next(iter_blocks(block_bytes))
    return blk, next(c for c in blk.header["cols"] if c["name"] == name)


# ---- the new layout ---------------------------------------------------------

@pytest.mark.parametrize("keys", [
    ["ab", "bc", "", "a\0b", "ü", "\ud800"],
    HOSTILE,                               # "ab\0" ends in NUL
    ["x", "y" * 300],                      # one long entry
])
def test_dictionaries_round_trip_in_the_payload(keys):
    recs = [(i, {"id": f"d{i}", "doc": {"published_at": float(i),
                                        "key": keys[i % len(keys)],
                                        "title": keys[-1 - i % len(keys)]}})
            for i in range(3 * len(keys))]
    data = encode_block(recs)
    assert data[:4] == MAGIC == b"ACB2"
    blk, desc = _payload_dict_desc(data)
    assert blk.layout == "payload" and "vocab" not in desc
    assert desc["vn"] == len(set(keys))
    assert blk.records() == recs
    codes, vocab = blk.lane_key()
    assert [vocab[c] for c in codes] == [keys[i % len(keys)]
                                         for i in range(len(recs))]
    codes_b, entries = blk.lane_key_bytes()
    assert np.array_equal(codes_b, codes)
    assert entries == [k.encode("utf-8", "surrogatepass") for k in vocab]
    kind, (tcodes, tvocab), _ = blk.column("d:title")
    assert kind == "dict" and [tvocab[c] for c in tcodes] == \
        [r[1]["doc"]["title"] for r in recs]


def test_the_header_carries_no_dictionary():
    recs = [(i, {"id": f"d{i}", "doc": {"published_at": float(i),
                                        "key": f"key-{i % 7}",
                                        "extra": f"pad{i:06d}" * 10}})
            for i in range(200)]
    data = encode_block(recs)
    hlen = int.from_bytes(data[4:8], "little")
    header = data[8:8 + hlen]
    assert b"pad000199" not in header and b"key-3" not in header[
        :header.index(b'"stats"')]
    assert b"pad000199" in data[8 + hlen:]
    # the header is a small fraction of what it held with json dictionaries
    legacy = legacy_encode_block(recs)
    assert hlen * 10 < int.from_bytes(legacy[4:8], "little")


def test_a_flipped_dictionary_byte_fails_the_checksum(tmp_path):
    recs = [(i, {"id": f"d{i}", "doc": {"published_at": float(i),
                                        "key": f"key-{i % 5}"}})
            for i in range(40)]
    data = bytearray(encode_block(recs))
    _, desc = _payload_dict_desc(bytes(data))
    hlen = int.from_bytes(data[4:8], "little")
    data[8 + hlen + desc["voff"] + 2] ^= 0x01      # inside "key-..."
    with pytest.raises(CorruptBlockError, match="checksum"):
        list(iter_blocks(bytes(data)))
    # the older layout's dictionary lay outside the checksum: the same
    # flip changed a key and went unnoticed
    legacy = bytearray(legacy_encode_block(recs))
    legacy[legacy.index(b'"key-1"') + 2] ^= 0x01
    blk = next(iter_blocks(bytes(legacy)))
    assert "kdy-1" in blk.lane_key()[1]


def test_a_flipped_dictionary_byte_in_a_log_raises(tmp_path):
    log = _filled(tmp_path, tail=False)
    log.close()
    seg = log._sealed[0].name
    path = tmp_path / "log" / seg
    raw = bytearray(path.read_bytes())
    _, desc = _payload_dict_desc(bytes(raw))
    hlen = int.from_bytes(raw[4:8], "little")
    raw[8 + hlen + desc["voff"]] ^= 0x20
    path.write_bytes(bytes(raw))
    log2 = _log(tmp_path)
    with pytest.raises(CorruptSegmentError):
        log2.scan_lanes()
    with pytest.raises(CorruptSegmentError):
        list(log2.scan())
    log2.close()


# ---- the older layout -------------------------------------------------------

def test_a_legacy_block_still_decodes():
    recs = [(i, p) for i, p in enumerate(_records(60))]
    new = next(iter_blocks(encode_block(recs)))
    legacy = legacy_encode_block(recs)
    assert legacy[:4] == LEGACY_MAGIC
    old = next(iter_blocks(legacy))
    assert (old.layout, new.layout) == ("header", "payload")
    assert old.records() == new.records() == recs
    assert old.lane_key()[1] == new.lane_key()[1]
    assert np.array_equal(old.lane_key()[0], new.lane_key()[0])
    assert old.lane_key_bytes()[1] == new.lane_key_bytes()[1]
    assert np.array_equal(old.lane_ts(), new.lane_ts(), equal_nan=True)
    assert np.array_equal(old.lane_value(), new.lane_value())


@pytest.mark.parametrize("every", [1, 2])
def test_a_legacy_or_mixed_log_scans_like_its_records(tmp_path, every):
    log = _filled(tmp_path)
    before = _lanes(log.scan_lanes())
    _to_legacy(log, every=every)
    layouts = {next(iter_blocks((tmp_path / "log" / s.name).read_bytes())
                    ).layout for s in log._sealed}
    assert layouts == ({"header"} if every == 1 else {"header", "payload"})
    assert list(log.scan()) == [(i, p) for i, p in
                                enumerate(sum((_records(40, start=i)
                                               for i in range(0, 400, 40)),
                                              []) + _records(25, start=400))]
    ts, codes, vocab, values = _lanes(log.scan_lanes())
    # the record scan, folded as the pipeline's extractors read it
    rows = [(p["doc"]["published_at"], default_key(p["doc"]),
             p["doc"]["value"] if not isinstance(p["doc"]["value"], bool)
             else 1.0)
            for _, p in log.scan()
            if isinstance(p, dict) and "published_at" in p["doc"]]
    assert ts.tolist() == [r[0] for r in rows]
    assert [vocab[c] for c in codes] == [r[1] for r in rows]
    assert values.tolist() == [r[2] for r in rows]
    # the vocabulary also holds the keys of dropped rows ("" of the raw
    # payload, "rss" of documents with no event time), in block order
    kept = set(r[1] for r in rows)
    assert [k for k in vocab if k in kept] == list(
        dict.fromkeys(r[1] for r in rows))
    _same(before, (ts, codes, vocab, values))
    log.close()


@pytest.mark.parametrize("magic", [b"ACB0", b"ACB3", b"\0\0\0\0"])
def test_an_unknown_magic_is_refused(magic):
    data = encode_block([(0, {"id": "d0", "doc": {"published_at": 1.0}})])
    with pytest.raises(CorruptBlockError, match="bad block magic"):
        list(iter_blocks(magic + data[4:]))


# ---- scan_lanes against the older decoder -----------------------------------

def _lanes(lanes):
    return lanes.ts, lanes.key_codes, lanes.key_vocab, lanes.values


def _same(a, b):
    assert np.array_equal(a[0], b[0]) and a[0].dtype == b[0].dtype
    assert np.array_equal(a[1], b[1]) and a[1].dtype == b[1].dtype
    assert a[2] == b[2]
    assert np.array_equal(a[3], b[3]) and a[3].dtype == b[3].dtype


@pytest.mark.parametrize("scan", [
    {},
    {"from_offset": 123},
    {"ts_min": 20.0, "ts_max": 60.0},
    {"keys": ["", "ab\0", "текст", "absent"]},
    {"keys": ["k\ud800"], "ts_min": 10.0, "from_offset": 50},
])
def test_scan_lanes_equals_the_older_decoder(tmp_path, scan):
    new = _filled(tmp_path, "new")
    old = _filled(tmp_path, "old")
    _to_legacy(old)
    expect = legacy_scan_lanes(old, **scan)
    assert len(expect[2]) > 0
    _same(_lanes(new.scan_lanes(**scan)), expect)
    _same(_lanes(old.scan_lanes(**scan)), expect)
    new.close()
    old.close()


def test_scan_lanes_keeps_first_seen_order_across_blocks(tmp_path):
    """Keys that recur in later blocks keep their first code; a key first
    seen in the tail comes last."""
    log = _log(tmp_path)
    log.append([{"id": f"a{i}", "doc": {"published_at": 1.0,
                                        "key": "bcad"[i % 4]}}
                for i in range(32)])
    log.roll()
    log.append([{"id": "z", "doc": {"published_at": 2.0, "key": "tail"}},
                {"id": "y", "doc": {"published_at": 2.0, "key": "a"}}])
    lanes = log.scan_lanes()
    assert lanes.key_vocab == ["b", "c", "a", "d", "tail"]
    assert lanes.key_codes.tolist() == [0, 1, 2, 3] * 8 + [4, 2]
    log.close()


# ---- spans and the counter --------------------------------------------------

def test_scan_lanes_times_two_sub_stages_once_per_call(tmp_path):
    from repro.obs import StageProfiler

    log = _filled(tmp_path)
    prof = StageProfiler("t")
    for _ in range(3):
        with prof.stage("decode"):
            log.scan_lanes(profiler=prof)
    snap = prof.snapshot()
    assert snap["decode.blocks"]["calls"] == snap["decode.keys"]["calls"] == 3
    assert snap["decode.blocks"]["total_ms"] + snap["decode.keys"][
        "total_ms"] <= snap["decode"]["total_ms"]
    log.close()


def test_blocks_decoded_are_counted_by_layout(tmp_path):
    log = _filled(tmp_path, tail=False)
    blocks = sum(len(list(iter_blocks((tmp_path / "log" / s.name)
                                      .read_bytes())))
                 for s in log._sealed)
    c0 = columnar_blocks_decoded()
    log.scan_lanes()
    c1 = columnar_blocks_decoded()
    assert (c1["payload"] - c0["payload"], c1["header"] - c0["header"]) \
        == (blocks, 0)
    _to_legacy(log, every=2)
    old = sum(len(list(iter_blocks((tmp_path / "log" / s.name)
                                   .read_bytes())))
              for s in log._sealed[::2])
    c2 = columnar_blocks_decoded()
    log.scan_lanes()
    c3 = columnar_blocks_decoded()
    assert (c3["payload"] - c2["payload"], c3["header"] - c2["header"]) \
        == (blocks - old, old)
    log.close()


def test_the_pipeline_exports_blocks_decoded(tmp_path):
    from repro.core import AlertMixPipeline, PipelineConfig

    p = AlertMixPipeline(
        PipelineConfig(num_sources=0, analytics=True, store_columnar=True,
                       store_dir=str(tmp_path / "s")), seed=0)
    try:
        p.store.append_documents(
            [(f"d{i}", {"title": "t", "published_at": float(i),
                        "channel": "news"}) for i in range(50)])
        p.store.log.roll()
        assert p.store.log.scan_lanes().count == 50
        text = p.metrics_text()
        for layout in ("payload", "header"):
            assert f'columnar_blocks_decoded_total{{layout="{layout}"}}' \
                in text
        assert p.obs.metrics.counter("columnar_blocks_decoded_total").value(
            layout="payload") == columnar_blocks_decoded()["payload"] >= 1
    finally:
        p.close()
