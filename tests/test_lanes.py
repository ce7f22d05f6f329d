"""Both document logs hand out the same event lanes.

``EventLog.scan_lanes`` reads its JSON records row by row;
``ColumnarEventLog.scan_lanes`` reads sealed blocks' lanes and its JSON
tail.  Over the same documents both must give the same event times,
keys and values, row for row, and both must equal a plain reading of
the documents: time ``published_at`` (a number, not a bool; else the
row is dropped), key ``key`` else ``channel`` else ``"all"``, value
``value`` when numeric else 1.0, non-document payloads dropped."""
import numpy as np
import pytest

from repro.store import ColumnarEventLog, EventLog

CHANNELS = ["news", "blogs", "текст", "🦉 feeds"]


def _payloads(n=240, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        if i % 17 == 4:
            out.append({"note": i})              # not a document
            continue
        doc = {"channel": CHANNELS[i % len(CHANNELS)], "title": f"t{i}"}
        if i % 7 == 0:
            doc["key"] = f"metric-{i % 3}"
        if i % 11 == 3:
            doc["published_at"] = True           # a bool is not a time
        elif i % 13 == 5:
            pass                                 # no time at all
        elif i % 2:
            doc["published_at"] = int(rng.integers(0, 900))
        else:
            doc["published_at"] = float(rng.uniform(0.0, 900.0))
        if i % 5 == 2:
            doc["value"] = "high"                # not a number: 1.0
        elif i % 3 == 1:
            doc["value"] = float(rng.uniform(-5.0, 5.0))
        out.append({"id": f"d{i}", "doc": doc})
    return out


def _plain(payloads, from_offset=0, ts_min=None, ts_max=None, keys=None):
    rows = []
    for off, p in enumerate(payloads):
        if off < from_offset or not isinstance(p.get("doc"), dict):
            continue
        d = p["doc"]
        t = d.get("published_at")
        if type(t) not in (int, float):
            continue
        key = str(d["key"]) if "key" in d else str(d.get("channel", "all"))
        v = d.get("value", 1.0)
        v = float(v) if type(v) in (int, float) else 1.0
        if (ts_min is not None and t < ts_min) or \
                (ts_max is not None and t >= ts_max) or \
                (keys is not None and key not in keys):
            continue
        rows.append((float(t), key, v))
    return rows


def _rows(lanes):
    assert lanes.ts.dtype == lanes.values.dtype == np.float64
    return list(zip(lanes.ts.tolist(),
                    [lanes.key_vocab[c] for c in lanes.key_codes.tolist()],
                    lanes.values.tolist()))


SCANS = {
    "whole": {},
    "from_offset": {"from_offset": 101},
    "ts_range": {"ts_min": 200.0, "ts_max": 650.5},
    "keys": {"keys": ["blogs", "metric-1", "🦉 feeds"]},
}


@pytest.mark.parametrize("layout", ["sealed", "tail"])
@pytest.mark.parametrize("scan", sorted(SCANS))
def test_both_logs_scan_the_same_lanes(tmp_path, scan, layout):
    payloads = _payloads()
    kw = SCANS[scan]
    with EventLog(str(tmp_path / "json"), segment_bytes=4096) as js:
        js.append(payloads)
        from_json = _rows(js.scan_lanes(**kw))
    with ColumnarEventLog(str(tmp_path / "col"), segment_bytes=1 << 30,
                          block_rows=32) as col:
        col.append(payloads[:150])
        if layout == "sealed":
            col.roll()
        col.append(payloads[150:])
        if layout == "sealed":
            col.roll()
            assert col.status()["columnar"]["sealed_columnar_segments"] == 2
        from_col = _rows(col.scan_lanes(**kw))
    want = _plain(payloads, **kw)
    assert len(want) > 20
    assert from_json == want
    assert from_col == want
