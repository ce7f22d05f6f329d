"""The session cell's comparison: at a size a test run holds, on the
CPU (the kernel in interpret mode), ``nexmark_q11_replay`` is correct and
its bfloat16 control is not, and each fault a session layout can have,
planted under the timed path, turns ``correct`` false."""
import json

import numpy as np
import pytest

from bench.gen.nexmark import generate_bids
from bench.gen.nexmark_by_bidder import log_data
from bench.harness import BENCH, run_cell
from bench.refs.sessions import sessionize

SEED = 2 ** 32 + 12345
CELL = "nexmark_q11_replay"
Q11 = json.loads((BENCH / "configs" / "nexmark_q11.json").read_text())
# half a second of bids, and a gap of five event ids (0.5 ms), so that
# the log holds gap cuts and exact-gap pairs
SPAN_S, GAP_S = 0.5, 0.0005


def _overrides():
    return {"generator": dict(Q11["generator"], span_s=SPAN_S),
            "pipeline": dict(Q11["pipeline"], window_gap_s=GAP_S)}


def _run(**kw):
    return run_cell(CELL, SEED, 0.3, False, require_chip=False,
                    overrides=_overrides(), **kw)


def test_the_small_log_has_what_the_faults_need():
    data = log_data(_overrides(), SEED)
    order = np.lexsort((data.ts, data.keys))
    k, t = data.keys[order], data.ts[order]
    same = k[1:] == k[:-1]
    assert np.sum(same & (t[:-1] + GAP_S == t[1:])) > 0    # exact gaps
    assert np.sum(same & (t[:-1] + GAP_S < t[1:])) > 0     # gap cuts
    starts, ends = sessionize(data.keys, data.ts, GAP_S)
    sizes = np.unique(np.column_stack([data.keys, starts]), axis=0,
                      return_counts=True)[1]
    assert len(ends) == sizes.size and sizes.max() > 100


def test_bids_are_the_auction_cells_bids_keyed_by_bidder():
    g = dict(Q11["generator"], span_s=SPAN_S)
    bids = generate_bids(g, SEED)
    data = log_data({"generator": g}, SEED)
    assert np.array_equal(data.keys, bids.bidder)
    assert np.array_equal(data.ts, bids.ts)
    docs = next(data.chunks())
    assert [d["key"] for _, d in docs[:50]] == \
        [str(b) for b in bids.bidder[:50]]
    assert [d["auction"] for _, d in docs[:50]] == bids.auction[:50].tolist()


def test_sessionize_merges_at_the_gap_and_cuts_past_it():
    keys = np.array([7, 7, 7, 9, 7])
    ts = np.array([0.0, 10.0, 20.0, 5.0, np.nextafter(30.0, 31.0)])
    starts, ends = sessionize(keys, ts, 10.0)
    assert starts.tolist() == [0.0, 0.0, 0.0, 5.0, ts[4]]
    assert ends == {("7", 0.0): 30.0, ("9", 5.0): 15.0,
                    ("7", ts[4]): ts[4] + 10.0}


def test_session_cell_is_correct_and_its_control_is_not():
    r = _run(control=True)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert r["checks"]["session_end_mismatch"]["value"] == 0
    assert r["info"]["control_correct"] is False
    assert r["info"]["control"]["session_end_mismatch"]["value"] > 0
    assert list(r)[-1] == "checks"


def _split_at_exact_gap(monkeypatch):
    """The cut taken at an exact gap too (``<=`` for ``<``)."""
    import repro.alerts.batch as batch
    inner = batch._session_opens

    def opens(codes, ts, gap_s):
        by_key, by_gap = inner(codes, ts, gap_s)
        by_gap[1:] |= ~by_key[1:] & (ts[:-1] + gap_s == ts[1:])
        return by_key, by_gap
    monkeypatch.setattr(batch, "_session_opens", opens)


def _end_off_by_the_gap(monkeypatch):
    import repro.alerts.batch as batch
    inner = batch.reduce_columns

    def shifted(ts, codes, values, vocab, spec, **kw):
        out = inner(ts, codes, values, vocab, spec, **kw)
        out[len(out) // 2].window_end += spec.gap_s
        return out
    monkeypatch.setattr(batch, "reduce_columns", shifted)


def _dropped_bid(monkeypatch):
    import repro.alerts.batch as batch
    inner = batch.reduce_columns

    def dropped(ts, codes, values, vocab, spec, **kw):
        keep = np.arange(ts.size) != ts.size // 2
        return inner(ts[keep], codes[keep], values[keep], vocab, spec, **kw)
    monkeypatch.setattr(batch, "reduce_columns", dropped)


@pytest.mark.parametrize("fault", [_split_at_exact_gap, _end_off_by_the_gap,
                                   _dropped_bid])
def test_session_faults_are_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r = _run()
    assert not r["correct"] and r["failed"] >= 1


def test_pack_sessions_share_reads_a_session_replay_window():
    """The session driver's window at a tiny size: the new reader reads
    a number no larger than the whole pack stage's share."""
    import tempfile
    import time

    from bench.drivers import session_replay
    from bench.harness import CompileMonitor, Ctx, load_json, load_module

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = dict(Q11, **_overrides())
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    with tempfile.TemporaryDirectory() as tmp:
        ctx = Ctx(cell=cell, config=config, traffic=traffic, seed=SEED,
                  seconds=0.5, trace=False, control=False, tmp=tmp,
                  monitor=CompileMonitor(), t_start=time.perf_counter())
        out = session_replay.run(ctx)

    def read(name):
        return load_module(BENCH / "metrics" / f"{name}.py").read(
            out.record)
    share = read("replay.pack_sessions_share")
    assert share is not None and 0.0 < share <= read("replay.pack_share")
    per_replay = out.info["packs_per_replay"]
    assert per_replay["session_packs"] == 1.0
    assert (per_replay["sessions_key"] + per_replay["sessions_gap"]
            == out.info["slots"])
