"""The traffic generators: NEXmark's proportions and hot keys, and the
feed fleet's determinism and the documents it gives."""
import json

import numpy as np
import pytest

from bench.gen.feeds import generate_items, log_data
from bench.gen.nexmark import (FIRST_AUCTION_ID, bid_documents,
                               generate_bids)
from bench.harness import BENCH

NEXMARK = json.loads((BENCH / "configs" / "nexmark_q5.json").read_text())
FEEDS = json.loads((BENCH / "configs" / "alertmix_feeds.json").read_text())


@pytest.fixture(scope="module")
def bids():
    g = dict(NEXMARK["generator"], span_s=5.0)     # 50,000 events
    return g, generate_bids(g, seed=2 ** 33 + 5)


def test_nexmark_proportions(bids):
    g, b = bids
    assert b.count == 50_000 * 46 // 50
    assert np.all(np.diff(b.ts) > 0)
    assert b.ts[-1] < g["span_s"]


def test_nexmark_hot_auction_share(bids):
    g, b = bids
    eid = np.round(b.ts * g["first_event_rate"]).astype(np.int64)
    last = (eid // 50) * 3 + 2 + FIRST_AUCTION_ID
    hot = (last - FIRST_AUCTION_ID) // 100 * 100 + FIRST_AUCTION_ID
    share = np.mean(b.auction == hot)
    # 1 - 1/hot_auction_ratio, plus the cold draws that land on it
    assert 0.49 < share < 0.53
    hot_bidder = np.mean(b.bidder % 100 == (1 + 1000) % 100)
    assert 0.74 < hot_bidder < 0.78


def test_nexmark_in_flight_window(bids):
    g, b = bids
    eid = np.round(b.ts * g["first_event_rate"]).astype(np.int64)
    last = (eid // 50) * 3 + 2 + FIRST_AUCTION_ID
    assert np.all(b.auction <= last + 10)
    assert np.all(b.auction >= np.maximum(
        last - g["num_in_flight_auctions"], FIRST_AUCTION_ID) - 100)
    cold = b.auction % 100 != 0
    assert np.all(b.auction[cold] >= last[cold] - 100)


def test_nexmark_records_carry_beam_widths(bids):
    g, b = bids
    docs = bid_documents(b, 1, 0, 200)
    lens = [len(d["extra"]) for _, d in docs]
    assert 100 - 32 - 14 <= min(lens) and max(lens) < 100 - 32 + 14
    assert all(d["key"] == str(a) for (_, d), a in zip(docs, b.auction))
    assert bid_documents(b, 1, 0, 200) == docs


def test_nexmark_config_names_what_it_changed():
    assert NEXMARK["source"] and len(NEXMARK["source"]) <= 200
    assert set(NEXMARK["reduced"]) == {"span_s", "persons_and_auctions",
                                       "max_bids"}
    assert NEXMARK["assumed"]
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cfg = {c["name"]: c for c in bench["configs"]}["nexmark_q5"]
    assert sorted(cfg["reduced"]) == sorted(NEXMARK["reduced"])


def _feeds(n=300):
    return dict(FEEDS, pipeline=dict(FEEDS["pipeline"], num_sources=n))


def test_a_fixed_seed_gives_identical_items():
    g = FEEDS["generator"]
    a = generate_items(g, 300, 3600.0, 2 ** 40 + 1)
    b = generate_items(g, 300, 3600.0, 2 ** 40 + 1)
    c = generate_items(g, 300, 3600.0, 2 ** 40 + 2)
    for f in ("src", "ts", "dup", "malformed", "text"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.ts, c.ts) or a.ts.size != c.ts.size
    assert a.ts.min() >= 0.0 and a.ts.max() <= 3600.0


def test_the_fleet_follows_its_rate_model():
    """Night hours at 0.35 of the base rate, 5% syndicated, 1%
    malformed."""
    g = FEEDS["generator"]
    items = generate_items(g, 20_000, 3600.0, 2 ** 41 + 3)
    expect = 20_000 * g["base_rate_per_hour"] * 0.35
    assert abs(items.ts.size - expect) < 0.03 * expect
    assert abs(items.dup.mean() - g["dup_fraction"]) < 0.005
    assert abs(items.malformed.mean() - g["malformed_fraction"]) < 0.003


def test_log_documents_drop_malformed_and_repeated_guids():
    seed = 2 ** 35 + 11
    cfg = _feeds()
    data = log_data(cfg, seed)
    docs = [d for batch in data.chunks() for d in batch]
    guids = [guid for guid, _ in docs]
    assert len(set(guids)) == len(guids) == data.count
    assert np.all(np.diff(data.ts) >= 0)
    mix = set(cfg["pipeline"]["channel_mix"])
    assert set(data.keys.tolist()) <= mix
    again = log_data(cfg, seed)
    assert np.array_equal(again.ts, data.ts)
    assert np.array_equal(again.keys, data.keys)
