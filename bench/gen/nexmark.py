"""NEXmark event stream as the Apache Beam Nexmark suite generates it.

A vectorised copy of Beam's ``NexmarkGenerator`` arithmetic
(``GeneratorConfig``, ``AuctionGenerator``, ``PersonGenerator``,
``BidGenerator``, ``PriceGenerator``): event ids run 0, 1, 2, ...; of
every ``person + auction + bid`` ids (1:3:46 by default) the first are
persons, the next auctions and the rest bids; event ``i`` happens at
``i / first_event_rate`` seconds of event time.  A bid goes to the hot
auction (the first of the batch of the last 100 auctions) with
probability ``1 - 1/hot_auction_ratio``, otherwise to a random auction
among the last ``num_in_flight_auctions`` plus a lead of 10; its bidder
is the hot bidder with probability ``1 - 1/hot_bidders_ratio``,
otherwise one of the last ``num_active_people`` people plus a lead of
10; its price is ``round(10**(6u) * 100)`` cents; its ``extra`` string
pads the record to ``avg_bid_byte_size`` bytes on average (+-20%).

Random draws come from NumPy's generator seeded from ``--seed``, not
from Java's ``Random``, so the ids follow Beam's distribution and not
its exact sequence.  Persons and auctions are counted to keep the id
sequences and then dropped: only bids are returned.
"""
from __future__ import annotations

import string
from dataclasses import dataclass

import numpy as np

from bench.gen import CHUNK, LogData

FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
HOT_AUCTION_RATIO = 100      # AuctionGenerator.HOT_AUCTION_RATIO
HOT_BIDDER_RATIO = 100       # PersonGenerator.HOT_BIDDER_RATIO
AUCTION_ID_LEAD = 10
PERSON_ID_LEAD = 10
BID_FIXED_BYTES = 8 + 8 + 8 + 8   # auction, bidder, price, dateTime


@dataclass
class Bids:
    ts: np.ndarray          # float64 event time (s)
    auction: np.ndarray     # int64
    bidder: np.ndarray      # int64
    price: np.ndarray       # int64 cents
    extra_len: np.ndarray   # int64 bytes of the extra string

    @property
    def count(self) -> int:
        return int(self.ts.size)


def _last_base0_auction_id(eid: np.ndarray, g: dict) -> np.ndarray:
    total = g["person_proportion"] + g["auction_proportion"] + \
        g["bid_proportion"]
    epoch, offset = eid // total, eid % total
    pp, ap = g["person_proportion"], g["auction_proportion"]
    person = offset < pp
    bid = offset >= pp + ap
    epoch = np.where(person, epoch - 1, epoch)
    offset = np.where(person | bid, ap - 1, offset - pp)
    return epoch * ap + offset


def _last_base0_person_id(eid: np.ndarray, g: dict) -> np.ndarray:
    total = g["person_proportion"] + g["auction_proportion"] + \
        g["bid_proportion"]
    epoch, offset = eid // total, eid % total
    offset = np.minimum(offset, g["person_proportion"] - 1)
    return epoch * g["person_proportion"] + offset


def generate_bids(g: dict, seed: int) -> Bids:
    """Every bid among the first ``first_event_rate * span_s`` events."""
    rng = np.random.default_rng([seed, 0x5E0B1D])
    total = g["person_proportion"] + g["auction_proportion"] + \
        g["bid_proportion"]
    n_events = int(round(g["first_event_rate"] * g["span_s"]))
    eid = np.arange(n_events, dtype=np.int64)
    eid = eid[eid % total >= g["person_proportion"] + g["auction_proportion"]]
    n = eid.size

    last_a = _last_base0_auction_id(eid, g)
    hot_a = rng.integers(0, g["hot_auction_ratio"], n) > 0
    lo = np.maximum(last_a - g["num_in_flight_auctions"], 0)
    span = last_a - lo + 1 + AUCTION_ID_LEAD
    cold_a = lo + (rng.random(n) * span).astype(np.int64)
    auction = np.where(hot_a, (last_a // HOT_AUCTION_RATIO) *
                       HOT_AUCTION_RATIO, cold_a) + FIRST_AUCTION_ID

    last_p = _last_base0_person_id(eid, g)
    hot_b = rng.integers(0, g["hot_bidders_ratio"], n) > 0
    people = last_p + 1
    active = np.minimum(people, g["num_active_people"])
    cold_b = people - active + (rng.random(n) * (active + PERSON_ID_LEAD)
                                ).astype(np.int64)
    bidder = np.where(hot_b, (last_p // HOT_BIDDER_RATIO) * HOT_BIDDER_RATIO
                      + 1, cold_b) + FIRST_PERSON_ID

    price = np.round(10.0 ** (rng.random(n) * 6.0) * 100.0).astype(np.int64)
    want = max(g["avg_bid_byte_size"] - BID_FIXED_BYTES, 0)
    delta = int(round(want * 0.2))
    extra_len = want - delta + (rng.integers(0, 2 * delta, n) if delta
                                else np.zeros(n, np.int64))
    ts = eid.astype(np.float64) / g["first_event_rate"]
    return Bids(ts=ts, auction=auction, bidder=bidder, price=price,
                extra_len=extra_len.astype(np.int64))


def log_data(config: dict, seed: int) -> LogData:
    """The bids as a sealed-log cell writes and checks them."""
    bids = generate_bids(config["generator"], seed)

    def chunks():
        for lo in range(0, bids.count, CHUNK):
            yield bid_documents(bids, seed, lo, min(lo + CHUNK, bids.count))
    return LogData(keys=bids.auction, ts=bids.ts,
                   values=bids.price.astype(np.float64), chunks=chunks)


def bid_documents(bids: Bids, seed: int, lo: int = 0, hi: int = None):
    """Log records for bids ``lo:hi``: ``key`` = auction and ``value`` =
    price, the lanes the columnar log reads, plus the bid's other
    fields at their Beam widths."""
    hi = bids.count if hi is None else hi
    rng = np.random.default_rng([seed, 0xE47A, lo])
    letters = np.frombuffer(string.ascii_letters.encode(), np.uint8)
    pool = letters[rng.integers(0, letters.size, 4096)].tobytes().decode()
    starts = rng.integers(0, 4096 - 256, hi - lo)
    out = []
    for j, i in enumerate(range(lo, hi)):
        s = int(starts[j])
        out.append((f"bid-{i}", {
            "key": str(int(bids.auction[i])),
            "value": int(bids.price[i]),
            "published_at": float(bids.ts[i]),
            "bidder": int(bids.bidder[i]),
            "extra": pool[s:s + int(bids.extra_len[i])]}))
    return out
