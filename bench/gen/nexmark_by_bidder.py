"""NEXmark bids keyed by bidder, for the per-user queries (Query 11,
user sessions).

The bid stream is ``bench.gen.nexmark.generate_bids`` at the
configuration's generator settings, unchanged: the same bids, times and
prices as the auction-keyed cells.  Each log record carries ``key`` =
bidder and ``value`` = price, the lanes the columnar log reads, plus the
bid's auction and its other fields at their Beam widths.
"""
from __future__ import annotations

import numpy as np

from bench.gen import CHUNK, LogData
from bench.gen.nexmark import bid_documents, generate_bids


def log_data(config: dict, seed: int) -> LogData:
    """The bids as a sealed-log cell writes and checks them, keyed by
    bidder."""
    bids = generate_bids(config["generator"], seed)

    def chunks():
        for lo in range(0, bids.count, CHUNK):
            hi = min(lo + CHUNK, bids.count)
            docs = bid_documents(bids, seed, lo, hi)
            for (_, doc), auction, bidder in zip(
                    docs, bids.auction[lo:hi].tolist(),
                    bids.bidder[lo:hi].tolist()):
                doc["key"] = str(bidder)
                doc["auction"] = auction
            yield docs
    return LogData(keys=bids.bidder, ts=bids.ts,
                   values=bids.price.astype(np.float64), chunks=chunks)
