"""The multi-feed fleet's items and the documents a pipeline accepts
from them.

The rate model is a copy of the repository's ``SourceSimulator``: every
source publishes a Poisson number of items per hour at
``base_rate_per_hour * diurnal(hour) * max(0.1, burst)``, with
``diurnal = 0.35 + 0.65 * max(0, sin((hour - 5) / 24 * 2 pi))`` and
``burst = 1 + 0.3 * sin(source % 97 + hour)``, each item at a uniform
time in its hour; 5% are syndicated duplicates (a guid shared across
sources), 1% are malformed.  The hour of day at virtual time 0 is
``start_hour``.  Everything is drawn from ``--seed``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from bench.gen import CHUNK, LogData

_WORDS = (
    "market news alert update report breaking global local tech sports "
    "science health economy election storm earnings launch study race "
    "deal vote court data strike rally quake fire flood win loss open"
).split()


@dataclass
class FleetItems:
    """Items of every source, sorted by (source, time)."""
    src: np.ndarray         # int64 source index
    ts: np.ndarray          # float64 publish time (virtual s)
    bucket: np.ndarray      # int64 hour bucket
    ordinal: np.ndarray     # int64 index of the item in its bucket
    dup: np.ndarray         # bool syndicated
    malformed: np.ndarray   # bool
    text: np.ndarray        # int64 index into the title/body pool

    def guid(self, k: int) -> str:
        b, i = int(self.bucket[k]), int(self.ordinal[k])
        if self.dup[k]:
            return f"syndicated-{b}-{i % 7}"
        return f"{int(self.src[k])}-{b}-{i}"


def hourly_rate(g: dict, sources: np.ndarray, bucket: int) -> np.ndarray:
    hour = (g["start_hour"] + bucket) % 24.0
    diurnal = 0.35 + 0.65 * max(0.0, math.sin((hour - 5.0) / 24.0 * 2
                                              * math.pi))
    burst = 1.0 + 0.3 * np.sin(sources % 97 + hour)
    return g["base_rate_per_hour"] * diurnal * np.maximum(0.1, burst)


def generate_items(g: dict, num_sources: int, span_s: float,
                   seed: int) -> FleetItems:
    """Items published in ``[0, span_s]``."""
    rng = np.random.default_rng([seed, 0xFEED5])
    sources = np.arange(num_sources, dtype=np.int64)
    parts = {k: [] for k in ("src", "ts", "bucket", "ordinal")}
    for b in range(int(math.ceil(span_s / 3600.0))):
        n = rng.poisson(hourly_rate(g, sources, b))
        src = np.repeat(sources, n)
        first = np.cumsum(n) - n
        parts["src"].append(src)
        parts["bucket"].append(np.full(src.size, b, np.int64))
        parts["ordinal"].append(np.arange(src.size) - np.repeat(first, n))
        parts["ts"].append(b * 3600.0 + rng.random(src.size) * 3600.0)
    cols = {k: np.concatenate(v) for k, v in parts.items()}
    keep = cols["ts"] <= span_s
    cols = {k: v[keep] for k, v in cols.items()}
    order = np.lexsort((cols["ts"], cols["src"]))
    cols = {k: v[order] for k, v in cols.items()}
    m = cols["src"].size
    return FleetItems(
        src=cols["src"], ts=cols["ts"], bucket=cols["bucket"],
        ordinal=cols["ordinal"], dup=rng.random(m) < g["dup_fraction"],
        malformed=rng.random(m) < g["malformed_fraction"],
        text=rng.integers(0, g["text_pool"], m))


def text_pool(g: dict, seed: int):
    rng = np.random.default_rng([seed, 0x7E47])
    words = np.array(_WORDS)
    titles = [" ".join(words[rng.integers(0, words.size, 6)])
              for _ in range(g["text_pool"])]
    bodies = [" ".join(words[rng.integers(0, words.size, 60)])
              for _ in range(g["text_pool"])]
    return titles, bodies


def channel_of_sources(channel_mix: dict, num_sources: int,
                       seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 0xC4A7])
    names = np.array(list(channel_mix))
    p = np.array([channel_mix[c] for c in names], np.float64)
    return names[rng.choice(names.size, num_sources, p=p / p.sum())]


def log_data(config: dict, seed: int) -> LogData:
    """The documents the fleet's first ``log_span_s`` give, as a
    sealed-log cell writes and checks them."""
    g = config["generator"]
    n = config["pipeline"]["num_sources"]
    items = generate_items(g, n, g["log_span_s"], seed)
    titles, bodies = text_pool(g, seed)
    channels = channel_of_sources(config["pipeline"]["channel_mix"], n, seed)
    docs = accepted_documents(items, channels, titles, bodies,
                              g["log_span_s"])

    def chunks():
        for lo in range(0, len(docs), CHUNK):
            yield docs[lo:lo + CHUNK]
    return LogData(
        keys=np.array([d["channel"] for _, d in docs]),
        ts=np.array([d["published_at"] for _, d in docs], np.float64),
        values=np.ones(len(docs)), chunks=chunks)


def accepted_documents(items: FleetItems, channels, titles, bodies,
                       upto_s: float):
    """The documents a pipeline accepts from the fleet's items published
    in ``[0, upto_s]``: malformed items and repeated guids dropped, in
    publish order, each with the source's channel."""
    sel = np.flatnonzero((items.ts >= 0.0) & (items.ts <= upto_s))
    sel = sel[np.argsort(items.ts[sel], kind="stable")]
    seen = set()
    out = []
    for k in sel:
        if items.malformed[k]:
            continue
        guid = items.guid(k)
        if guid in seen:
            continue
        seen.add(guid)
        t = int(items.text[k])
        src = int(items.src[k])
        out.append((guid, {"title": titles[t], "body": bodies[t],
                           "published_at": float(items.ts[k]), "sid": src,
                           "channel": channels[src],
                           "ingested_at": float(items.ts[k])}))
    return out
