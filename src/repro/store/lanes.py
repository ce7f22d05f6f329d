"""What a document's event is, and the lanes a log hands out.

Every log payload the store plane appends is ``{"id": ..., "doc":
{...}}``.  A document's event is its (time, key, value):

    time   ``doc["published_at"]``, a number (a bool is not one); a row
           without one is dropped
    key    ``doc_key(doc)``
    value  ``doc["value"]`` when it is a number, else 1.0

Both logs read documents by this rule: ``EventLog.scan_lanes`` row by
row (``add_doc_rows``), ``ColumnarEventLog.scan_lanes`` from its sealed
blocks' lanes and, for its JSON tail, row by row the same way.  The key
is a data-format decision: sealed blocks freeze ``doc_key`` into their
``_key`` lane.  ``Lanes`` is what a scan returns, one row per event, and
what the batch path (``repro.alerts.batch.reduce_columns``) reduces.
"""
from __future__ import annotations

from dataclasses import dataclass
from itertools import count
from typing import Iterable, List, Optional, Set, Tuple

import numpy as np


def doc_key(doc: dict) -> str:
    """A document's aggregation key."""
    return str(doc.get("key", doc.get("channel", "all")))


def is_number(x) -> bool:
    """Whether a document field reads as a number: an int or a float,
    not a bool."""
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def doc_event(payload) -> Optional[Tuple[float, str, float]]:
    """A log payload's (time, key, value), or None when the row is not
    a document or has no numeric time."""
    if not (isinstance(payload, dict) and isinstance(payload.get("doc"),
                                                     dict)):
        return None
    doc = payload["doc"]
    ts = doc.get("published_at")
    if not is_number(ts):
        return None
    v = doc.get("value", 1.0)
    return float(ts), doc_key(doc), float(v) if is_number(v) else 1.0


@dataclass
class Lanes:
    """Column arrays ready for the batch kernel path: one row per
    event, already filtered/pruned — no per-record objects."""
    ts: np.ndarray                      # float64 event times
    key_codes: np.ndarray               # int64 codes into key_vocab
    key_vocab: List[str]
    values: np.ndarray                  # float64 value lane

    @property
    def count(self) -> int:
        return int(self.ts.shape[0])


def decode_utf8(entries: List[bytes]) -> List[str]:
    """utf-8 bytes back to strings; ``surrogatepass`` lets every Python
    string round-trip, lone surrogates included."""
    try:
        return list(map(bytes.decode, entries))
    except UnicodeDecodeError:          # a lone surrogate
        return [b.decode("utf-8", "surrogatepass") for b in entries]


def add_doc_rows(records: Iterable[Tuple[int, object]],
                 entries: List[bytes], ts_parts: List[np.ndarray],
                 entry_parts: List[np.ndarray],
                 val_parts: List[np.ndarray], *,
                 ts_min: Optional[float] = None,
                 ts_max: Optional[float] = None,
                 keyset: Optional[Set[str]] = None) -> None:
    """Read ``(offset, payload)`` records by the rule above into a
    scan's parts (see ``merge_lanes``): rows in ``[ts_min, ts_max)``
    whose key is in ``keyset``, each row's key one more entry."""
    rows: List[Tuple[float, int, float]] = []
    for _, payload in records:
        event = doc_event(payload)
        if event is None:
            continue
        ts, key, v = event
        if (ts_min is not None and ts < ts_min) or \
                (ts_max is not None and ts >= ts_max):
            continue
        if keyset is not None and key not in keyset:
            continue
        rows.append((ts, len(entries), v))
        entries.append(key.encode("utf-8", "surrogatepass"))
    if rows:
        arr = np.array(rows, dtype=np.float64)
        ts_parts.append(arr[:, 0])
        entry_parts.append(arr[:, 1].astype(np.int64))
        val_parts.append(arr[:, 2])


def merge_lanes(entries: List[bytes], ts_parts: List[np.ndarray],
                entry_parts: List[np.ndarray],
                val_parts: List[np.ndarray]) -> Lanes:
    """One vocabulary from the scanned key entries (utf-8 bytes), codes
    in the order the keys were first seen, and the lanes concatenated.
    One dict operation per entry: ``seen`` is the index of the entry's
    first sighting, and those indices rise in the order the keys were
    first seen, so ranking them gives the codes.  Only the distinct keys
    are decoded to ``str``."""
    if not ts_parts:
        return Lanes(ts=np.empty(0), key_codes=np.empty(0, dtype=np.int64),
                     key_vocab=[], values=np.empty(0))
    first: dict = {}
    seen = np.fromiter(map(first.setdefault, entries, count()),
                       dtype=np.int64, count=len(entries))
    code_of = np.empty(len(entries), dtype=np.int64)
    code_of[np.fromiter(first.values(), dtype=np.int64,
                        count=len(first))] = np.arange(len(first))
    return Lanes(ts=np.concatenate(ts_parts),
                 key_codes=code_of[seen][np.concatenate(entry_parts)],
                 key_vocab=decode_utf8(list(first)),
                 values=np.concatenate(val_parts))
