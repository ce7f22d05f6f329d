"""Generators, one module each, chosen by a configuration's
``generator.kind``: ``bench/gen/<kind>.py``.

``log_data(config, seed)`` gives what a sealed-log cell needs: the log's
documents, written in chunks through the store's own writer, and the
same events as plain arrays (key, event time, value) for the reference.
A generator module that serves sealed-log cells defines its own
``log_data(config, seed) -> LogData``.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Callable, Iterator, List

import numpy as np

CHUNK = 4096


@dataclass
class LogData:
    keys: np.ndarray            # slot key per event (str or int)
    ts: np.ndarray              # float64 event time
    values: np.ndarray          # float64 value lane
    chunks: Callable[[], Iterator[List[tuple]]]   # (doc_id, doc) batches

    @property
    def count(self) -> int:
        return int(self.ts.size)


def log_data(config: dict, seed: int) -> LogData:
    kind = config["generator"]["kind"]
    return importlib.import_module(f"bench.gen.{kind}").log_data(config,
                                                                  seed)
