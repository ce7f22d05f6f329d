"""Kernel launch counters per route — which part of the program sends
work to the device, how much, and how often at a shape never seen.

Every launch that ``repro.kernels.ops`` makes of a kernel is counted
under the route that asked for it:

  replay   ``ReplayEngine.replay_log`` / ``replay_columns``
  drain    ``ReplayEngine.replay_late_events`` (late-event journal)
  query    ``QueryEngine`` cold-range scans
  direct   any other caller (benchmarks, tests)

Per (kernel, route) it keeps launches, the memberships N and slots S
they carried, launches run in interpret mode, and new shapes: launches
of a static shape this process had not launched before, each of which
builds (or loads) one executable.

Beside them, ``pack_slot_index()`` counts the column packs
(``repro.alerts.batch.pack_columns``) by the path that built their
(window start, key) slot index: ``dense`` (a presence table over the
key's range), ``sort`` (a sort of the keys) or ``session`` (the session
layout: a sort by key and event time, cut at idle gaps), and
``pack_sessions()`` counts the sessions those session packs laid out by
what opened each: a new ``key`` or a ``gap`` longer than the spec's.
``columnar_blocks_decoded()`` counts the columnar store's blocks read
and checksummed (``repro.store.columnar.blocks.iter_blocks``) by where
each keeps its string dictionaries: in the checksummed ``payload``, or
in the json ``header`` as blocks written before that did.

The counters are process-wide, as the kernels' compiled executables
are, and never import JAX, so the metrics collector can read them from
anywhere.
"""
from __future__ import annotations

import threading
from typing import Dict, Hashable, Set

ROUTES = ("replay", "drain", "query", "direct")
FIELDS = ("launches", "memberships", "slots", "new_shapes", "interpreted")


class KernelLaunches:
    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[str, Dict[str, Dict[str, int]]] = {}
        self._shapes: Dict[str, Set[Hashable]] = {}

    def record(self, kernel: str, route: str, *, memberships: int,
               slots: int, shape: Hashable, interpreted: bool) -> None:
        """Count one launch of ``kernel`` for ``route``; ``shape`` is the
        launch's static signature (what the jit cache keys on)."""
        if route not in ROUTES:
            raise ValueError(f"route must be one of {ROUTES}, not {route!r}")
        with self._lock:
            c = self._counts.setdefault(kernel, {}).get(route)
            if c is None:
                c = self._counts[kernel][route] = dict.fromkeys(FIELDS, 0)
            seen = self._shapes.setdefault(kernel, set())
            c["launches"] += 1
            c["memberships"] += memberships
            c["slots"] += slots
            c["interpreted"] += int(interpreted)
            if shape not in seen:
                seen.add(shape)
                c["new_shapes"] += 1

    def snapshot(self) -> Dict[str, Dict[str, Dict[str, int]]]:
        """{kernel: {route: {launches, memberships, slots, new_shapes,
        interpreted}}}, a copy."""
        with self._lock:
            return {k: {r: dict(c) for r, c in routes.items()}
                    for k, routes in self._counts.items()}


#: the process's counters, fed by ``repro.kernels.ops``
KERNEL_LAUNCHES = KernelLaunches()


def kernel_launches() -> Dict[str, Dict[str, Dict[str, int]]]:
    """Snapshot of the process's kernel launch counters."""
    return KERNEL_LAUNCHES.snapshot()


PACK_PATHS = ("dense", "sort", "session")
_pack_lock = threading.Lock()
_pack_paths: Dict[str, int] = dict.fromkeys(PACK_PATHS, 0)


def record_pack_slot_index(path: str) -> None:
    """Count one column pack whose slot index took ``path``, one of
    ``PACK_PATHS``."""
    with _pack_lock:
        _pack_paths[path] += 1


def pack_slot_index() -> Dict[str, int]:
    """Snapshot of the process's column packs per slot-index path."""
    with _pack_lock:
        return dict(_pack_paths)


SESSION_CUTS = ("key", "gap")
_session_cuts: Dict[str, int] = dict.fromkeys(SESSION_CUTS, 0)


def record_pack_sessions(key: int, gap: int) -> None:
    """Count the sessions one session pack laid out: ``key`` opened by
    a key's first event, ``gap`` by an event more than the gap after
    the key's previous one."""
    with _pack_lock:
        _session_cuts["key"] += key
        _session_cuts["gap"] += gap


def pack_sessions() -> Dict[str, int]:
    """Snapshot of the process's packed sessions per cut."""
    with _pack_lock:
        return dict(_session_cuts)


BLOCK_LAYOUTS = ("payload", "header")
_block_layouts: Dict[str, int] = dict.fromkeys(BLOCK_LAYOUTS, 0)


def record_columnar_block(layout: str) -> None:
    """Count one columnar block decoded whose dictionaries lie in
    ``layout``, one of ``BLOCK_LAYOUTS``."""
    with _pack_lock:
        _block_layouts[layout] += 1


def columnar_blocks_decoded() -> Dict[str, int]:
    """Snapshot of the process's decoded columnar blocks per layout."""
    with _pack_lock:
        return dict(_block_layouts)
