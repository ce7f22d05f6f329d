"""ColumnarEventLog — the columnar store plane's log.

Same contract as ``repro.store.EventLog`` (append / scan / truncate /
tick / crash-tolerant reopen), different physics:

* The ACTIVE tail stays JSON — one checksummed line **per append
  batch** (``B|first|count|crc32|<json array>``), so the torn-tail
  guarantee holds at batch granularity (an acked append survives a
  crash; a torn final batch is truncated away at reopen) while append
  cost amortizes across the batch.  Legacy per-record lines still
  decode, so an old JSONL tail adopts cleanly.
* On roll the tail is SEALED into a binary columnar segment
  (``seg-<first>.colb``, see ``blocks.py``): typed ts/key/channel/
  doc_id/value lanes, block checksums, min/max-ts + key-range stats.
  ``scan_columns()``/``scan_lanes()`` feed the batch kernel path with
  zero per-record Python for sealed data; ``scan()`` reconstructs the
  original payloads losslessly.
* Maintenance rides ``tick`` like segment roll: keyed compaction
  (keep-last-per-doc-id, Kafka-style), bytes/age retention, and
  tiered offload of sealed segments to an object store.  The manifest
  is the source of truth for what is local vs cold; a cold fetch
  failure dead-letters (``store_cold_unavailable``) and skips instead
  of wedging the reader, and a compaction that loses the commit race
  dead-letters ``compaction_conflict`` and retries on a later tick.
"""
from __future__ import annotations

import contextlib
import json
import os
import re
import zlib
from typing import Iterator, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..segment_log import (EventLog, Segment, CorruptSegmentError,
                           MANIFEST, _decode)
from ..lanes import Lanes, add_doc_rows, merge_lanes
from .blocks import (Block, CorruptBlockError, encode_file, file_stats,
                     iter_blocks)
from .tiering import ObjectStore, ObjectStoreError

_COLB_RE = re.compile(r"^seg-(\d{12})(?:\.g(\d+))?\.colb$")


def _colb_name(first: int, gen: int = 0) -> str:
    return (f"seg-{first:012d}.colb" if gen == 0
            else f"seg-{first:012d}.g{gen}.colb")


class ColumnarEventLog(EventLog):
    """Columnar-sealed EventLog with compaction, retention, offload."""

    def __init__(self, dir_path: str, *, segment_bytes: int = 1 << 20,
                 segment_age_s: Optional[float] = None, fsync: bool = False,
                 block_rows: int = 2048,
                 compact_interval_s: Optional[float] = None,
                 compact_head_segments: int = 2,
                 retention_max_bytes: Optional[int] = None,
                 retention_max_age_s: Optional[float] = None,
                 object_store: Optional[ObjectStore] = None,
                 offload_keep_local: int = 2):
        if block_rows < 1:
            raise ValueError("block_rows must be >= 1")
        self.block_rows = block_rows
        self.compact_interval_s = compact_interval_s
        self.compact_head_segments = max(1, compact_head_segments)
        self.retention_max_bytes = retention_max_bytes
        self.retention_max_age_s = retention_max_age_s
        self.object_store = object_store
        self.offload_keep_local = max(0, offload_keep_local)
        self.dead_letters = None          # wired by the pipeline
        self.tracer = None                # wired by the pipeline
        self._cold: Set[str] = set()      # segment names in the object store
        self._seg_ts: dict = {}           # name -> [min_ts, max_ts]
        self._last_compact: Optional[float] = None
        self._manifest_version = 0        # bumps on every manifest rewrite
        self.cstats = {
            "sealed_columnar_segments": 0,
            "blocks_written": 0,
            "blocks_pruned": 0,
            "compactions": 0,
            "compaction_conflicts": 0,
            "compacted_records_dropped": 0,
            "offloaded_segments": 0,
            "cold_fetches": 0,
            "cold_fetch_failures": 0,
            "retention_released_segments": 0,
            "torn_seals_recovered": 0,
        }
        super().__init__(dir_path, segment_bytes=segment_bytes,
                         segment_age_s=segment_age_s, fsync=fsync)

    # ---- tracing helper -----------------------------------------------------
    def _span(self, name: str, **attrs):
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, attrs=attrs)

    def _dead_letter(self, payload: dict, reason: str) -> None:
        if self.dead_letters is not None:
            self.dead_letters.publish(payload, reason=reason)

    # ---- recovery -----------------------------------------------------------
    def _recover(self) -> None:
        man = os.path.join(self.dir, MANIFEST)
        if os.path.exists(man):
            with open(man, encoding="utf-8") as fh:
                doc = json.load(fh)
            self._sealed = [Segment(**s) for s in doc["segments"]]
            self.truncated_through = doc.get("truncated_through", 0)
            self._cold = set(doc.get("cold", []))
            self._seg_ts = {n: tuple(v)
                            for n, v in doc.get("seg_ts", {}).items()}
            self.stats.sealed_segments = len(self._sealed)
        known = {s.name for s in self._sealed}
        dirty = False
        # conversion/compaction temp files never survive a restart
        for name in list(os.listdir(self.dir)):
            if name.endswith(".colb.tmp"):
                os.remove(os.path.join(self.dir, name))
        for s in self._sealed:
            path = os.path.join(self.dir, s.name)
            if s.name in self._cold:
                # offload committed (manifest says cold) but the crash
                # beat the local unlink: finish the job
                if os.path.exists(path):
                    os.remove(path)
                continue
            if not os.path.exists(path):
                raise CorruptSegmentError(f"sealed segment missing: {s.name}")
        self.next_offset = (self._sealed[-1].last + 1 if self._sealed
                            else self.truncated_through)
        strays = sorted(n for n in os.listdir(self.dir)
                        if n.startswith("seg-") and n not in known)
        for name in [n for n in strays
                     if int(n[4:16]) < self.truncated_through]:
            os.remove(os.path.join(self.dir, name))
            strays.remove(name)
        jsonls = [n for n in strays if n.endswith(".jsonl")]
        for name in [n for n in strays if n.endswith(".colb")]:
            first = int(name[4:16])
            path = os.path.join(self.dir, name)
            if _colb_name(first) != name or first < self.next_offset \
                    or f"seg-{first:012d}.jsonl" in jsonls:
                # superseded: a compaction/offload leftover, or a torn
                # seal whose JSON twin is still authoritative — the
                # tail will be re-sealed from the JSON on the next roll
                os.remove(path)
                if f"seg-{first:012d}.jsonl" in jsonls:
                    self.cstats["torn_seals_recovered"] += 1
                continue
            # conversion completed but the manifest write was lost:
            # adopt the columnar segment (its blocks are checksummed)
            try:
                with open(path, "rb") as fh:
                    data = fh.read()
                recs: List[Tuple[int, object]] = []
                for blk in iter_blocks(data):
                    recs.extend([(o, None) for o, _ in
                                 zip(blk.offsets().tolist(),
                                     range(blk.rows))])
                st = file_stats(data)
            except CorruptBlockError:
                os.remove(path)
                continue
            self._sealed.append(Segment(
                name=name, first=recs[0][0], last=recs[-1][0],
                records=len(recs), bytes=len(data)))
            if st["min_ts"] is not None:
                self._seg_ts[name] = (st["min_ts"], st["max_ts"])
            self.next_offset = recs[-1][0] + 1
            self.stats.sealed_segments = len(self._sealed)
            dirty = True
        self._sealed.sort(key=lambda s: s.first)
        if len(jsonls) > 1:
            for name in jsonls[:-1]:
                self._adopt_unsealed(name)
            jsonls = jsonls[-1:]
            dirty = False                 # _adopt_unsealed wrote it
        elif dirty:
            self._write_manifest()
        if jsonls:
            self._reopen_active(jsonls[0])
        self.cstats["sealed_columnar_segments"] = sum(
            1 for s in self._sealed if s.name.endswith(".colb"))

    # ---- manifest (atomic; adds cold + per-segment ts stats) ---------------
    def _write_manifest(self) -> None:
        self._manifest_version += 1
        live = {s.name for s in self._sealed}
        self._seg_ts = {n: v for n, v in self._seg_ts.items() if n in live}
        doc = {"segments": [s.as_dict() for s in self._sealed],
               "truncated_through": self.truncated_through,
               "cold": sorted(self._cold & live),
               "seg_ts": {n: list(v) for n, v in self._seg_ts.items()}}
        self._cold &= live
        tmp = os.path.join(self.dir, MANIFEST + ".tmp")
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, os.path.join(self.dir, MANIFEST))

    # ---- batch-framed JSON tail ---------------------------------------------
    def append(self, batch: Sequence) -> Tuple[int, int]:
        """Durably append ``batch`` as ONE checksummed frame — the
        per-batch framing amortizes serialization + checksum + flush
        across the batch (~4x over per-record canonical-JSON lines;
        the remainder is stdlib ``json.dumps``, kept deliberately —
        the tail stays plain JSON for the torn-tail guarantees)."""
        with self._lock:
            if self.closed:
                raise RuntimeError(
                    f"EventLog {self.dir!r} is closed; reopen it "
                    f"(ColumnarEventLog(dir)) to continue appending")
            if not batch:
                return self.next_offset, self.next_offset - 1
            if self._fh is None:
                self._open_segment()
            first = self.next_offset
            body = json.dumps(list(batch), separators=(",", ":"))
            data = body.encode("utf-8")
            head = f"B|{first}|{len(batch)}|{zlib.crc32(data):08x}|"
            self._fh.write(head + body + "\n")
            n = len(head) + len(data) + 1
            self._active_bytes += n
            self._active_records += len(batch)
            self.stats.appended_bytes += n
            self.stats.appended_records += len(batch)
            self.next_offset += len(batch)
            self._fh.flush()
            if self.fsync:
                os.fsync(self._fh.fileno())
            if self._active_bytes >= self.segment_bytes:
                self._seal_active()
            return first, self.next_offset - 1

    @staticmethod
    def _decode_frame(line: str) -> Optional[List[Tuple[int, object]]]:
        """One tail line -> its records, or None when torn/corrupt."""
        if not line.endswith("\n"):
            return None
        if line.startswith("B|"):
            try:
                _, first, count, crc, body = line[:-1].split("|", 4)
                first, count = int(first), int(count)
                if zlib.crc32(body.encode("utf-8")) != int(crc, 16):
                    return None
                payloads = json.loads(body)
            except (ValueError, KeyError):
                return None
            if not isinstance(payloads, list) or len(payloads) != count:
                return None
            return [(first + i, p) for i, p in enumerate(payloads)]
        rec = _decode(line)               # legacy per-record framing
        return None if rec is None else [rec]

    def _scan_file(self, name: str) -> Tuple[List[Tuple[int, object]], int]:
        path = os.path.join(self.dir, name)
        if name.endswith(".colb"):
            with open(path, "rb") as fh:
                data = fh.read()
            return self._decode_colb(name, data), len(data)
        out: List[Tuple[int, object]] = []
        good = 0
        with open(path, "r", encoding="utf-8", newline="") as fh:
            for line in fh:
                recs = self._decode_frame(line)
                if recs is None:
                    break
                out.extend(recs)
                good += len(line.encode("utf-8"))
        return out, good

    @staticmethod
    def _decode_colb(name: str, data: bytes) -> List[Tuple[int, object]]:
        try:
            out: List[Tuple[int, object]] = []
            for blk in iter_blocks(data):
                out.extend(blk.records())
            return out
        except CorruptBlockError as e:
            raise CorruptSegmentError(f"{name}: {e}") from e

    # ---- seal: JSON tail -> columnar segment --------------------------------
    def _convert(self, first: int, recs: List[Tuple[int, object]],
                 gen: int = 0) -> Segment:
        """Write records as a ``.colb`` file (atomic) -> its Segment."""
        name = _colb_name(first, gen)
        data = encode_file(recs, block_rows=self.block_rows)
        path = os.path.join(self.dir, name)
        tmp = path + ".tmp"               # cleared by _recover on crash
        with open(tmp, "wb") as fh:
            fh.write(data)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
        st = file_stats(data)
        if st["min_ts"] is not None:
            self._seg_ts[name] = (st["min_ts"], st["max_ts"])
        self.cstats["blocks_written"] += \
            -(-len(recs) // self.block_rows)
        return Segment(name=name, first=recs[0][0], last=recs[-1][0],
                       records=len(recs), bytes=len(data))

    def _seal_active(self) -> None:
        if self._fh is None or self._active_records == 0:
            return
        self._fh.flush()
        os.fsync(self._fh.fileno())
        self._fh.close()
        self._fh = None
        jname = self._active_name
        with self._span("store.seal", segment=jname,
                        records=self._active_records):
            recs, _ = self._scan_file(jname)
            seg = self._convert(self._active_first, recs)
            self._sealed.append(seg)
            self.stats.sealed_segments = len(self._sealed)
            self.cstats["sealed_columnar_segments"] += 1
            self._active_name = None
            self._active_bytes = 0
            self._active_records = 0
            self._active_opened_at = None
            self._write_manifest()        # commit point for the seal
            os.remove(os.path.join(self.dir, jname))

    def _adopt_unsealed(self, name: str) -> None:
        recs, _ = self._scan_file(name)
        if not recs:
            os.remove(os.path.join(self.dir, name))
            return
        seg = self._convert(int(name[4:16]), recs)
        self._sealed.append(seg)
        self._sealed.sort(key=lambda s: s.first)
        self.stats.sealed_segments = len(self._sealed)
        self.cstats["sealed_columnar_segments"] += 1
        self.next_offset = max(self.next_offset, recs[-1][0] + 1)
        self._write_manifest()
        os.remove(os.path.join(self.dir, name))

    def roll(self) -> None:
        """Seal the active JSON tail into a columnar segment NOW —
        size/age rolls take the same path on their own; this is for
        benchmarks/operators that want a deterministic seal point."""
        with self._lock:
            self._seal_active()

    # ---- read side ----------------------------------------------------------
    def _fetch_cold(self, seg: Segment) -> Optional[bytes]:
        """Fetch an offloaded segment; on failure dead-letter
        ``store_cold_unavailable`` and return None (the reader skips
        the segment instead of wedging)."""
        with self._span("store.cold_fetch", segment=seg.name):
            try:
                if self.object_store is None:
                    raise ObjectStoreError("no object store attached")
                data = self.object_store.get(seg.name)
            except Exception as e:
                self.cstats["cold_fetch_failures"] += 1
                self._dead_letter(
                    {"segment": seg.name, "first": seg.first,
                     "last": seg.last, "records": seg.records,
                     "error": str(e)},
                    reason="store_cold_unavailable")
                return None
            self.cstats["cold_fetches"] += 1
            return data

    def _segment_data(self, seg: Segment,
                      cold: Set[str]) -> Optional[bytes]:
        if seg.name in cold:
            return self._fetch_cold(seg)
        with open(os.path.join(self.dir, seg.name), "rb") as fh:
            return fh.read()

    def scan(self, from_offset: int = 0) -> Iterator[Tuple[int, object]]:
        with self._lock:
            sealed = list(self._sealed)
            active = self._active_name
            cold = set(self._cold)
            if self._fh is not None:
                self._fh.flush()
        for seg in sealed:
            if seg.last < from_offset:
                continue
            if seg.name.endswith(".colb"):
                data = self._segment_data(seg, cold)
                if data is None:
                    continue              # cold fetch failed: skip, logged
                recs = self._decode_colb(seg.name, data)
            else:
                recs, _ = self._scan_file(seg.name)
            if len(recs) != seg.records:
                raise CorruptSegmentError(
                    f"{seg.name}: {len(recs)} valid of {seg.records} records")
            for off, payload in recs:
                if off >= from_offset:
                    yield off, payload
        if active is not None:
            recs, _ = self._scan_file(active)
            for off, payload in recs:
                if off >= from_offset:
                    yield off, payload

    def scan_columns(self, from_offset: int = 0, *,
                     ts_min: Optional[float] = None,
                     ts_max: Optional[float] = None,
                     keys: Optional[Sequence[str]] = None
                     ) -> Iterator[Block]:
        """Yield decoded columnar Blocks from sealed segments, pruning
        whole blocks on their min/max-ts + key-range stats before the
        payload is even checksummed.  (The JSON tail has no blocks; use
        ``scan_lanes`` for a combined view.)"""
        keyset = None if keys is None else set(keys)
        kmin = min(keyset) if keyset else None
        kmax = max(keyset) if keyset else None

        def want(header: dict) -> bool:
            if header["last"] < from_offset:
                return False
            st = header["stats"]
            if ts_min is not None and st["max_ts"] is not None \
                    and st["max_ts"] < ts_min:
                self.cstats["blocks_pruned"] += 1
                return False
            if ts_max is not None and st["min_ts"] is not None \
                    and st["min_ts"] >= ts_max:
                self.cstats["blocks_pruned"] += 1
                return False
            if keyset and st["min_key"] is not None \
                    and (kmax < st["min_key"] or kmin > st["max_key"]):
                self.cstats["blocks_pruned"] += 1
                return False
            return True

        with self._lock:
            sealed = list(self._sealed)
            cold = set(self._cold)
        for seg in sealed:
            if seg.last < from_offset or not seg.name.endswith(".colb"):
                continue
            data = self._segment_data(seg, cold)
            if data is None:
                continue
            try:
                for blk in iter_blocks(data, want=want):
                    yield blk
            except CorruptBlockError as e:
                raise CorruptSegmentError(f"{seg.name}: {e}") from e

    def scan_lanes(self, from_offset: int = 0, *,
                   ts_min: Optional[float] = None,
                   ts_max: Optional[float] = None,
                   keys: Optional[Sequence[str]] = None,
                   profiler=None) -> Lanes:
        """Gather ts/key/value lanes across the whole log: sealed
        columnar segments decode as numpy arrays (zero per-record
        Python); the JSON tail (and any legacy sealed JSONL) is
        materialized row by row — bounded by one segment's size.

        Documents are read by ``repro.store.lanes``' rule, as
        ``EventLog.scan_lanes`` reads them: the blocks' lanes hold it
        column by column, and the tail is read by the same row reader;
        rows without a numeric event time (and non-document payloads)
        are dropped.

        The blocks' key dictionaries merge on their utf-8 bytes: codes
        follow each key's first sighting (blocks in log order, each
        block's dictionary in its order, then the tail's rows), and only
        the distinct keys are decoded to ``str``.

        ``profiler`` (a ``StageProfiler``, the replay's) times the scan
        in two passes: ``decode.blocks`` (files, headers, checksums, the
        ts, value and key-code lanes) then ``decode.keys`` (the merge of
        the key dictionaries, the remap and the final concatenation)."""
        def stage(name: str):
            return (contextlib.nullcontext() if profiler is None
                    else profiler.stage(name))

        keyset = None if keys is None else set(keys)
        entries: List[bytes] = []       # kept blocks' key dictionaries
        ts_parts: List[np.ndarray] = []
        entry_parts: List[np.ndarray] = []   # each row's index in entries
        val_parts: List[np.ndarray] = []
        with stage("decode.blocks"):
            keybytes = None if keyset is None else {
                k.encode("utf-8", "surrogatepass") for k in keyset}
            for blk in self.scan_columns(from_offset, ts_min=ts_min,
                                         ts_max=ts_max, keys=keys):
                bts = blk.lane_ts()
                mask = ~np.isnan(bts)
                if from_offset > blk.first:
                    mask &= blk.offsets() >= from_offset
                if ts_min is not None:
                    mask &= bts >= ts_min
                if ts_max is not None:
                    mask &= bts < ts_max
                codes, bentries = blk.lane_key_bytes()
                if keybytes is not None:
                    allowed = np.fromiter(map(keybytes.__contains__,
                                              bentries),
                                          dtype=bool, count=len(bentries))
                    mask &= allowed[codes]
                bvals = blk.lane_value()
                if not mask.all():
                    if not mask.any():
                        continue
                    bts, codes, bvals = bts[mask], codes[mask], bvals[mask]
                ts_parts.append(bts)
                entry_parts.append(np.add(codes, len(entries),
                                          dtype=np.int64))
                val_parts.append(bvals)
                entries.extend(bentries)
            add_doc_rows(self._tail_records(from_offset), entries,
                         ts_parts, entry_parts, val_parts, ts_min=ts_min,
                         ts_max=ts_max, keyset=keyset)
        with stage("decode.keys"):
            lanes = merge_lanes(entries, ts_parts, entry_parts, val_parts)
            # the parts hold views of the segments' bytes and one object
            # per entry: free them inside the stage that used them last
            del entries, ts_parts, entry_parts, val_parts
        return lanes

    def _tail_records(self, from_offset: int
                      ) -> Iterator[Tuple[int, object]]:
        """The records at or past ``from_offset`` of the JSON tail and
        of any legacy sealed JSONL: what ``scan_lanes`` reads row by
        row."""
        with self._lock:
            sealed = list(self._sealed)
            active = self._active_name
            if self._fh is not None:
                self._fh.flush()
        names = [s.name for s in sealed
                 if s.last >= from_offset and not s.name.endswith(".colb")]
        if active is not None:
            names.append(active)
        for name in names:
            recs, _ = self._scan_file(name)
            yield from (r for r in recs if r[0] >= from_offset)

    # ---- keyed compaction (keep-last-per-doc-id) ----------------------------
    def _compact_plan(self) -> Optional[dict]:
        """Snapshot the compaction inputs under the lock.  Candidates
        are LOCAL sealed columnar segments behind the head window
        (the newest ``compact_head_segments`` stay untouched, like
        Kafka's dirty head)."""
        with self._lock:
            colb = [s for s in self._sealed
                    if s.name.endswith(".colb") and s.name not in self._cold]
            if len(colb) <= self.compact_head_segments:
                return None
            candidates = colb[:-self.compact_head_segments]
            return {"candidates": candidates,
                    "version": self._manifest_version}

    def _compact_build(self, plan: dict) -> Optional[dict]:
        """Heavy phase, outside the lock: find the last offset of every
        doc_id across the WHOLE log, then rewrite each candidate
        keeping only rows that still are the last write of their key."""
        last_of: dict = {}
        for off, payload in self.scan():   # includes head + tail
            if isinstance(payload, dict) and isinstance(
                    payload.get("id"), str):
                last_of[payload["id"]] = off
        rewritten = []                     # (old Segment, new Segment|None)
        for seg in plan["candidates"]:
            recs, _ = self._scan_file(seg.name)
            kept = [(off, p) for off, p in recs
                    if not (isinstance(p, dict)
                            and isinstance(p.get("id"), str))
                    or last_of.get(p["id"]) == off]
            dropped = len(recs) - len(kept)
            if dropped == 0:
                rewritten.append((seg, seg))
                continue
            if not kept:
                rewritten.append((seg, None))
                continue
            m = _COLB_RE.match(seg.name)
            gen = (int(m.group(2)) if m.group(2) else 0) + 1
            new = self._convert(int(m.group(1)), kept, gen=gen)
            rewritten.append((seg, new))
        return {"rewritten": rewritten}

    def _compact_commit(self, plan: dict, built: dict) -> bool:
        """Swap the rewritten segments in, atomically via the manifest.
        If the log changed shape underneath (truncate/retention ran,
        another compactor won), abandon: remove the new files and
        dead-letter ``compaction_conflict`` — a later tick retries."""
        with self._lock:
            names = {s.name for s in self._sealed}
            conflict = (self._manifest_version != plan["version"]
                        or any(old.name not in names
                               for old, _ in built["rewritten"]))
            if conflict:
                self.cstats["compaction_conflicts"] += 1
            else:
                dropped = 0
                by_name = {old.name: new
                           for old, new in built["rewritten"]}
                out: List[Segment] = []
                for s in self._sealed:
                    if s.name not in by_name:
                        out.append(s)
                        continue
                    new = by_name[s.name]
                    dropped += s.records - (new.records if new else 0)
                    if new is not None:
                        out.append(new)
                self._sealed = out
                self.stats.sealed_segments = len(self._sealed)
                self.cstats["compactions"] += 1
                self.cstats["compacted_records_dropped"] += dropped
                self.cstats["sealed_columnar_segments"] = sum(
                    1 for s in self._sealed if s.name.endswith(".colb"))
                self._write_manifest()    # commit point
                for old, new in built["rewritten"]:
                    if new is None or new.name != old.name:
                        os.remove(os.path.join(self.dir, old.name))
        if conflict:
            for old, new in built["rewritten"]:
                if new is not None and new.name != old.name:
                    try:
                        os.remove(os.path.join(self.dir, new.name))
                    except OSError:
                        pass
                    self._seg_ts.pop(new.name, None)
            self._dead_letter(
                {"candidates": [old.name
                                for old, _ in built["rewritten"]]},
                reason="compaction_conflict")
            return False
        return True

    def compact(self) -> dict:
        """One keyed-compaction pass; -> summary dict."""
        plan = self._compact_plan()
        if plan is None:
            return {"compacted": 0, "dropped": 0, "conflict": False}
        with self._span("store.compact",
                        candidates=len(plan["candidates"])):
            built = self._compact_build(plan)
            before = self.cstats["compacted_records_dropped"]
            ok = self._compact_commit(plan, built)
            return {"compacted": len(plan["candidates"]) if ok else 0,
                    "dropped": self.cstats["compacted_records_dropped"]
                    - before,
                    "conflict": not ok}

    # ---- retention (bytes/age) ----------------------------------------------
    def enforce_retention(self, now: float) -> int:
        """Release the oldest sealed segments until the log fits the
        bytes budget, plus any prefix entirely older (by max event
        time) than the age budget.  Whole-prefix granularity — the
        same unit as ``truncate``."""
        with self._lock:
            sealed = list(self._sealed)
        if not sealed:
            return 0
        upto = None
        if self.retention_max_age_s is not None:
            cutoff = now - self.retention_max_age_s
            for s in sealed:
                ts = self._seg_ts.get(s.name)
                if ts is None or ts[1] >= cutoff:
                    break
                upto = s.last + 1
        if self.retention_max_bytes is not None:
            total = sum(s.bytes for s in sealed)
            for s in sealed:
                if total <= self.retention_max_bytes:
                    break
                total -= s.bytes
                upto = max(upto or 0, s.last + 1)
        if upto is None:
            return 0
        before = self.stats.truncated_segments
        freed = self.truncate(upto)
        self.cstats["retention_released_segments"] += \
            self.stats.truncated_segments - before
        return freed

    def truncate(self, upto: int) -> int:
        """Cold-aware truncate: offloaded segments are deleted from the
        object store instead of the local directory."""
        freed = 0
        with self._lock:
            doomed = [s for s in self._sealed if s.last < upto]
            if not doomed:
                return 0
            self._sealed = [s for s in self._sealed if s.last >= upto]
            self.stats.sealed_segments = len(self._sealed)
            self.truncated_through = max(self.truncated_through,
                                         max(s.last for s in doomed) + 1)
            cold = set(self._cold)
            self._write_manifest()
            for seg in doomed:
                if seg.name in cold:
                    try:
                        self.object_store.delete(seg.name)
                    except Exception:
                        pass              # orphan object, never re-read
                else:
                    os.remove(os.path.join(self.dir, seg.name))
                freed += seg.records
                self.stats.truncated_segments += 1
                self.stats.truncated_records += seg.records
            self.cstats["sealed_columnar_segments"] = sum(
                1 for s in self._sealed if s.name.endswith(".colb"))
        return freed

    # ---- tiered offload -----------------------------------------------------
    def offload(self) -> int:
        """Move sealed columnar segments beyond the newest
        ``offload_keep_local`` to the object store.  Ordering: put the
        object FIRST, then commit via the manifest, then unlink the
        local copy — a crash at any point leaves either a harmless
        orphan object or a local copy ``_recover`` finishes deleting."""
        if self.object_store is None:
            return 0
        moved = 0
        with self._lock:
            local = [s for s in self._sealed
                     if s.name.endswith(".colb") and s.name not in self._cold]
            todo = local[:max(0, len(local) - self.offload_keep_local)]
            for seg in todo:
                path = os.path.join(self.dir, seg.name)
                with self._span("store.offload", segment=seg.name,
                                bytes=seg.bytes):
                    with open(path, "rb") as fh:
                        data = fh.read()
                    try:
                        self.object_store.put(seg.name, data)
                    except Exception as e:
                        self._dead_letter(
                            {"segment": seg.name, "error": str(e)},
                            reason="store_cold_unavailable")
                        continue
                    self._cold.add(seg.name)
                    self._write_manifest()   # commit point
                    os.remove(path)
                    self.cstats["offloaded_segments"] += 1
                    moved += 1
        return moved

    # ---- tick: roll + maintenance -------------------------------------------
    def tick(self, now: float) -> None:
        super().tick(now)
        if self.compact_interval_s is not None and (
                self._last_compact is None
                or now - self._last_compact >= self.compact_interval_s):
            self._last_compact = now
            self.compact()
        if self.object_store is not None:
            self.offload()
        if (self.retention_max_bytes is not None
                or self.retention_max_age_s is not None):
            self.enforce_retention(now)

    # ---- observability ------------------------------------------------------
    def status(self) -> dict:
        out = super().status()
        out["columnar"] = {**self.cstats,
                           "cold_segments": len(self._cold),
                           "block_rows": self.block_rows}
        return out
