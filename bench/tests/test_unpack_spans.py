"""The two sub-stages of the replay's ``unpack`` stage on a replay window
of each generator, run on the CPU at a tiny size: ``unpack.order`` and
``unpack.records`` together cover the stage to within a point of the
window, and each is one pass a replay."""
import pytest
from test_decode_spans import CELLS, _window

from bench.device.program_spans import replay_share
from bench.harness import BENCH, load_module

SUBS = ("unpack.order", "unpack.records")


@pytest.mark.parametrize("name", sorted(CELLS))
def test_unpack_sub_stages_cover_unpack_once_a_replay(name):
    from repro.obs.profiler import recent_passes

    out = _window(name)
    read = {m: load_module(BENCH / "metrics" / f"replay.{m}_share.py"
                           ).read(out.record)
            for m in ("unpack_order", "unpack_records")}
    unpack = replay_share(out.record, "unpack")
    assert unpack is not None and all(v > 0.0 for v in read.values())
    assert sum(read.values()) == pytest.approx(unpack, abs=1.0)
    assert sum(read.values()) <= unpack + 1e-9

    mine = [p for p in recent_passes() if p[0] == "replay"]
    end = max(start + secs for _, stage, start, secs in mine
              if "." not in stage)
    begin = end - out.record.window_s
    for stage in ("unpack",) + SUBS:
        passes = sum(1 for _, s, start, _ in mine
                     if s == stage and start >= begin)
        assert passes == out.info["replays"], stage
