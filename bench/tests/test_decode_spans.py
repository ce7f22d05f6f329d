"""The two sub-stages of the replay's ``decode`` stage on a replay window
of each generator, run on the CPU at a tiny size: ``decode.blocks`` and
``decode.keys`` together cover the stage to within a point of the
window, and each is one pass a replay."""
import importlib
import json
import tempfile
import time

import pytest

from bench.device.program_spans import replay_share
from bench.harness import BENCH, CompileMonitor, Ctx, load_json, load_module

SEED = 2 ** 32 + 4101
SUBS = ("decode.blocks", "decode.keys")


def _q5(config):
    return {"generator": dict(config["generator"], span_s=0.5)}


def _feeds(config):
    return {"pipeline": dict(config["pipeline"], num_sources=500),
            "generator": dict(config["generator"], base_rate_per_hour=20.0,
                              log_span_s=600.0)}


CELLS = {"nexmark_q5_replay": _q5, "feeds_replay": _feeds,
         "nexmark_q11_replay": _q5}


def _window(name):
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    config.update(CELLS[name](config))
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    with tempfile.TemporaryDirectory() as tmp:
        ctx = Ctx(cell=cell, config=config, traffic=traffic, seed=SEED,
                  seconds=0.5, trace=False, control=False, tmp=tmp,
                  monitor=CompileMonitor(), t_start=time.perf_counter())
        return driver.run(ctx)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_decode_sub_stages_cover_decode_once_a_replay(name):
    from repro.obs.profiler import recent_passes

    out = _window(name)
    read = {m: load_module(BENCH / "metrics" / f"replay.{m}_share.py"
                           ).read(out.record)
            for m in ("decode_blocks", "decode_keys")}
    decode = replay_share(out.record, "decode")
    assert decode is not None and all(v > 0.0 for v in read.values())
    assert sum(read.values()) == pytest.approx(decode, abs=1.0)
    assert sum(read.values()) <= decode + 1e-9

    mine = [p for p in recent_passes() if p[0] == "replay"]
    end = max(start + secs for _, stage, start, secs in mine
              if "." not in stage)
    begin = end - out.record.window_s
    for stage in ("decode",) + SUBS:
        passes = sum(1 for _, s, start, _ in mine
                     if s == stage and start >= begin)
        assert passes == out.info["replays"], stage
