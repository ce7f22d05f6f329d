"""The readers of the program's own stage spans: the window rule on a
hand-built ring, and the six ``replay.pack_*`` / ``replay.kernel_*``
metrics on a replay window run on the CPU."""
import json
from types import SimpleNamespace

import pytest

from bench.device.program_spans import window_share
from bench.harness import BENCH, CompileMonitor, Ctx, load_json, load_module

SUBS = {"replay.pack_share": ("replay.pack_assign_share",
                              "replay.pack_unique_share",
                              "replay.pack_slots_share"),
        "replay.kernel_stage_share": ("replay.kernel_dispatch_share",
                                      "replay.kernel_wait_share",
                                      "replay.kernel_fetch_share")}

# a warm-up replay at [0, 2), then a window of 10 s ending at 20 s, the
# end of the newest top-level pass
RING = [
    ("replay", "pack_events", 0.0, 1.0),          # warm-up
    ("replay", "pack_events.unique", 0.2, 0.5),
    ("replay", "kernel", 1.0, 1.0),
    ("replay", "pack_events", 9.0, 4.0),          # straddles the start
    ("replay", "pack_events.unique", 9.5, 3.0),
    ("other", "pack_events", 12.0, 30.0),         # another profiler
    ("replay", "kernel", 13.0, 5.0),
    ("replay", "kernel.wait", 13.5, 4.0),
    ("replay", "state_merge", 18.0, 2.0),         # the newest: ends at 20
]


@pytest.mark.parametrize("stage,share", [
    ("pack_events", 30.0),              # 3 of its 4 s lie inside
    ("pack_events.unique", 25.0),       # 2.5 of 3 s inside
    ("kernel", 50.0),
    ("kernel.wait", 40.0),
    ("state_merge", 20.0),
])
def test_window_rule_clips_passes_to_the_window(stage, share):
    assert window_share(RING, 10.0, stage) == pytest.approx(share)


def test_window_rule_reads_none_when_the_ring_wrapped():
    wrapped = RING[3:]                   # oldest pass starts at 9 s
    assert window_share(wrapped, 10.0, "kernel") == pytest.approx(50.0)
    assert window_share(wrapped, 11.5, "kernel") is None
    assert window_share(RING[6:], 10.0, "kernel") is None


def test_window_rule_reads_none_without_the_stage():
    assert window_share(RING, 10.0, "unpack") is None
    assert window_share(RING, 10.0, "kernel", profiler="query") is None
    assert window_share([], 10.0, "kernel") is None


def test_readers_of_a_cpu_replay_window():
    """The replay driver's window at a tiny size: every new metric reads
    a number, the kernel sub-stages add up to the kernel stage, and the
    pack sub-stages to no more than the pack stage."""
    import tempfile
    import time

    from bench.drivers import replay

    config = load_json(BENCH / "configs" / "nexmark_q5.json")
    config["generator"] = dict(config["generator"], span_s=0.5)
    traffic = load_json(BENCH / "traffic" / "replay_loop.json")
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "nexmark_q5_replay")
    with tempfile.TemporaryDirectory() as tmp:
        ctx = Ctx(cell=cell, config=config, traffic=traffic,
                  seed=2 ** 32 + 7, seconds=0.5, trace=False,
                  control=False, tmp=tmp, monitor=CompileMonitor(),
                  t_start=time.perf_counter())
        record = replay.run(ctx).record
    read = {m["name"]: load_module(BENCH / "metrics" / f"{m['name']}.py"
                                   ).read(record)
            for m in bench["per_layer"] if m["source"] == "program_span"}
    assert all(v is not None and 0.0 <= v <= 100.0 for v in read.values())
    kernel = sum(read[m] for m in SUBS["replay.kernel_stage_share"])
    assert kernel == pytest.approx(read["replay.kernel_stage_share"],
                                   abs=0.5)
    pack = sum(read[m] for m in SUBS["replay.pack_share"])
    assert read["replay.pack_share"] - 5.0 <= pack
    assert pack <= read["replay.pack_share"] + 1e-9


def test_reader_reads_none_before_any_replay():
    reader = load_module(BENCH / "metrics" / "replay.kernel_wait_share.py")
    assert reader.read(SimpleNamespace(window_s=0.0)) is None
