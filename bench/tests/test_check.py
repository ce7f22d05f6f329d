"""The comparison that decides ``correct``.

At a size a test run holds, on the CPU (the kernel in interpret mode):
the reference passes the program's own output, its bfloat16 control
fails, and each fault a cell can have, planted under the timed path,
turns ``correct`` false.  The harness itself refuses a host without a
TPU."""
import json
import subprocess
import sys

import numpy as np
import pytest

from bench.harness import BENCH, ROOT, run_cell
from bench.refs.reduce import (compare, control_reduce, reference_reduce,
                               window_rows)

SEED = 2 ** 32 + 12345


def _nexmark(span_s=0.5):
    g = json.loads((BENCH / "configs" / "nexmark_q5.json").read_text())
    return {"generator": dict(g["generator"], span_s=span_s)}


def _feeds(n=2000, rate=200.0, span_s=600.0):
    """At 200 items per feed-hour a channel's window holds more than 256
    events, which bfloat16 cannot count."""
    c = json.loads((BENCH / "configs" / "alertmix_feeds.json").read_text())
    return {"pipeline": dict(c["pipeline"], num_sources=n),
            "generator": dict(c["generator"], base_rate_per_hour=rate,
                              log_span_s=span_s)}


CELLS = {"nexmark_q5_replay": _nexmark, "feeds_replay": _feeds}


def _replay_cell(cell, **kw):
    return run_cell(cell, SEED, 0.3, False, require_chip=False,
                    overrides=CELLS[cell](), **kw)


def test_reference_passes_the_kernel_and_fails_bfloat16():
    """The pipeline's batch path (pack_columns -> window_reduce) against
    the reference, and the same replay with values cast to bfloat16."""
    import ml_dtypes
    from repro.alerts import WindowSpec
    from repro.alerts.batch import reduce_columns

    rng = np.random.default_rng(3)
    ts = np.sort(rng.random(4000) * 60.0)
    codes = rng.integers(0, 40, ts.size)
    vals = np.round(10.0 ** (rng.random(ts.size) * 6.0) * 100.0)
    spec = WindowSpec(kind="sliding", size_s=10.0, slide_s=5.0)
    vocab = [str(i) for i in range(40)]
    idx, starts = window_rows(ts, {"kind": "sliding", "size_s": 10.0,
                                   "slide_s": 5.0})
    ref = reference_reduce(codes[idx], starts, vals[idx])

    def lanes(values):
        aggs = reduce_columns(ts, codes, values, vocab, spec,
                              interpret=True, with_min=True)
        return {(a.key, a.window_start): (a.count, a.sum, a.sumsq, a.max,
                                          a.min) for a in aggs}

    good = compare(lanes(vals), ref)
    assert good["slots_differ"] == good["count_mismatch"] == 0
    assert good["extreme_mismatch"] == 0
    assert good["sum_err_over_bound"] <= 1.0
    assert good["sumsq_err_over_bound"] <= 1.0
    bf16 = vals.astype(ml_dtypes.bfloat16).astype(np.float64)
    bad = compare(lanes(bf16), ref)
    assert bad["extreme_mismatch"] > 0 and bad["sum_err_over_bound"] > 10
    ctl = compare(control_reduce(codes[idx], starts, vals[idx]), ref)
    assert ctl["extreme_mismatch"] > 0 and ctl["sum_err_over_bound"] > 10


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_replay_cell_is_correct_and_its_control_is_not(cell):
    r = _replay_cell(cell, control=True)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert r["info"]["control_correct"] is False
    assert list(r)[-1] == "checks"


def _replay_state_unchanged(monkeypatch):
    from repro.store.replay import ReplayEngine
    monkeypatch.setattr(ReplayEngine, "replay_columns",
                        lambda self, lanes, watermark=None: ([], []))


def _replay_half_batch(monkeypatch):
    import repro.alerts.batch as batch
    inner = batch.reduce_columns

    def half(ts, codes, values, vocab, spec, **kw):
        n = ts.size // 2
        return inner(ts[:n], codes[:n], values[:n], vocab, spec, **kw)
    monkeypatch.setattr(batch, "reduce_columns", half)


def _replay_altered(monkeypatch):
    import repro.alerts.batch as batch
    inner = batch.reduce_columns

    def altered(*a, **kw):
        out = inner(*a, **kw)
        out[len(out) // 2].count += 1
        return out
    monkeypatch.setattr(batch, "reduce_columns", altered)


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", [_replay_state_unchanged,
                                   _replay_half_batch, _replay_altered])
def test_replay_faults_are_not_correct(monkeypatch, fault, cell):
    fault(monkeypatch)
    assert not _replay_cell(cell)["correct"]


def test_without_a_tpu_the_run_prints_no_result(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "feeds_replay", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                          "HOME": str(tmp_path)})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
