"""``chip_smoke.py``'s logic on the CPU, and the bring-up guards it leans
on: no silent interpret mode off the CPU, and a compile cache that can be
placed from outside.

The smoke's phases run here at a tiny size with the kernel in interpret
mode (the CPU's explicit choice).  Its worker pool is shrunk to one so
that fetches fall behind and events reach the late-event journal, as the
full-size pool does at 20,000 feeds."""
import importlib.util
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]

_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


def test_pipeline_routes_match_their_references(tmp_path):
    before = cs.launch_counts()
    p = cs.build_pipeline(str(tmp_path), num_sources=300, workers=1,
                          resizer=False, segment_bytes=4096,
                          query_max_windows_per_key=4)
    try:
        late = cs.run_with_late_drains(p, virtual_s=1800.0, chunk_s=600.0,
                                       per_worker=1)
        cold = cs.check_cold_query(p)
        replay = cs.check_log_replay(p)
    finally:
        p.close()
    assert late["late_events"] > 0 and late["drains"] > 0
    assert late["slots"] > 0
    assert cold["windows"] > 0 and cold["floor"] > 0
    assert replay["events"] > 0 and replay["slots"] > 0
    counts = cs.launches_since(before)
    for route in ("drain", "query", "replay"):
        assert counts[route]["launches"] > 0, route
        # on the CPU the kernel runs in interpret mode, chosen explicitly
        assert counts[route]["interpreted"] == counts[route]["launches"]
    assert "direct" not in counts
    # with min: two launches per replay, of one shape
    assert counts["replay"]["launches"] == 2
    assert counts["replay"]["new_shapes"] <= 1


def test_direct_and_sliding_batches_match_the_reference():
    from repro.store.columnar import Lanes

    rng = np.random.default_rng(1)
    n = 400
    lanes = Lanes(ts=rng.uniform(0.0, 1800.0, n),
                  key_codes=rng.integers(0, 4, n).astype(np.int64),
                  key_vocab=["news", "custom_rss", "facebook", "twitter"],
                  values=np.ones(n))
    before = cs.launch_counts()
    res = cs.check_direct(lanes, n_events=3000, n_segments=100)
    counts = cs.launches_since(before)
    assert list(counts) == ["direct"]
    assert counts["direct"]["launches"] == 2
    assert counts["direct"]["memberships"] == 3000 + res["sliding_events"]
    assert counts["direct"]["slots"] == 100 + res["sliding_slots"]
    assert res["slots"] > 100
    assert res["sliding_events"] > n       # each event in several windows
    assert res["sum_err_over_bound"] <= 1.0


def _ref():
    keys = ["a", "a", "b"]
    return cs.reference_reduce(keys, np.array([0.0, 0.0, 60.0]),
                               np.array([1.5, -2.0, 3.0]))


def test_reference_reduce_groups_in_float64():
    assert _ref() == {("a", 0.0): (2, -0.5, 6.25, 1.5, 3.5),
                      ("b", 60.0): (1, 3.0, 9.0, 3.0, 3.0)}


@pytest.mark.parametrize("mutate,match", [
    (lambda g: g.update({("a", 0.0): (3, -0.5, 6.25, 1.5)}), "count"),
    (lambda g: g.update({("a", 0.0): (2, -0.5, 6.25, 1.0)}), "max"),
    (lambda g: g.update({("a", 0.0): (2, -0.5 + 1e-5, 6.25, 1.5)}), "sum"),
    (lambda g: g.update({("b", 60.0): (1, 3.0, 9.0 + 1e-5, 3.0)}), "sumsq"),
    (lambda g: g.pop(("b", 60.0)), "slots differ"),
])
def test_compare_rejects_each_kind_of_mismatch(mutate, match):
    ref = _ref()
    got = {k: v[:4] for k, v in ref.items()}
    assert cs.compare("t", dict(got), ref)["slots"] == 2
    mutate(got)
    with pytest.raises(cs.SmokeFailure, match=match):
        cs.compare("t", got, ref)


def test_sum_bound_admits_one_float32_rounding():
    a, b = np.float32(1.0), np.float32(2.0 ** -24 * 1.5)
    got_sum = float(np.float32(a + b))       # one rounding
    ref = cs.reference_reduce(["k", "k"], np.zeros(2), np.array([a, b]))
    cs.compare("t", {("k", 0.0): (2, got_sum, float(a * a + b * b),
                                  1.0)}, ref)
    assert not math.isclose(got_sum, float(a) + float(b), rel_tol=0.0)


def test_main_refuses_a_host_without_tpu(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(cs.SmokeFailure, match="no TPU"):
        cs.main()
    assert '"ok": true' not in capsys.readouterr().out


@pytest.mark.parametrize("alone", [False, True])
def test_script_exits_nonzero_without_tpu(tmp_path, alone):
    """Run as the script: in the checkout, and alone in a directory
    that holds nothing else of the repo."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout


def test_kernels_refuse_silent_interpret_off_cpu(monkeypatch):
    from repro.kernels import ops

    vals, segs = np.ones(8, np.float32), np.zeros(8, np.int32)
    for backend, interpret in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(ops.jax, "default_backend", lambda b=backend: b)
        assert ops._default_interpret() is interpret
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="interpret mode is chosen only"):
        ops.window_reduce(vals, segs, 2)
    monkeypatch.undo()
    out = np.asarray(ops.window_reduce(vals, segs, 2, interpret=True))
    assert out[0].tolist() == [8.0, 8.0, 8.0, 1.0]


@pytest.fixture
def config_updates(monkeypatch):
    from repro import compile_cache

    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return compile_cache, calls


def test_compile_cache_honours_the_environment(config_updates, monkeypatch,
                                               tmp_path):
    compile_cache, calls = config_updates
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() is None
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert [name for name, _ in calls] == [
        "jax_persistent_cache_min_compile_time_secs"]


def test_compile_cache_defaults_to_one_ignored_checkout_path(config_updates,
                                                             monkeypatch):
    compile_cache, calls = config_updates
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir() == str(
        ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == first
    assert ("jax_compilation_cache_dir", first) in calls
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in calls
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
