"""Chaos-plane benchmark/soak driver (repro.chaos): how much failure
the five planes absorb per wall-second, and the proof artifact that
they absorbed ALL of it.

  smoke (default)   every catalog scenario x 2 seeds at catalog length
                    (~30 virtual min each) — the tier-1-sized matrix
  soak (--soak)     every scenario x 3 seeds at 8x virtual length
                    (hours of virtual time per scenario) — the
                    scheduled CI job

Each run writes ``BENCH_chaos.json``: scenarios run, faults injected
by kind, invariant checks passed, recovery latencies, and the
bitwise-reproducibility fingerprints.  On ANY invariant breach the
failing ``(scenario, seed)`` is written to ``CHAOS_FAILURE.json``
(plus the full report so far) and the process exits red — the seed
line alone reproduces the failure:

  PYTHONPATH=src python -m benchmarks.bench_chaos            # smoke
  PYTHONPATH=src python -m benchmarks.bench_chaos --soak     # CI soak
"""
from __future__ import annotations

import json
import sys
import time

from repro.chaos import SCENARIOS, ChaosInvariantError, run_scenario

SMOKE_SEEDS = (0, 1)
SOAK_SEEDS = (0, 1, 2)
SOAK_SCALE = 8.0


def run_matrix(*, soak: bool = False) -> dict:
    seeds = SOAK_SEEDS if soak else SMOKE_SEEDS
    scale = SOAK_SCALE if soak else 1.0
    out: dict = {"mode": "soak" if soak else "smoke",
                 "scenarios": {}, "failures": []}
    total_faults = 0
    t0 = time.perf_counter()
    for name in sorted(SCENARIOS):
        runs = []
        for seed in seeds:
            try:
                r = run_scenario(name, seed=seed, duration_scale=scale)
                faults = (sum(r["faults"]["connector"].values())
                          + sum(sum(v.values())
                                for v in r["faults"]["sinks"].values())
                          + sum(r["faults"]["object_store"].values()))
                total_faults += faults
                runs.append({
                    "seed": seed, "ok": True,
                    "virtual_s": r["virtual_s"],
                    "wall_s": r["wall_s"],
                    "accepted": r["ledger"]["accepted"],
                    "faults_injected": faults,
                    "crashes": r["crashes"],
                    "recovery_latency_s": r["recovery_latency_s"],
                    "checks_passed": r["checks_passed"],
                    "fingerprint": r["fingerprint"],
                })
            except ChaosInvariantError as exc:
                runs.append({"seed": seed, "ok": False,
                             "error": str(exc)})
                out["failures"].append(
                    {"scenario": name, "seed": seed,
                     "reproduce": f"run_scenario({name!r}, seed={seed}, "
                                  f"duration_scale={scale})",
                     "error": str(exc)})
        out["scenarios"][name] = runs
    out["total_wall_s"] = round(time.perf_counter() - t0, 3)
    out["total_faults_injected"] = total_faults
    out["virtual_hours"] = round(
        sum(r.get("virtual_s", 0.0) for rs in out["scenarios"].values()
            for r in rs) / 3600.0, 2)
    return out


def main(rows: list, *, soak: bool = False) -> list:
    res = run_matrix(soak=soak)
    with open("BENCH_chaos.json", "w", encoding="utf-8") as fh:
        json.dump(res, fh, indent=2)
    if res["failures"]:
        # the failing seed is the whole reproduction recipe — persist
        # it separately so CI can surface it as a red-run artifact
        with open("CHAOS_FAILURE.json", "w", encoding="utf-8") as fh:
            json.dump(res["failures"], fh, indent=2)
    ok_runs = [r for rs in res["scenarios"].values()
               for r in rs if r.get("ok")]
    wall = sum(r["wall_s"] for r in ok_runs) or 1e-9
    virtual = sum(r["virtual_s"] for r in ok_runs)
    rows.append((
        "chaos_matrix",
        1e6 * res["total_wall_s"] / max(len(ok_runs), 1),  # us per run
        f"scenarios={len(res['scenarios'])} runs={len(ok_runs)} "
        f"faults={res['total_faults_injected']} "
        f"speedup={virtual / wall:,.0f}x-realtime "
        f"failures={len(res['failures'])}",
    ))
    recs = [r["recovery_latency_s"] for r in ok_runs
            if r.get("recovery_latency_s") is not None]
    if recs:
        rows.append((
            "chaos_recovery_latency",
            1e6 * max(recs),                   # worst virtual recovery
            f"virtual_s_max={max(recs):.0f} n={len(recs)}",
        ))
    assert not res["failures"], (
        "chaos invariants violated — see CHAOS_FAILURE.json: "
        + "; ".join(f["reproduce"] for f in res["failures"]))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out: list = []
    main(out, soak="--soak" in sys.argv)
    for name, us, derived in out:
        print(f"{name},{us:.0f},{derived}")
