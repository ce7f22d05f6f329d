"""Roofline table generator — reads the dry-run records and emits the
§Roofline table (markdown to experiments/roofline.md + CSV rows)."""
from __future__ import annotations

import glob
import json
import os

ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load_records(path="experiments/dryrun"):
    recs = {}
    for f in glob.glob(os.path.join(path, "*.json")):
        r = json.load(open(f))
        recs[(r["arch"], r["shape"], r["mesh"])] = r
    return recs


def render_markdown(recs, mesh="single") -> str:
    lines = [
        "| arch | shape | t_comp (s) | t_mem (s) | t_coll (s) | dominant "
        "| useful/HLO | frac (XLA) | t_mem adj | t_coll adj | frac (TPU-adj) "
        "| peak GiB | fits |",
        "|---|---|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    archs = sorted({a for a, _, m in recs if m == mesh})
    for arch in archs:
        for shape in ORDER:
            r = recs.get((arch, shape, mesh))
            if r is None:
                continue
            if r["status"] == "skipped":
                lines.append(f"| {arch} | {shape} | skipped "
                             f"({r['reason'][:48]}…) |||||||||||")
                continue
            if r["status"] != "ok":
                lines.append(f"| {arch} | {shape} | ERROR |||||||||||")
                continue
            ro = r["roofline"]
            ka = ro.get("kernel_adjusted", {})
            bp = r["bytes_per_device"]
            lines.append(
                f"| {arch} | {shape} "
                f"| {ro['t_compute_s']:.3f} | {ro['t_memory_s']:.3f} "
                f"| {ro['t_collective_s']:.3f} | {ka.get('dominant', ro['dominant'])} "
                f"| {ro['useful_flops_ratio']:.2f} "
                f"| {ro['roofline_fraction']:.3f} "
                f"| {ka.get('t_memory_s', 0):.3f} "
                f"| {ka.get('t_collective_s', 0):.3f} "
                f"| **{ka.get('roofline_fraction', 0):.3f}** "
                f"| {bp['peak']/2**30:.1f} "
                f"| {'yes' if bp['fits_16GiB'] else 'NO'} |")
    return "\n".join(lines)


def main(rows):
    recs = load_records()
    ok = sum(1 for r in recs.values() if r["status"] == "ok")
    skipped = sum(1 for r in recs.values() if r["status"] == "skipped")
    err = sum(1 for r in recs.values() if r["status"] == "error")
    fits = sum(1 for r in recs.values()
               if r["status"] == "ok" and r["bytes_per_device"]["fits_16GiB"])
    md = ("# Roofline table (single-pod 16x16 mesh)\n\n"
          + render_markdown(recs, "single")
          + "\n\n# Multi-pod (2x16x16) — pass/fail + peaks\n\n"
          + render_markdown(recs, "multi"))
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/roofline.md", "w") as f:
        f.write(md)
    rows.append((
        "roofline_table",
        0.0,
        f"cells ok={ok} skipped={skipped} error={err} fits={fits}/{ok} "
        f"-> experiments/roofline.md",
    ))
    if ok:
        best = max((r for r in recs.values() if r["status"] == "ok"),
                   key=lambda r: r["roofline"]["roofline_fraction"])
        rows.append((
            "roofline_best_cell",
            0.0,
            f"{best['arch']}.{best['shape']}.{best['mesh']} "
            f"frac={best['roofline']['roofline_fraction']:.3f} "
            f"dom={best['roofline']['dominant']}",
        ))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = []
    main(out)
    for name, us, derived in out:
        print(f"{name},{us:.0f},{derived}")
