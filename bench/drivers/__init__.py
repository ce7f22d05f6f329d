"""Drivers, chosen by a traffic mix's ``driver``, and what they share:
the deployment's pipeline, built from its configuration file."""
from __future__ import annotations

from typing import Optional


def build_pipeline(config: dict, *, store_dir: str,
                   sinks: Optional[list] = None):
    """``AlertMixPipeline`` with the configuration's settings, rules and
    own seed (the deployment, the same in every run), its store plane
    under ``store_dir``."""
    import repro.alerts as alerts
    from repro.core import AlertMixPipeline, PipelineConfig

    rules = [getattr(alerts, r["type"])(
        **{k: v for k, v in r.items() if k != "type"})
        for r in config["rules"]]
    cfg = PipelineConfig(**config["pipeline"], store_dir=store_dir)
    return AlertMixPipeline(cfg, seed=config["pipeline_seed"], sinks=sinks,
                            analytics_rules=rules)


def window_spec(config: dict) -> dict:
    """The window spec as the reference reads it."""
    p = config["pipeline"]
    spec = {"kind": p["window_kind"], "size_s": p["window_size_s"]}
    if p["window_kind"] == "sliding":
        spec["slide_s"] = p.get("window_slide_s", p["window_size_s"] / 2.0)
    return spec
