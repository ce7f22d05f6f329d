"""CPU train-step throughput on reduced configs (one per family) and the
data-plane ingestion rate feeding it."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import OptimizerConfig, ParallelConfig
from repro.configs import get_arch
from repro.models.model import build_model
from repro.models.param import init_params
from repro.train.step import init_opt_state, make_train_step

FAMILIES = ["qwen2_5_3b", "dbrx_132b", "mamba2_1_3b", "zamba2_2_7b"]


def main(rows):
    for arch in FAMILIES:
        cfg = get_arch(arch).smoke
        model = build_model(cfg)
        params = init_params(model.param_defs(), jax.random.PRNGKey(0))
        ocfg = OptimizerConfig(total_steps=100)
        par = ParallelConfig()
        opt = init_opt_state(params, ocfg, par)
        step = jax.jit(make_train_step(model, ocfg, par))
        b, s = 4, 128
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (b, s)).astype(np.int32))}
        params, opt, _ = step(params, opt, batch)      # compile
        t0 = time.time()
        iters = 5
        for _ in range(iters):
            params, opt, metrics = step(params, opt, batch)
        jax.block_until_ready(metrics["loss"])
        dt = (time.time() - t0) / iters
        rows.append((
            f"train_step_{arch}",
            1e6 * dt,
            f"{b*s/dt:,.0f}tok/s loss={float(metrics['loss']):.3f}",
        ))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = []
    main(out)
    for name, us, derived in out:
        print(f"{name},{us:.0f},{derived}")
