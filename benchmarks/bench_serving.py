"""Continuous batching vs static batching on the smoke model: tokens/s,
decode steps, TTFT — the FeedRouter admission policy is the variable."""
from __future__ import annotations

import time

import jax

from repro.config import ServeConfig
from repro.configs import get_arch
from repro.data.tokenizer import HashTokenizer
from repro.models.model import build_model
from repro.models.param import init_params
from repro.serve.engine import Request, ServeEngine


def _requests(tok, n):
    # varied generation lengths: continuous batching wins by refilling
    # slots that finish early
    return [Request(rid=i, prompt_tokens=tok.encode(f"news {i} " + "w " * (i % 5),
                                                    add_eos=False),
                    max_new_tokens=4 + 3 * (i % 4)) for i in range(n)]


def main(rows):
    cfg = get_arch("qwen2_5_3b").smoke
    model = build_model(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    tok = HashTokenizer(cfg.vocab)
    n = 16

    # continuous batching (replenish as slots free up)
    eng = ServeEngine(model, params, ServeConfig(
        max_batch=4, max_seq_len=128, replenish_after=1,
        replenish_timeout_s=0.0), eos_id=-1)
    for r in _requests(tok, n):
        eng.submit(r)
    t0 = time.time()
    eng.run_until_drained()
    cont_wall = time.time() - t0
    cont_steps = eng.steps

    # static batching: admit 4, run to completion, repeat
    eng2 = ServeEngine(model, params, ServeConfig(
        max_batch=4, max_seq_len=128, replenish_after=10**9,
        replenish_timeout_s=10**9), eos_id=-1)
    for r in _requests(tok, n):
        eng2.submit(r)
    t0 = time.time()
    total_steps = 0
    while len(eng2.main_q) or any(eng2.active):
        eng2.last_admit_at = -1e18      # force admission at batch boundary
        eng2.finished_since_admit = 10**9
        eng2.step()
        total_steps += 1
        while any(eng2.active):
            eng2.step()
            total_steps += 1
    static_wall = time.time() - t0

    rows.append((
        "serving_continuous_vs_static",
        1e6 * cont_wall,
        f"continuous_steps={cont_steps} static_steps={total_steps} "
        f"tokens={eng.tokens_generated} "
        f"speedup={static_wall / max(cont_wall, 1e-9):.2f}x",
    ))
    assert eng.tokens_generated == eng2.tokens_generated
    assert cont_steps <= total_steps
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = []
    main(out)
    for name, us, derived in out:
        print(f"{name},{us:.0f},{derived}")
