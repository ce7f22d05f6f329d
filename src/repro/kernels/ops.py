"""Jitted public wrappers for the Pallas kernels.

The kernels target the TPU.  ``interpret=None`` resolves from the JAX
backend: native compilation on ``tpu``, the Pallas interpreter on
``cpu`` (where the test suite validates the kernel bodies), and an error
on any other backend, so a machine whose TPU failed to initialise never
falls back to the interpreter in silence.
"""
from __future__ import annotations

import jax

import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.kernels.token_hash import token_window_hash
from repro.kernels.window_reduce import window_reduce_fwd


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile natively only on tpu, and interpret mode "
        f"is chosen only on cpu; the JAX backend here is {backend!r}. "
        f"Pass interpret=True to run the interpreter anyway.")


def flash_attention(q, k, v, *, causal=True, window=0, block_q=256,
                    block_k=256, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=interpret)


def ssd_scan(x, dt, a, b_mat, c_mat, *, chunk=256, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return ssd_scan_fwd(x, dt, a, b_mat, c_mat, chunk=chunk,
                        interpret=interpret)


def grouped_matmul(x, w, counts, *, block_c=128, block_d=512, block_f=512,
                   interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return moe_gmm(x, w, counts, block_c=block_c, block_d=block_d,
                   block_f=block_f, interpret=interpret)


def window_hash(tokens, *, window=64, block_b=8, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return token_window_hash(tokens, window=window, block_b=block_b,
                             interpret=interpret)


def window_reduce(values, seg_ids, num_segments, *, block_s=128,
                  block_n=1024, interpret=None):
    """Per-segment count/sum/sumsq/max -> (num_segments, 4) f32 (the
    alerts-stage windowed reduction; segment = flat (key, window) slot)."""
    if interpret is None:
        interpret = _default_interpret()
    if values.shape[0] == 0:           # empty launch: nothing to reduce
        empty = jnp.zeros((num_segments, 4), jnp.float32)
        return empty.at[:, 3].set(-jnp.inf)
    return window_reduce_fwd(values, seg_ids, num_segments=num_segments,
                             block_s=block_s, block_n=block_n,
                             interpret=interpret)
