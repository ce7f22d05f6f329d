"""Batched per-(key, window) segment reductions for the alerts stage.

One grid launch computes count / sum / sum-of-squares / max for every
segment (a segment is one flattened (key, window) slot) over a flat event
tensor.  Layout:

  values  (1, N) f32   event values, 0-padded
  seg_ids (1, N) i32   segment id per event in [0, S); -1 marks padding
  out     (4, S) f32   rows: count, sum, sumsq, max (-inf when empty)

Grid is (segment blocks, event blocks) with the event dimension innermost:
each output block is revisited across consecutive steps, so the kernel
initialises it at event-block 0 and accumulates afterwards — the standard
TPU sequential-grid accumulation pattern.  Per step the VPU compares the
event block against the block's segment ids (a (block_s, block_n) one-hot)
and reduces along events; count/sum/sumsq could equally ride the MXU as a
one-hot matmul, but max needs the compare anyway so everything stays on
the VPU.

The kernel compiles natively for the TPU (``tests/test_tpu_compile.py``
compiles it for a v5e at the batch sizes the pipeline launches, and
``chip_smoke.py`` runs it on the chip).  On the CPU the tests run it in
interpret mode against ``ref.window_reduce_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(vals_ref, segs_ref, out_ref, *, block_s: int):
    i = pl.program_id(0)               # segment block (outer, output-fixed)
    j = pl.program_id(1)               # event block (inner, accumulated)

    @pl.when(j == 0)
    def _init():
        row = jax.lax.broadcasted_iota(jnp.int32, (4, block_s), 0)
        out_ref[...] = jnp.where(row == 3, -jnp.inf, 0.0).astype(jnp.float32)

    v = vals_ref[...].astype(jnp.float32)           # (1, block_n)
    s = segs_ref[...]                               # (1, block_n) i32
    seg_row = i * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (block_s, s.shape[1]), 0)
    onehot = s == seg_row                           # (block_s, block_n)

    cnt = jnp.sum(onehot.astype(jnp.float32), axis=1)
    sm = jnp.sum(jnp.where(onehot, v, 0.0), axis=1)
    sq = jnp.sum(jnp.where(onehot, v * v, 0.0), axis=1)
    mx = jnp.max(jnp.where(onehot, v, -jnp.inf), axis=1)

    prev = out_ref[...]                             # (4, block_s)
    out_ref[...] = jnp.stack([prev[0] + cnt, prev[1] + sm,
                              prev[2] + sq, jnp.maximum(prev[3], mx)])


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "block_s", "block_n", "interpret"),
)
def window_reduce_fwd(
    values: jax.Array,    # (N,) float
    seg_ids: jax.Array,   # (N,) int32, -1 = padding
    *,
    num_segments: int,
    block_s: int = 128,
    block_n: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Returns (num_segments, 4) f32: count, sum, sumsq, max per segment."""
    n = values.shape[0]
    block_n = min(block_n, max(8, n))
    block_s = min(block_s, max(8, num_segments))
    n_pad = -n % block_n
    s_pad = -num_segments % block_s
    vals = jnp.pad(values.astype(jnp.float32), (0, n_pad))[None, :]
    segs = jnp.pad(seg_ids.astype(jnp.int32), (0, n_pad),
                   constant_values=-1)[None, :]
    s_total = num_segments + s_pad

    out = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s),
        grid=(s_total // block_s, (n + n_pad) // block_n),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((4, block_s), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((4, s_total), jnp.float32),
        interpret=interpret,
        name="window_reduce",
    )(vals, segs)
    return out[:, :num_segments].T
