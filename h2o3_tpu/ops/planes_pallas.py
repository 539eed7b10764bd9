"""Pallas TPU kernel of a K-class model's prediction frame
(models/model.py `_linked_planes`): from the walk's (rows, K) sums to the
frame's planes — the label and a (data, mask) pair a class — in ONE kernel,
the softmax link inside it.

Layout: ROWS RIDE THE LANES. On the TPU the walk's (rows, K) f32 output
lives column-major in HBM (`{0,1:T(8,128)}`), so its transpose is the same
bytes and a (K, rows) block is a plain tile of it: the classes on the
sublanes. A class's row of the tile is stored as its (rows,) plane. XLA
made each plane a fusion of its own, and every one read all the classes of
every tile (0.124 ms a plane at 2,449,215 rows, 23 classes; PERF.md §5).

The link is the f32 ops of `jax.nn.softmax(f0 + lr * sums, axis=1)`: the
margins, their maximum over the classes, exp of the difference, the sum,
the quotient. The label is NumPy's argmax — the first maximum, a NaN
counts as one — a class at a time; a NaN score is NA, and rows >= n are NA
in every plane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from h2o3_tpu.ops.hist_pallas import KERNEL_TRACES, use_pallas  # noqa: F401

# rows of a grid step: (K, 8192) f32 in, 2K + 2 planes of 8192 out — a few
# MB of VMEM with both buffers at 23 classes
TILE_ROWS = 8192


def _kernel(lim_ref, f0_ref, s_ref, lab_ref, lab_na_ref, *planes, lr,
            label_dtype):
    x = f0_ref[...] + lr * s_ref[...]                    # (K, rows)
    e = jnp.exp(x - jnp.max(x, axis=0, keepdims=True))
    p = e / jnp.sum(e, axis=0, keepdims=True)
    rows = x.shape[1]
    row = pl.program_id(0) * rows \
        + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    pad = row >= lim_ref[0]
    best, lab = p[0:1], jnp.zeros((1, rows), jnp.int32)
    for c in range(1, p.shape[0]):
        v = p[c:c + 1]
        take = (v > best) | (jnp.isnan(v) & ~jnp.isnan(best))
        best, lab = jnp.where(take, v, best), jnp.where(take, c, lab)
    lab_ref[...] = jnp.where(pad, 0, lab)[0].astype(label_dtype)
    # an i1 vector does not extend to 8 bits in a 1-D store: through int32
    lab_na_ref[...] = jnp.where(pad, 1, 0)[0].astype(jnp.uint8)
    for c in range(p.shape[0]):
        v = p[c:c + 1]
        na = pad | jnp.isnan(v)
        planes[2 * c][...] = jnp.where(na, 0.0, v)[0]
        planes[2 * c + 1][...] = jnp.where(na, 1, 0)[0].astype(jnp.uint8)


def class_planes(sums, f0, lim, *, lr: float, label_dtype):
    """[(label, mask), (p_0, mask_0), ...]: the planes of (n, K) sums
    whose rows >= lim[0] are padding; f0 (K,) the initial margins."""
    n, K = sums.shape
    KERNEL_TRACES.inc(kernel="class_planes", L=str(K))
    rows = min(TILE_ROWS, n)        # a frame shorter than a tile: one block
    plane = pl.BlockSpec((rows,), lambda i: (i,))
    out = pl.pallas_call(
        functools.partial(_kernel, lr=lr, label_dtype=label_dtype),
        name="class_planes",
        grid=(-(-n // rows),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((K, 1), lambda i: (0, 0)),
                  pl.BlockSpec((K, rows), lambda i: (0, i))],
        out_specs=[plane] * (2 * K + 2),
        out_shape=[jax.ShapeDtypeStruct((n,), label_dtype),
                   jax.ShapeDtypeStruct((n,), jnp.uint8)]
        + [jax.ShapeDtypeStruct((n,), jnp.float32),
           jax.ShapeDtypeStruct((n,), jnp.uint8)] * K,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(lim, f0.reshape(K, 1), sums.T)
    return list(zip(out[0::2], out[1::2]))
