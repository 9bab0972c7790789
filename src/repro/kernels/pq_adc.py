"""pq_adc — MXU-native ADC (asymmetric distance computation) LUT scan.

The paper's PQ filter (§4.1.1) scans memory-resident codes against a per-query
lookup table. A CPU implementation gathers lut[m, code]; gathers are the weak
operation on TPU's vector unit, so the TPU-native form turns each subspace
scan into a (1, 256) x (256, bn) matmul against a one-hot on the MXU —
gather-free. The LUT (M, 256) f32 = 16 KiB lives wholly in VMEM; codes
stream from HBM block-by-block through the grid pipeline (double-buffered).

Tiling contract: the scores leave as one lane-dense (1, block_n) row of a
(1, N) output (a 1-D f32 output block does not match XLA's T(1024) tiling of
a 1-D array on the TPU), so a compiled block_n is a multiple of 128; 256 = 2
lanes of 128. `interpret` is required (True: jnp on the CPU; False: Mosaic).
The matmuls run at HIGHEST precision: at the default one the MXU rounds the
f32 LUT to bf16, which moved v5e scores by up to 0.05 against a float64
reference; the one-hot operand is exact either way.

Pad guard: N is padded up to a block_n multiple, and the padded tail used to
score the zero pad's codes as if they were real records — garbage distances
that any caller consuming the padded buffer (the shape-bucketed wrappers in
kernels/ops.py keep it) could mistake for candidates. The kernel now masks
every row at or past the true length to +inf; `nvalid` lets a bucketing
caller that pre-padded name the true length as a TRACED scalar, so one
compiled kernel serves every length inside a bucket.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(nvalid_ref, codes_ref, lut_ref, out_ref):
    codes = codes_ref[...].astype(jnp.int32)              # (bn, M)
    lut = lut_ref[...]                                    # (M, 256) f32
    bn, m = codes.shape
    acc = jnp.zeros((1, bn), jnp.float32)
    for j in range(m):  # M is small and static: unrolled, each an MXU matmul
        onehot = (codes[:, j][:, None]
                  == jax.lax.broadcasted_iota(jnp.int32, (bn, 256), 1))
        # (1, 256) x (bn, 256)^T -> (1, bn): the result is one lane-dense
        # row, so the output block is (1, bn) of a (1, N) array
        acc = acc + jax.lax.dot_general(
            lut[j:j + 1], onehot.astype(jnp.float32),
            (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    # pad-tail guard: rows past the true length scored the zero pad's codes
    # — poison them so no caller can rank the pad as a candidate
    row = pl.program_id(0) * bn + jax.lax.broadcasted_iota(
        jnp.int32, (1, bn), 1)
    out_ref[...] = jnp.where(row < nvalid_ref[0], acc, jnp.inf)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "interpret", "keep_pad"))
def pq_adc(codes, lut, *, interpret, block_n=512, keep_pad=False,
           nvalid=None):
    """codes (N, M) uint8; lut (M, 256) f32 -> (N,) f32.

    `nvalid` (traced scalar, defaults to N) marks the true row count when
    the caller already padded `codes` (shape bucketing): rows >= nvalid
    come back +inf. `keep_pad=True` returns the full padded buffer (its
    tail guarded to +inf) instead of slicing — the bucketed wrappers slice
    once at their own bucket boundary."""
    n, m = codes.shape
    pad = (-n) % block_n
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    np_ = codes.shape[0]
    nv = jnp.asarray([n if nvalid is None else nvalid], jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(np_ // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, m), lambda i, nv: (i, 0)),
            pl.BlockSpec((m, 256), lambda i, nv: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i, nv: (0, i)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.float32),
        interpret=interpret,
    )(nv, codes, lut)[0]
    return out if keep_pad else out[:n]
