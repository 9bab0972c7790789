"""fused_search — the beam loop's page stream as ONE pipelined Pallas grid.

Before this kernel the disk hot path was two separately-jitted calls per
hop: page_scan (exact scoring of the fetched tiles) and pq_adc (ADC LUT
ranking of the residents' codes), each with its own grid, its own HBM pass
and its own dispatch. The fused kernel runs the WHOLE multi-hop page
schedule as a single PrefetchScalarGridSpec grid:

  grid step i handles page schedule[i] (the schedule is hop-major: hop t's
  pages first, then the pages LAANN-style look-ahead staged for hop t+1
  from the current frontier's best unexpanded candidates, and so on) —

    * the HBM->VMEM DMAs for step i+1's vector tile AND code tile are
      issued by the Pallas pipeline while step i computes: this is the
      double buffer the analytic `prefetch_overlap` rebate only modeled;
    * the body fuses both distance computations over the SAME resident
      tile: the exact (n_p, d) x (d, Q) page-scan matmul and the ADC LUT
      scan — with the whole stacked LUT resident in VMEM, the M
      per-subspace one-hot matmuls collapse into ONE (n_p, M*256) x
      (M*256, Q) MXU matmul — so hop t's PQ ranking overlaps hop t+1's
      fetch instead of serializing behind it.

VMEM budget per step (f32): page tile n_p*d*4 + code tile n_p*M + query
block d*Q*4 + stacked LUT M*256*Q*4 (the per-query LUTs live transposed as
(M, 256, Q) so each subspace's scan is one MXU matmul for the whole query
block) + two output tiles n_p*Q*4, plus the (n_p, M*256) f32 one-hot — at
the sift-like page shape (n_p=10, d=128, M=16, Q=256) that is ~4.3 MiB,
~8.5 MiB double-buffered, inside the v5e's 16 MiB default scoped VMEM
(tests/test_tpu_compile.py compiles it for a described v5e at that shape).
The uint8 codes are widened to int32 before the one-hot broadcast: Mosaic
has no layout for the (n_p, M) -> (n_p, M, 1) shape cast on 8-bit vectors.

The kernel is a MEASUREMENT surface, not a result path: `pipeline="fused"`
searches still take their results from the reference beam search (bit
identity is golden-locked), and this kernel re-executes the traced page
schedule to produce a measured wall-clock step time next to the modeled
device time. tests/test_kernels.py sweeps it against composing
ref.page_scan_ref + ref.pq_adc_ref per page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_kernel(page_ids_ref, q_ref, qsq_ref, lut_ref, pages_ref,
                  codes_ref, out_exact_ref, out_adc_ref):
    """Grid step i: fused exact scan + ADC scan of page page_ids[i].
    q_ref (d, Q); lut_ref (M, 256, Q); pages block (1, n_p, d); codes block
    (1, n_p, M); outputs (1, n_p, Q) each."""
    x = pages_ref[0].astype(jnp.float32)                    # (n_p, d)
    q = q_ref[...].astype(jnp.float32)                      # (d, Q)
    x2 = jnp.sum(jnp.square(x), axis=-1, keepdims=True)     # (n_p, 1)
    xq = jnp.dot(x, q, precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)      # MXU (n_p, Q)
    out_exact_ref[0] = x2 - 2.0 * xq + qsq_ref[...]

    # Fusion keeps the WHOLE stacked LUT resident as one VMEM block, so the
    # per-subspace scan collapses into a single MXU matmul: the (n_p, M)
    # codes become one (n_p, M*256) one-hot whose column layout matches the
    # LUT flattened to (M*256, Q) — summing the M per-subspace products is
    # the matmul's own reduction. (The standalone page_adc/pq_adc path keeps
    # the per-subspace form; this bigger matmul is what the fused schedule
    # buys on top of the double buffer.)
    codes = codes_ref[0].astype(jnp.int32)                  # (n_p, M)
    n_p, m = codes.shape
    qn = q_ref.shape[1]
    onehot = (codes[:, :, None]
              == jax.lax.broadcasted_iota(jnp.int32, (n_p, m, 256), 2))
    out_adc_ref[0] = jnp.dot(
        onehot.astype(jnp.float32).reshape(n_p, m * 256),
        lut_ref[...].reshape(m * 256, qn),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_page_rank(pages, page_codes, page_ids, q, lut, *, interpret):
    """One pipelined grid over the page schedule.

    pages (P, n_p, d); page_codes (P, n_p, M) uint8; page_ids (W,) int32
    (the hop-major schedule); q (Q, d); lut (Q, M, 256) per-query ADC LUTs.
    Returns (exact (W, n_p, Q), adc (W, n_p, Q)) f32.
    """
    p, n_p, d = pages.shape
    m = page_codes.shape[2]
    w = page_ids.shape[0]
    qn = q.shape[0]
    qt = jnp.swapaxes(q, 0, 1)                              # (d, Q)
    qsq = jnp.sum(jnp.square(q.astype(jnp.float32)), -1)[None, :]  # (1, Q)
    lut_t = jnp.transpose(lut.astype(jnp.float32), (1, 2, 0))  # (M, 256, Q)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w,),
        in_specs=[
            pl.BlockSpec((d, qn), lambda i, ids: (0, 0)),          # q
            pl.BlockSpec((1, qn), lambda i, ids: (0, 0)),          # qsq
            pl.BlockSpec((m, 256, qn), lambda i, ids: (0, 0, 0)),  # lut
            pl.BlockSpec((1, n_p, d), lambda i, ids: (ids[i], 0, 0)),
            pl.BlockSpec((1, n_p, m), lambda i, ids: (ids[i], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n_p, qn), lambda i, ids: (i, 0, 0)),
            pl.BlockSpec((1, n_p, qn), lambda i, ids: (i, 0, 0)),
        ],
    )
    return pl.pallas_call(
        _fused_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((w, n_p, qn), jnp.float32),
                   jax.ShapeDtypeStruct((w, n_p, qn), jnp.float32)],
        interpret=interpret,
    )(page_ids.astype(jnp.int32), qt, qsq, lut_t, pages, page_codes)


# --- the unfused counterpart (two separately-jitted grids) -----------------


def _adc_kernel(page_ids_ref, lut_ref, codes_ref, out_ref):
    codes = codes_ref[0]                                    # (n_p, M)
    n_p, m = codes.shape
    qn = lut_ref.shape[2]
    acc = jnp.zeros((n_p, qn), jnp.float32)
    for j in range(m):
        onehot = (codes[:, j][:, None].astype(jnp.int32)
                  == jax.lax.broadcasted_iota(jnp.int32, (n_p, 256), 1))
        acc = acc + jnp.dot(onehot.astype(jnp.float32), lut_ref[j],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    out_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_adc(page_codes, page_ids, lut, *, interpret):
    """The ADC half alone, its own grid and dispatch — the second of the
    two calls the fused kernel replaces (the exact half alone is
    kernels/page_scan.py). page_codes (P, n_p, M) uint8; page_ids (W,);
    lut (Q, M, 256) -> (W, n_p, Q) f32."""
    p, n_p, m = page_codes.shape
    w = page_ids.shape[0]
    qn = lut.shape[0]
    lut_t = jnp.transpose(lut.astype(jnp.float32), (1, 2, 0))  # (M, 256, Q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w,),
        in_specs=[
            pl.BlockSpec((m, 256, qn), lambda i, ids: (0, 0, 0)),
            pl.BlockSpec((1, n_p, m), lambda i, ids: (ids[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_p, qn), lambda i, ids: (i, 0, 0)),
    )
    return pl.pallas_call(
        _adc_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((w, n_p, qn), jnp.float32),
        interpret=interpret,
    )(page_ids.astype(jnp.int32), lut_t, page_codes)
