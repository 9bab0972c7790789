"""page_scan — the paper's disk path, TPU-native.

One kernel fuses three of the paper's techniques:
  * the "4 KB random page read" becomes a dynamic-index HBM->VMEM block DMA
    driven by scalar-prefetched page ids (PrefetchScalarGridSpec);
  * *Pipeline* (§4.3.2) is the Pallas grid pipeline: the DMA for page i+1
    overlaps the MXU compute on page i (double buffering) — no speculation,
    so the Finding-5 penalty does not exist on TPU;
  * *PageSearch* (§4.3.3) is free: the MXU scores ALL n_p records of the
    fetched tile against the whole query block in one (n_p, d) x (d, Q)
    matmul — computing only the target record would waste the tile anyway.

Layout contract (TPU tiling): d padded to 128 lanes, n_p to 8 sublanes,
Q (query block) a multiple of 128 for MXU efficiency. `interpret` is
required: True runs the body as jnp on the CPU (tests/test_kernels.py sweeps
shapes/dtypes against ref.page_scan_ref), False compiles it with Mosaic for
the TPU (tests/test_tpu_compile.py compiles it for a described v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(page_ids_ref, q_ref, qsq_ref, pages_ref, out_ref):
    """Grid step i handles page page_ids[i].
    q_ref (d, Q) VMEM; pages_ref block (1, n_p, d); out (1, n_p, Q)."""
    x = pages_ref[0].astype(jnp.float32)                  # (n_p, d)
    q = q_ref[...].astype(jnp.float32)                    # (d, Q)
    x2 = jnp.sum(jnp.square(x), axis=-1, keepdims=True)   # (n_p, 1)
    # f32 passes: at default precision the MXU rounds float pages to bf16
    xq = jnp.dot(x, q, precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)      # MXU (n_p, Q)
    out_ref[0] = x2 - 2.0 * xq + qsq_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_scan(pages, page_ids, q, *, interpret):
    """pages (P, n_p, d); page_ids (W,); q (Q, d) -> (W, n_p, Q) f32."""
    p, n_p, d = pages.shape
    w = page_ids.shape[0]
    qn = q.shape[0]
    qt = jnp.swapaxes(q, 0, 1)                            # (d, Q)
    qsq = jnp.sum(jnp.square(q.astype(jnp.float32)), -1)[None, :]  # (1, Q)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w,),
        in_specs=[
            pl.BlockSpec((d, qn), lambda i, ids: (0, 0)),         # q
            pl.BlockSpec((1, qn), lambda i, ids: (0, 0)),         # qsq
            pl.BlockSpec((1, n_p, d), lambda i, ids: (ids[i], 0, 0)),  # page
        ],
        out_specs=pl.BlockSpec((1, n_p, qn), lambda i, ids: (i, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((w, n_p, qn), jnp.float32),
        interpret=interpret,
    )(page_ids.astype(jnp.int32), qt, qsq, pages)
