"""Public jit'd wrappers for the Pallas kernels.

The wrappers pick the execution mode from the backend: Mosaic-compiled on a
TPU, interpret mode (the kernel body runs as jnp per grid step) on the CPU,
and an error on any other backend — these are TPU kernels, and no backend
silently stands in for the chip.

Shape bucketing: the raw kernels are jitted per exact shape, so a beam
width that moves every step (DynamicWidth shrinking/growing the frontier,
the admission controller's degrade ladder) would trigger a recompile per
distinct width. The wrappers here pad the varying axis up to a power-of-two
bucket (mirroring MutableIndex's chunked-capacity trick, which bounds
recompiles the same way on the vid axis) and slice the result back, so the
whole width ladder 1..2^k shares k+1 compiled variants. Padding ids point
at page 0 (always valid); padded pq_adc rows are guarded to +inf by the
kernel itself (`nvalid`), so a bucket can never leak garbage distances.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_search import fused_page_rank as _fused_page_rank
from repro.kernels.fused_search import page_adc as _page_adc
from repro.kernels.page_scan import page_scan as _page_scan
from repro.kernels.pq_adc import pq_adc as _pq_adc

_MIN_BUCKET = 4     # smallest width bucket (floor of the power-of-two ladder)


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for a TPU or interpret on the CPU; "
        f"JAX's backend is {backend!r}")


def bucket_size(n: int, floor: int = _MIN_BUCKET) -> int:
    """Next power of two >= n (>= floor): the padded size whose compiled
    kernel this call shares with every other length in the bucket."""
    if n < 1:
        raise ValueError(f"bucket_size needs n >= 1, got {n}")
    b = floor
    while b < n:
        b *= 2
    return b


def _pad_ids(page_ids, bucket: int):
    """Pad a page-id schedule to its bucket with id 0 (always a valid page;
    the padded grid steps score page 0 and are sliced away)."""
    w = page_ids.shape[0]
    if w == bucket:
        return page_ids
    return jnp.concatenate(
        [page_ids, jnp.zeros((bucket - w,), page_ids.dtype)])


def page_scan(pages, page_ids, q):
    """Fused page-fetch + score-all-residents (PageSearch+Pipeline on TPU).
    Width-bucketed: all widths in (bucket/2, bucket] share one compile."""
    w = page_ids.shape[0]
    b = bucket_size(w)
    out = _page_scan(pages, _pad_ids(page_ids, b), q,
                     interpret=_interpret())
    return out[:w]


def pq_adc(codes, lut, block_n=512):
    """ADC LUT scan over PQ codes (memory-layout PQ filter). Length-bucketed
    above the kernel's own block padding: all N in (bucket/2, bucket] share
    one compile, with the true length passed as a traced scalar and the pad
    tail guarded to +inf inside the kernel."""
    n = codes.shape[0]
    b = bucket_size(n, floor=min(block_n, bucket_size(n)))
    if b > n:
        codes = jnp.pad(codes, ((0, b - n), (0, 0)))
    out = _pq_adc(codes, lut, block_n=block_n, interpret=_interpret(),
                  nvalid=jnp.int32(n))
    return out[:n]


def fused_page_rank(pages, page_codes, page_ids, q, lut):
    """The fused pipelined hot path (kernels/fused_search.py): one grid,
    double-buffered page DMA overlapping exact-scan + ADC compute.
    Width-bucketed like page_scan."""
    w = page_ids.shape[0]
    b = bucket_size(w)
    exact, adc = _fused_page_rank(pages, page_codes, _pad_ids(page_ids, b),
                                  q, lut, interpret=_interpret())
    return exact[:w], adc[:w]


def page_adc(page_codes, page_ids, lut):
    """The ADC half as its own grid (the unfused counterpart the fused
    kernel absorbs; used for measured-overlap comparisons)."""
    w = page_ids.shape[0]
    b = bucket_size(w)
    out = _page_adc(page_codes, _pad_ids(page_ids, b), lut,
                    interpret=_interpret())
    return out[:w]
