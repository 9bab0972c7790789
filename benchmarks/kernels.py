"""Kernel microbench: interpret-mode wall time (CPU, correctness path) plus
the ANALYTIC device numbers the kernel is designed for (HBM-bound page_scan,
MXU-bound pq_adc) — the dry-run/roofline methodology at kernel granularity.
Peaks come from the shared device table (repro.core.device_model): the
entry of the device_kind REPRO_TPU_DEVICE names, else the attached TPU's."""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device_model import tpu_device
from repro.kernels import page_scan, pq_adc


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6


def main():
    dev = tpu_device(os.environ.get("REPRO_TPU_DEVICE"))
    rng = np.random.default_rng(0)
    print("name,us_per_call,derived")
    # page_scan: W=16 pages of (8,128) vs 128 queries
    pages = jnp.asarray(rng.normal(size=(1024, 8, 128)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 1024, 16).astype(np.int32))
    q = jnp.asarray(rng.normal(size=(128, 128)).astype(np.float32))
    us = _time(page_scan, pages, ids, q)
    bytes_moved = 16 * 8 * 128 * 4
    flops = 2 * 16 * 8 * 128 * 128
    t_mem = dev.memory_s(bytes_moved) * 1e6
    t_mxu = dev.compute_s(flops) * 1e6
    print(f"page_scan_16x8x128_q128,{us:.1f},"
          f"{dev.name}_mem_us={t_mem:.3f};{dev.name}_mxu_us={t_mxu:.3f};bound="
          f"{'memory' if t_mem > t_mxu else 'compute'}")
    # pq_adc: 64k codes x M=16
    codes = jnp.asarray(rng.integers(0, 256, (65536, 16)).astype(np.uint8))
    lut = jnp.asarray(rng.normal(size=(16, 256)).astype(np.float32))
    us = _time(pq_adc, codes, lut)
    bytes_moved = 65536 * 16
    flops = 2 * 65536 * 16 * 256  # one-hot matmul form
    print(f"pq_adc_64k_m16,{us:.1f},"
          f"{dev.name}_mem_us={dev.memory_s(bytes_moved) * 1e6:.3f};"
          f"{dev.name}_mxu_us={dev.compute_s(flops) * 1e6:.3f}")
    return 0


if __name__ == "__main__":
    main()
