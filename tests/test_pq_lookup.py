"""The ADC table lookup of the beam search: the TPU's select form against
the CPU's gather form, called directly at the served widths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.search_kernel import adc_lookup_gather, adc_lookup_select
from repro.core.searchutils import SENTINEL

B, M, KSUB, DSUB = 16, 16, 256, 8      # one served batch, PQ M=16
N_ROWS = 4096                          # rows of the code table


@pytest.mark.parametrize("n", [2048, 512])   # w_cap x R: sift 32x64, deep 8x64
def test_select_lookup_matches_gather(n):
    rng = np.random.default_rng(n)
    cents = rng.standard_normal((M, KSUB, DSUB)).astype(np.float32)
    q = rng.standard_normal((B, M, DSUB)).astype(np.float32)
    q[0, 0] = cents[0, 0]              # a table entry of exactly 0
    luts = jnp.sum(jnp.square(cents[None] - q[:, :, None]), -1)  # (B,M,256)
    table = rng.integers(0, KSUB, (N_ROWS, M), dtype=np.uint8)
    table[0], table[1], table[N_ROWS - 1] = 0, 255, np.arange(M) * 17
    ids = rng.integers(0, N_ROWS, (B, n), dtype=np.int32)
    ids[:, :4] = [0, 1, N_ROWS - 1, -1]
    ids[:, -n // 8:] = int(SENTINEL)   # padding ids, clamped as the kernel does
    safe = np.clip(ids, 0, N_ROWS - 1)
    codes = jnp.asarray(table[safe])   # (B, n, M)
    assert {0, 255} <= set(np.unique(codes).tolist())

    sel = jax.jit(jax.vmap(adc_lookup_select))(luts, codes)
    gat = jax.jit(jax.vmap(adc_lookup_gather))(luts, codes)
    assert sel.shape == gat.shape == (B, n, M)
    np.testing.assert_array_equal(np.asarray(sel).view(np.uint32),
                                  np.asarray(gat).view(np.uint32))
    assert float(jnp.min(gat)) == 0.0
    np.testing.assert_allclose(jnp.sum(sel, -1), jnp.sum(gat, -1),
                               rtol=1e-6)
