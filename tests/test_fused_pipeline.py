"""Fused pipelined kernel: equivalence, pad guards, compile-count bounds,
and the facade contract that `pipeline="fused"` changes ONLY the clock.

Four claims pinned here:
  1. fused_page_rank == page_scan_ref + per-page one-hot ADC (the fused
     body computes exactly what the two kernels it absorbs computed);
  2. pq_adc's pad tail is +inf-guarded inside the kernel (regression: a
     length with n % block_n != 0 used to leave garbage in the padded
     rows, visible to any bucketed caller that keeps the full buffer);
  3. the ops-layer shape bucketing bounds recompiles: a whole width ladder
     through the bucketed wrappers adds at most one compiled variant per
     power-of-two bucket (jit cache-size deltas, not timing);
  4. DiskIndex.search with pipeline="fused" is bit-identical to
     pipeline=True — the fused kernel is a measurement surface, never a
     result path — and carries measured_step_us next to the modeled time.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels as ops
from repro.kernels.fused_search import fused_page_rank, page_adc
from repro.kernels.pq_adc import pq_adc
from repro.kernels.ref import fused_page_rank_ref, pq_adc_ref


def _rand_case(rng, n_pages, n_p, d, m, w, q, dtype):
    pages = jnp.asarray(rng.normal(size=(n_pages, n_p, d)), dtype)
    codes = jnp.asarray(rng.integers(0, 256, (n_pages, n_p, m))
                        .astype(np.uint8))
    ids = jnp.asarray(rng.integers(0, n_pages, w).astype(np.int32))
    qs = jnp.asarray(rng.normal(size=(q, d)), dtype)
    lut = jnp.asarray((rng.normal(size=(q, m, 256)) ** 2).astype(np.float32))
    return pages, codes, ids, qs, lut


# -- 1. fused kernel == reference composition -------------------------------


@pytest.mark.parametrize("n_pages,n_p,d,m,w,q", [
    (16, 8, 128, 16, 4, 1),
    (64, 8, 128, 16, 8, 4),
    (32, 16, 256, 8, 6, 8),
    (8, 8, 128, 4, 3, 2),      # odd width (pad tail in the bucketed wrapper)
    (128, 8, 128, 16, 16, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_matches_ref(n_pages, n_p, d, m, w, q, dtype):
    rng = np.random.default_rng(n_pages + d + w)
    pages, codes, ids, qs, lut = _rand_case(rng, n_pages, n_p, d, m, w, q,
                                            dtype)
    exact, adc = fused_page_rank(pages, codes, ids, qs, lut, interpret=True)
    exact_ref, adc_ref = fused_page_rank_ref(pages, codes, ids, qs, lut)
    tol = 1e-5 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(np.asarray(exact), np.asarray(exact_ref),
                               rtol=tol, atol=tol * d)
    np.testing.assert_allclose(np.asarray(adc), np.asarray(adc_ref),
                               rtol=1e-4, atol=1e-3)


def test_fused_matches_split_kernels():
    """The fused grid and the two separate grids it replaces agree on the
    same schedule (duplicate ids included — a page staged twice scores
    identically both times)."""
    rng = np.random.default_rng(7)
    pages, codes, _, qs, lut = _rand_case(rng, 32, 8, 128, 16, 6, 8,
                                          jnp.float32)
    ids = jnp.asarray(np.array([3, 3, 0, 31, 7, 3], np.int32))
    exact_f, adc_f = fused_page_rank(pages, codes, ids, qs, lut,
                                     interpret=True)
    from repro.kernels.page_scan import page_scan
    exact_s = page_scan(pages, ids, qs, interpret=True)
    adc_s = page_adc(codes, ids, lut, interpret=True)
    np.testing.assert_allclose(np.asarray(exact_f), np.asarray(exact_s),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(adc_f), np.asarray(adc_s),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(adc_f[0]), np.asarray(adc_f[1]),
                               rtol=1e-6)


def test_fused_bucketed_wrapper_slices_pad():
    """ops.fused_page_rank pads the schedule to its bucket and must slice
    the padded steps back off."""
    rng = np.random.default_rng(11)
    pages, codes, ids, qs, lut = _rand_case(rng, 16, 8, 128, 8, 5, 4,
                                            jnp.float32)
    exact, adc = ops.fused_page_rank(pages, codes, ids, qs, lut)
    assert exact.shape == (5, 8, 4) and adc.shape == (5, 8, 4)
    exact_ref, adc_ref = fused_page_rank_ref(pages, codes, ids, qs, lut)
    np.testing.assert_allclose(np.asarray(exact), np.asarray(exact_ref),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(adc), np.asarray(adc_ref),
                               rtol=1e-4, atol=1e-3)


# -- 2. pq_adc pad-tail guard -----------------------------------------------


@pytest.mark.parametrize("n,block", [(100, 64), (513, 512), (7, 8), (65, 64)])
def test_pq_adc_pad_tail_is_inf(n, block):
    """n % block_n != 0: the kernel itself guards the padded rows to +inf
    (regression — the tail used to hold garbage LUT sums, hidden only by
    the caller's slice)."""
    rng = np.random.default_rng(n)
    codes = jnp.asarray(rng.integers(0, 256, (n, 16)).astype(np.uint8))
    lut = jnp.asarray((rng.normal(size=(16, 256)) ** 2).astype(np.float32))
    out = np.asarray(pq_adc(codes, lut, block_n=block, interpret=True,
                            keep_pad=True))
    assert out.shape[0] % block == 0 and out.shape[0] >= n
    np.testing.assert_allclose(out[:n], np.asarray(pq_adc_ref(codes, lut)),
                               rtol=1e-5)
    assert np.all(np.isinf(out[n:])), "padded rows must be +inf-guarded"
    assert np.all(out[n:] > 0)


def test_pq_adc_bucketed_wrapper():
    """The ops-layer bucketed pq_adc returns exactly n rows and matches the
    oracle even when n lands mid-bucket."""
    rng = np.random.default_rng(5)
    for n in (100, 513, 700, 1025):
        codes = jnp.asarray(rng.integers(0, 256, (n, 8)).astype(np.uint8))
        lut = jnp.asarray((rng.normal(size=(8, 256)) ** 2).astype(np.float32))
        out = np.asarray(ops.pq_adc(codes, lut, block_n=256))
        assert out.shape[0] == n
        np.testing.assert_allclose(out, np.asarray(pq_adc_ref(codes, lut)),
                                   rtol=1e-5)


# -- 3. bucketing bounds compiles -------------------------------------------


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False)])
def test_wrappers_pick_mode_from_backend(monkeypatch, backend, interpret):
    from repro.kernels import ops as kops
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kops._interpret() is interpret


def test_wrappers_refuse_other_backends(monkeypatch):
    """No backend silently stands in for the chip."""
    from repro.kernels import ops as kops
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        kops._interpret()


def test_bucket_size_ladder():
    assert [ops.bucket_size(n) for n in (1, 3, 4, 5, 8, 9, 16, 17)] == \
        [4, 4, 4, 8, 8, 16, 16, 32]
    with pytest.raises(ValueError):
        ops.bucket_size(0)


def test_width_ladder_bounded_compiles():
    """A whole width ladder through the bucketed wrappers compiles at most
    one variant per power-of-two bucket (the DynamicWidth/degrade case that
    motivated the bucketing)."""
    from repro.kernels.page_scan import page_scan as raw_scan
    rng = np.random.default_rng(2)
    pages = jnp.asarray(rng.normal(size=(32, 8, 128)).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, 256, (32, 8, 8)).astype(np.uint8))
    qs = jnp.asarray(rng.normal(size=(4, 128)).astype(np.float32))
    lut = jnp.asarray((rng.normal(size=(4, 8, 256)) ** 2).astype(np.float32))
    widths = list(range(1, 17))            # ladder spans buckets {4, 8, 16}
    before_scan = raw_scan._cache_size()
    before_fused = fused_page_rank._cache_size()
    for w in widths:
        ids = jnp.asarray(rng.integers(0, 32, w).astype(np.int32))
        ops.page_scan(pages, ids, qs)
        ops.fused_page_rank(pages, codes, ids, qs, lut)
    buckets = {ops.bucket_size(w) for w in widths}
    assert raw_scan._cache_size() - before_scan <= len(buckets)
    assert fused_page_rank._cache_size() - before_fused <= len(buckets)


def test_pq_adc_length_ladder_bounded_compiles():
    """Lengths sharing a bucket share a compile: nvalid is traced, so only
    the padded shape keys the jit cache."""
    rng = np.random.default_rng(3)
    lut = jnp.asarray((rng.normal(size=(8, 256)) ** 2).astype(np.float32))
    before = pq_adc._cache_size()
    lengths = [129, 150, 200, 255, 256]    # all bucket to 256 at block_n=64
    for n in lengths:
        codes = jnp.asarray(rng.integers(0, 256, (n, 8)).astype(np.uint8))
        ops.pq_adc(codes, lut, block_n=64)
    assert pq_adc._cache_size() - before <= 1


# -- 4. facade contract: fused changes only the clock -----------------------


def test_facade_fused_bit_identical(base_index, small_dataset):
    from repro.core import get_preset
    cfg = get_preset("pipeline", L=32)
    q = small_dataset.queries[:16]
    a = base_index.search(q, cfg)
    b = base_index.search(q, cfg.replace(pipeline="fused"))
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.dists, b.dists)
    np.testing.assert_array_equal(a.page_reads, b.page_reads)
    np.testing.assert_array_equal(a.hops, b.hops)
    assert a.measured_step_us is None
    assert b.measured_step_us is not None and len(b.measured_step_us) == 16
    assert np.all(b.measured_step_us >= 0)
    assert b.measured_step_us[b.page_reads > 0].min() > 0


def test_fused_stats_survive_concat_and_take(base_index, small_dataset):
    """measured_step_us rides the QueryStats lifecycle (batch concat, the
    serving layer's take) like every other kernel column."""
    from repro.core import get_preset
    cfg = get_preset("pipeline", L=32, pipeline="fused")
    q = small_dataset.queries[:12]
    st = base_index.search(q, cfg, batch=5)    # 3 batches -> concat path
    assert st.measured_step_us.shape == (12,)
    assert st.take(7).measured_step_us.shape == (7,)
