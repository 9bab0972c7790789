"""Property-based tests (hypothesis, optional) for the engine's invariants +
unit tests for PQ / layouts / Vamana pruning. When hypothesis is not
installed the property tests skip and the rest of the module still runs."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.searchutils import INF, SENTINEL, dedup_merge_topL

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - depends on the environment
    HAS_HYPOTHESIS = False


if HAS_HYPOTHESIS:
    @st.composite
    def id_key_flag_arrays(draw):
        n = draw(st.integers(2, 80))
        ids = draw(st.lists(st.integers(0, 20), min_size=n, max_size=n))
        # XLA flushes subnormals to zero; keep keys in the normal f32 range
        keys = draw(st.lists(
            st.floats(9.999999974752427e-07, 1e6, allow_nan=False, width=32),
            min_size=n, max_size=n))
        flags = draw(st.lists(st.booleans(), min_size=n, max_size=n))
        L = draw(st.integers(1, n))
        return ids, keys, flags, L

    @given(id_key_flag_arrays())
    @settings(max_examples=60, deadline=None)
    def test_dedup_merge_properties(data):
        ids, keys, flags, L = data
        i, k, f = dedup_merge_topL(
            jnp.asarray(ids, jnp.int32),
            jnp.asarray(keys, jnp.float32)[:, None],
            jnp.asarray(flags, bool)[:, None], L)
        i, k, f = np.asarray(i), np.asarray(k[:, 0]), np.asarray(f[:, 0])
        real = i[i < int(SENTINEL)]
        # unique ids
        assert len(set(real.tolist())) == len(real)
        # sorted by key
        kk = k[: len(real)]
        assert np.all(np.diff(kk) >= -1e-6)
        # min-key and OR-flag per id (exact reference)
        want = {}
        for id_, key_, fl in zip(ids, keys, flags):
            if id_ not in want:
                want[id_] = [key_, fl]
            else:
                want[id_][0] = min(want[id_][0], key_)
                want[id_][1] = want[id_][1] or fl
        for idx, id_ in enumerate(real.tolist()):
            np.testing.assert_allclose(k[idx], want[id_][0], rtol=1e-6)
            assert f[idx] == want[id_][1]
        # top-L: kept keys <= smallest dropped key
        if len(want) > L:
            dropped = sorted(v[0] for v in want.values())[L:]
            assert kk[-1] <= dropped[0] + 1e-6

    @given(st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=30, deadline=None)
    def test_quantize_roundtrip_bounded(seed):
        from repro.training.compression import dequantize, quantize
        rng = np.random.default_rng(seed)
        g = jnp.asarray(rng.normal(0, rng.uniform(1e-5, 10), (64,)),
                        jnp.float32)
        q, s = quantize(g)
        err = np.abs(np.asarray(dequantize(q, s) - g))
        assert err.max() <= float(s) / 2 + 1e-9  # half-ulp of the int8 grid
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_dedup_merge_properties():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_quantize_roundtrip_bounded():
        pass


def test_tpu_device_table_is_keyed_by_device_kind():
    from repro.core.device_model import tpu_device
    assert tpu_device("TPU v5 lite").name == "v5e"
    with pytest.raises(ValueError, match="'v5e'"):   # a name, not a kind
        tpu_device("v5e")


def test_tpu_device_assumes_no_chip():
    """With no TPU attached and no kind named there is nothing to price."""
    from repro.core.device_model import tpu_device
    with pytest.raises(RuntimeError, match="'cpu'"):
        tpu_device()


def test_error_feedback_unbiased():
    from repro.training.compression import ef_compress_tree, init_error_state
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(0, 1, (256,)), jnp.float32)}
    e = init_error_state(g)
    total_sent = np.zeros(256)
    steps = 50
    for _ in range(steps):
        sent, e = ef_compress_tree(g, e)
        total_sent += np.asarray(sent["w"])
    # long-run average of transmitted grads converges to the true grad
    np.testing.assert_allclose(total_sent / steps, np.asarray(g["w"]),
                               atol=2e-2)


def test_pq_error_decreases_with_m():
    from repro.core.pq import train_pq
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, 64)).astype(np.float32)
    q = rng.normal(size=(64,)).astype(np.float32)
    true = ((x - q) ** 2).sum(1)
    errs = []
    for m in (4, 16):
        pq = train_pq(x, m=m, sample=2048, iters=6)
        approx = pq.adc(q, np.arange(len(x)))
        errs.append(np.abs(approx - true).mean())
    assert errs[1] < errs[0]


def test_layout_roundtrip(small_dataset, small_graph):
    from repro.core.pages import build_layout
    G, _, _ = small_graph
    lay = build_layout(small_dataset.vectors, G)
    n = small_dataset.n
    vids = np.arange(n)
    back = lay.page_vids[lay.vid2page[vids], lay.vid2slot[vids]]
    np.testing.assert_array_equal(back, vids)
    # record contents match source
    np.testing.assert_allclose(
        lay.page_vecs[lay.vid2page[:50], lay.vid2slot[:50]],
        small_dataset.vectors[:50], rtol=1e-6)
    np.testing.assert_array_equal(
        lay.page_nbrs[lay.vid2page[:50], lay.vid2slot[:50]], G[:50])


def test_shuffle_perm_is_permutation(small_dataset, small_graph):
    from repro.core.page_shuffle import shuffle_order
    G, med, _ = small_graph
    out = shuffle_order(G, med, n_p=7)
    perm = out["perm"]
    assert sorted(perm.tolist()) == list(range(small_dataset.n))


def test_robust_prune_degree_and_self(small_dataset):
    from repro.core.vamana import _robust_prune_batch
    from repro.core.searchutils import SENTINEL
    x = jnp.asarray(small_dataset.vectors[:256])
    ids = jnp.arange(8, dtype=jnp.int32)
    cand = jnp.tile(jnp.arange(64, dtype=jnp.int32)[None], (8, 1))
    cd = jnp.asarray(np.linalg.norm(
        small_dataset.vectors[:64][None] - small_dataset.vectors[:8][:, None],
        axis=-1) ** 2)
    out = np.asarray(_robust_prune_batch(x, ids, cand, cd, R=16, alpha=1.2))
    for i in range(8):
        row = out[i][out[i] >= 0]
        assert i not in row.tolist()               # no self edge
        assert len(set(row.tolist())) == len(row)  # unique
        assert len(row) <= 16


def test_aisaq_layout_tradeoff(small_dataset, small_graph):
    """AiS: bigger records -> fewer records/page -> more disk, ~zero memory."""
    from repro.core import build_index, get_preset
    G, med, _ = small_graph
    idx_b = build_index(small_dataset, get_preset("baseline"),
                        graph=G, medoid_id=med)
    idx_a = build_index(small_dataset, get_preset("aisaq"),
                        graph=G, medoid_id=med)
    assert idx_a.layout.n_p <= idx_b.layout.n_p
    assert idx_a.layout.disk_bytes >= idx_b.layout.disk_bytes
    assert idx_a.memory_bytes() < idx_b.memory_bytes()
