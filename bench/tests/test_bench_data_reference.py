"""bench/data.py is deterministic per seed; bench/reference.py matches a
float64 brute force; bench/checks.py catches wrong and malformed answers."""
import numpy as np
import pytest

from bench import checks, data, reference

SIFT = {"name": "sift-like", "d": 128, "dtype": "uint8", "clusters": 64}
DEEP = {"name": "deep-like", "d": 96, "dtype": "float", "clusters": 64}
GUARANTEES = {"k": 10, "recall_at_10_min": 0.9, "rerank_rel_err_max": 1e-5}


@pytest.mark.parametrize("spec", [SIFT, DEEP], ids=["sift", "deep"])
def test_data_deterministic_per_seed(spec):
    a = data.make(spec, 512, 64, seed=2 ** 31 + 11)
    b = data.make(spec, 512, 64, seed=2 ** 31 + 11)
    c = data.make(spec, 512, 64, seed=2 ** 31 + 12)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == (512, spec["d"]) and a[1].shape == (64, spec["d"])
    assert a[0].dtype == np.float32
    if spec["dtype"] == "uint8":
        assert a[0].min() >= 0 and a[0].max() <= 255
        assert np.array_equal(a[0], np.round(a[0]))


def test_data_is_the_program_generators_arithmetic():
    from repro.core import make_dataset
    ds = make_dataset("deep-like", n=512, nq=32, seed=4)
    x, q = data.make(DEEP, 512, 32, seed=4)
    assert np.array_equal(ds.vectors, x) and np.array_equal(ds.queries, q)


def brute_force(base, queries, k):
    d = np.square(base[None].astype(np.float64)
                  - queries[:, None].astype(np.float64)).sum(-1)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, 1)


@pytest.mark.parametrize("spec", [SIFT, DEEP], ids=["sift", "deep"])
def test_reference_matches_float64_brute_force(spec):
    base, queries = data.make(spec, 700, 40, seed=9)
    ids, d = reference.exact_topk(base, queries, 10)
    want_ids, want_d = brute_force(base, queries, 10)
    np.testing.assert_allclose(d, want_d, rtol=1e-12)
    # ids agree wherever the distances are not tied
    assert (ids == want_ids).mean() > 0.99


def test_control_is_caught_and_program_answers_pass():
    base, queries = data.make(DEEP, 700, 40, seed=10)
    ref_ids, ref_d = reference.exact_topk(base, queries, 10)
    found, failed = checks.judge(base, queries, ref_ids,
                                 ref_d.astype(np.float32), ref_ids,
                                 GUARANTEES)
    assert failed == 0 and all(c["holds"] for c in found.values())
    c_ids, c_d = reference.control_topk(base, queries, 10)
    found, failed = checks.judge(base, queries, c_ids, c_d, ref_ids,
                                 GUARANTEES)
    assert not found["rerank_rel_err"]["holds"] and failed > 0


def test_checks_catch_altered_and_malformed_answers():
    base, queries = data.make(SIFT, 500, 20, seed=1)
    ids, d = reference.exact_topk(base, queries, 10)
    altered = ids.copy()
    altered[3, 0] = (altered[3, 0] + 1) % len(base)
    found, failed = checks.judge(base, queries, altered, d, ids, GUARANTEES)
    assert failed == 1 and not found["rerank_rel_err"]["holds"]
    short = ids.copy()
    short[0, 9] = -1
    repeated = ids.copy()
    repeated[1, 5] = repeated[1, 4]
    for bad in (short, repeated):
        found, failed = checks.judge(base, queries, bad, d, ids, GUARANTEES)
        assert found["malformed_answers"]["value"] == 1 and failed == 1
    found, _ = checks.judge(base, queries, ids[::-1], d[::-1], ids,
                            GUARANTEES)
    assert found["recall_at_10"]["value"] < 0.9
