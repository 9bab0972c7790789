"""A CPU rehearsal of each closed-loop cell prints a last line of the
contract's shape; the traced one prints its per-layer metrics."""
from bench import registry
from benchhelp import assert_contract_line, rehearse


def test_sift_closed64_traced():
    bench = registry.benchmark()
    cell = "sift-octopusann.closed64"
    info, result, err = rehearse(cell, seed=2 ** 31 + 3, trace=1)
    want = assert_contract_line(result, bench, cell, "per_layer")
    # a CPU trace has every reader's input but the chip's peaks
    assert set(result["metrics"]) == set(want) - {"search.roofline_pct"}
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    bd = result["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert info["window"]["compiled_in_window"] == 0
    assert "check recall_at_10" in err.strip().splitlines()[-3]


def test_deep_closed64():
    bench = registry.benchmark()
    cell = "deep-baseline.closed64"
    info, result, _ = rehearse(cell, seed=17)
    want = assert_contract_line(result, bench, cell, "end_to_end")
    assert set(result["metrics"]) == set(want) == {"qps", "recall_at_10",
                                                   "setup_s"}
    assert info["window"]["lowered_in_window"] == 0
