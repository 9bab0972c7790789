"""The control, the reference computed in bfloat16 in the program's place,
comes out not correct while the program's own answers pass: bench/control.py
at a tiny size on the CPU, for each configuration."""
import json

import pytest

from benchhelp import TINY


@pytest.mark.parametrize("config", ["deep-baseline", "sift-octopusann"])
def test_control_fails_and_program_passes(monkeypatch, capsys, config):
    from bench import control, run
    monkeypatch.setattr(run, "place_compile_cache", lambda: None)
    sets = [a for s in TINY for a in ("--set", s)]
    control.main(["--config", config, "--seeds", "3,2147483700",
                  "--seconds", "0.5", "--ladder", "12,16", "--ladder-seeds",
                  "1", "--recall-target", "0", "--rehearse", *sets])
    rows = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    cells = [r for r in rows if "cell" in r]
    # the ladder stops at its first rung, whose recall meets a target of 0
    assert [r["L"] for r in cells] == [12, 12, 12]
    assert [r["phase"] for r in cells] == ["ladder", "seeds", "seeds"]
    assert {"chosen_L": 12, "recall_target": 0} in rows
    for r in cells:
        assert r["program.correct"] is True, r
        assert r["control.correct"] is False, r
        assert r["control.rerank_rel_err"] > 10 * r["program.rerank_rel_err"]
