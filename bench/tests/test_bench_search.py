"""The search cell at a small size on the CPU: the program is correct by
the float64 reference; a run whose top-k kernel returns a wrong winner is
not; and the control (int8 brute force in the program's place) breaks a
limit."""

import numpy as np

from bench_tiny import SEED, control, run_tiny, tiny_cell

SEARCH = "search.sift1m-ivf1024"


def test_program_is_correct(tmp_path):
    out, _ = run_tiny(SEARCH, tmp_path, seconds=1.0)
    assert out["correct"] and out["attempted"] > 0
    checks = out["checks"]
    assert checks["distance_gap"]["value"] <= 1.0
    assert checks["value_mismatches"]["value"] == 0
    assert set(out["metrics"]) == {"search_qps", "setup_s"}


def test_altered_winner_is_not_correct(tmp_path, monkeypatch):
    from repro.kernels import ops

    orig = ops.ivf_topk
    k = tiny_cell(SEARCH).config["k"]

    def altered(queries, cands, ids, kk, *args, **kwargs):
        d, w = orig(queries, cands, ids, kk, *args, **kwargs)
        if kk != k:  # the probe
            return d, w
        w = np.array(w)
        w[0, 0] = (w[0, 0] + 1) % 3000
        return d, w

    monkeypatch.setattr(ops, "ivf_topk", altered)
    out, _ = run_tiny(SEARCH, tmp_path, seconds=1.0)
    assert not out["correct"]
    assert out["checks"]["distance_gap"]["value"] > 1.0


def test_control_breaks_a_limit():
    cell = tiny_cell(SEARCH)
    nums = control.readings(cell, SEED, 4)
    lim = cell.config["limits"]
    assert any(nums[k] > lim[k] for k in lim)
    assert nums["distance_gap"] > lim["distance_gap"]
