"""The take cell at a small size on the CPU: a run of the program is
correct by the reference; a run with an answer altered where the kernel
produces it is not; and the control (the reference in the program's place
at a lower type) breaks the limit.  The same holds for nullable int and
list columns (mini-block), which the table system and its reference
serve for a configuration that names them."""

import numpy as np
import pytest

from bench_tiny import SEED, control, run_tiny, tiny_cell

WIDE = "take-wide.sift1m-vectors"
NARROW_COLUMNS = {
    "tag": {"kind": "int", "dtype": "int64", "null_share": 0.1,
            "range": [0, 2**31]},
    "labels": {"kind": "list", "dtype": "int32", "null_share": 0.05,
               "length": [0, 8], "range": [0, 2**20]},
}


def narrow_cell():
    """The tiny take cell on a table with nullable int64 and List<int32>
    columns, taking those two."""
    cell = tiny_cell(WIDE)
    cell.config["columns"].update(NARROW_COLUMNS)
    cell.traffic["columns"] = list(NARROW_COLUMNS)
    return cell


def test_narrow_columns_are_correct(tmp_path):
    out, _ = run_tiny(WIDE, tmp_path, cell=narrow_cell())
    assert out["correct"] and out["attempted"] > 0
    assert out["checks"] == {"mismatched_rows": {"value": 0, "limit": 0}}


def test_altered_decode_is_not_correct(tmp_path, monkeypatch):
    from repro.kernels import ops

    orig = ops.miniblock_decode

    def altered(*args, **kwargs):
        rep, defs, vals = orig(*args, **kwargs)
        return rep, defs, vals.at[0, 0].add(1)

    monkeypatch.setattr(ops, "miniblock_decode", altered)
    out, _ = run_tiny(WIDE, tmp_path, cell=narrow_cell())
    assert not out["correct"]
    assert out["checks"]["mismatched_rows"]["value"] > 0


def test_narrow_control_breaks_the_limit():
    cell = narrow_cell()
    nums = control.readings(cell, SEED, 3)
    assert nums["mismatched_rows"] > cell.config["limits"]["mismatched_rows"]


@pytest.mark.parametrize("name", [WIDE])
def test_program_is_correct(name, tmp_path):
    out, lines = run_tiny(name, tmp_path)
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"] == {"mismatched_rows": {"value": 0, "limit": 0}}
    assert set(out["metrics"]) == {"take_rows_s", "take_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert lines[-1] == "check mismatched_rows: 0 (limit 0)"


def test_traced_run_reports_the_per_layer_metrics(tmp_path):
    out, lines = run_tiny(WIDE, tmp_path, trace=True)
    assert out["correct"]
    got = set(out["metrics"])
    # the CPU has no TPU plane: device metrics read nothing but the idle
    # share, and the kernel's roofline is left out
    assert {"dataset_take_ms.take", "encodings_self_ms.take",
            "store_drain_ms.take", "dispatch_ms.take"} <= got
    assert "fullzip_gather_roofline" not in got
    assert out["device"]["window_s"] > 0
    assert out["breakdown"]["idle_gaps"]
    assert "decode.fallback counters: none moved" in lines


def test_altered_gather_is_not_correct(tmp_path, monkeypatch):
    from repro.kernels import ops

    orig = ops.fullzip_gather

    def altered(zipped, rows, **kwargs):
        out = np.array(orig(zipped, rows, **kwargs))
        out.reshape(len(out), -1).view(np.uint8)[0, -1] ^= 0x40
        return out

    monkeypatch.setattr(ops, "fullzip_gather", altered)
    out, _ = run_tiny(WIDE, tmp_path)
    assert not out["correct"]
    assert out["checks"]["mismatched_rows"]["value"] > 0


@pytest.mark.parametrize("name", [WIDE])
def test_control_breaks_the_limit(name):
    cell = tiny_cell(name)
    nums = control.readings(cell, SEED, 3)
    assert nums["mismatched_rows"] > cell.config["limits"]["mismatched_rows"]
