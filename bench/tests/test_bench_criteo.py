"""The Criteo row-take cell at a small size on the CPU: a run of the program
is correct by its reference, a run with the decoded values altered where
the kernel produces them is not, the control (the reference at lower integer
types) breaks the limit, and the cell's per-layer metric files read the
spans they name."""

import numpy as np
import pytest

from bench_tiny import SEED, control, run_tiny
from harness import trace as tr
from harness.cell import load_cell

NAME = "take-rows.criteo1tb-multihot"


def tiny():
    """The cell at 3,000 rows in its 8 fragments, 64 rows a request."""
    cell = load_cell(NAME)
    cell.config["rows"] = 3000
    cell.traffic.update(rows_per_request=64, warmup={"quiet": 1, "max": 2})
    return cell


# the reference README's flags (mlcommons/training,
# recommendation_v2/torchrec_dlrm), as published
MULTI_HOT_SIZES = "3,2,1,2,6,1,1,1,1,7,3,8,1,6,9,5,1,1,1,12,100,27,10,3,1,1"
NUM_EMBEDDINGS_PER_FEATURE = (
    "40000000,39060,17295,7424,20265,3,7122,1543,63,40000000,3067956,405282,"
    "10,2209,11938,155,4,976,14,40000000,40000000,40000000,590152,12973,108,"
    "36")


def test_the_cell_takes_every_column_of_the_published_schema():
    cell = load_cell(NAME)
    cfg = cell.config
    assert cfg["multi_hot_sizes"] == \
        [int(x) for x in MULTI_HOT_SIZES.split(",")]
    assert cfg["num_embeddings_per_feature"] == \
        [int(x) for x in NUM_EMBEDDINGS_PER_FEATURE.split(",")]
    assert MULTI_HOT_SIZES in cfg["source_flags"]
    assert NUM_EMBEDDINGS_PER_FEATURE in cfg["source_flags"]
    cols = cell.system.columns(cfg)
    assert cell.traffic["columns"] == list(cols) and len(cols) == 40
    assert sum(cols[f"C{i}"]["size"] for i in range(1, 27)) == 214
    assert cols["C21"] == {"kind": "multi_hot", "size": 100,
                           "range": 40_000_000, "null_share": 0.03}
    assert cols["C17"]["range"] == 4 and cols["I12"]["null_share"] == 0.77


def test_generated_rows_follow_the_configuration():
    cell = tiny()
    data = cell.system.generate(cell.config, cell.traffic, SEED)
    c21 = data["columns"]["C21"]
    lens = np.diff(c21["offsets"])
    assert set(lens[c21["validity"]]) == {100}
    assert not lens[~c21["validity"]].any()
    assert c21["items"].dtype == np.int32 and c21["items"].max() < 40_000_000
    assert set(np.unique(data["columns"]["label"]["values"])) <= {0, 1}
    assert data["columns"]["I12"]["validity"].mean() < 0.5
    # the table comes from the configuration's data seed, not the run's
    again = cell.system.generate(cell.config, cell.traffic, SEED + 1)
    np.testing.assert_array_equal(again["columns"]["C1"]["items"],
                                  data["columns"]["C1"]["items"])


def test_program_is_correct(tmp_path):
    out, lines = run_tiny(NAME, tmp_path, cell=tiny())
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"] == {"mismatched_rows": {"value": 0, "limit": 0}}
    assert set(out["metrics"]) == {"take_rows_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert lines[-1] == "check mismatched_rows: 0 (limit 0)"


def test_traced_run_reports_the_per_layer_metrics(tmp_path):
    out, lines = run_tiny(NAME, tmp_path, trace=True, cell=tiny())
    assert out["correct"]
    got = set(out["metrics"])
    # the CPU has no TPU plane: the kernel's roofline is left out
    assert got == {"dataset_take_ms.rows", "miniblock_host_ms.rows",
                   "store_read_ms.take", "store_drain_ms.take",
                   "transfer_ms.take", "device_idle_pct.take"}
    assert out["metrics"]["dataset_take_ms.rows"]["value"] >= \
        out["metrics"]["miniblock_host_ms.rows"]["value"] > 0
    assert "decode.fallback counters: none moved" in lines


def test_altered_decode_is_not_correct(tmp_path, monkeypatch):
    from repro.kernels import ops

    orig = ops.miniblock_decode

    def altered(*args, **kwargs):
        rep, defs, vals = orig(*args, **kwargs)
        return rep, defs, vals + 1

    monkeypatch.setattr(ops, "miniblock_decode", altered)
    out, _ = run_tiny(NAME, tmp_path, cell=tiny())
    assert not out["correct"]
    assert out["checks"]["mismatched_rows"]["value"] > 0


def test_control_breaks_the_limit():
    cell = tiny()
    nums = control.readings(cell, SEED, 3)
    assert nums["mismatched_rows"] > cell.config["limits"]["mismatched_rows"]


def test_a_program_without_take_columns_fails_before_writing(monkeypatch):
    from repro.dataset import DatasetReader

    cell = tiny()
    data = cell.system.generate(cell.config, cell.traffic, SEED)
    monkeypatch.delattr(DatasetReader, "take_columns")
    with pytest.raises(RuntimeError, match="take_columns"):
        cell.system.build(cell.config, data, SEED)


def view():
    """One request: a two-leaf row take, read, decoded in one launch,
    selected, drained and assembled."""
    spans = [
        ("dataset.take_columns", 0.0, 10.0),
        ("dataset.locate", 0.0, 0.5),
        ("miniblock.take", 0.5, 2.0),
        ("miniblock.ranges", 0.5, 1.0),
        ("store.read", 1.0, 1.5),
        ("miniblock.parse", 1.5, 2.0),
        ("miniblock.decode_batch", 2.0, 6.0),
        ("miniblock.take", 2.0, 6.0),
        ("kernel.pack:miniblock_decode", 2.0, 3.0),
        ("kernel.h2d:miniblock_decode", 3.0, 3.5),
        ("kernel.launch:miniblock_decode", 3.5, 4.0),
        ("kernel.wait:miniblock_decode", 4.0, 4.5),
        ("kernel.d2h:miniblock_decode", 4.5, 5.0),
        ("kernel.unpack:miniblock_decode", 5.0, 6.0),
        ("miniblock.take", 6.0, 7.0),
        ("miniblock.select", 6.0, 7.0),
        ("drain:take:40-columns", 7.0, 8.5),
        ("dataset.assemble", 8.5, 10.0),
    ]
    return tr.TraceView((0.0, 10.0), spans, [("op", 3.5, 4.5)],
                        modules=[("jit_miniblock_decode_pallas", 3.5, 4.5)],
                        n_requests=1, work={}, peaks={"flops_s": 1e12,
                                                      "hbm_bytes_s": 1e9})


# milliseconds each metric reads from the view above, per request
EXPECTED_MS = {
    "dataset_take_ms.rows": 10e3,
    # ranges, parse, pack, unpack and select: 0.5 + 0.5 + 1 + 1 + 1
    "miniblock_host_ms.rows": 4e3,
    "store_read_ms.take": 0.5e3,
    "store_drain_ms.take": 1.5e3,
    "transfer_ms.take": 1e3,
}


@pytest.mark.parametrize("name", sorted(EXPECTED_MS))
def test_metric_reads_its_program_spans(name):
    cell = load_cell(NAME)
    metric = cell.metric_files[name]
    assert metric.read(view()) == pytest.approx(EXPECTED_MS[name])


@pytest.mark.parametrize("name", ["dataset_take_ms.rows",
                                  "miniblock_host_ms.rows"])
def test_metric_reads_nothing_from_a_program_without_its_spans(name):
    metric = load_cell(NAME).metric_files[name]
    assert metric.read(tr.TraceView((0.0, 1.0), [], [], n_requests=1)) \
        is None


def test_roofline_and_idle_share_read_the_device():
    cell = load_cell(NAME)
    v = view()
    assert cell.metric_files["device_idle_pct.take"].read(v) == \
        pytest.approx(90.0)
    roof = cell.metric_files["miniblock_decode_roofline.rows"]
    assert roof.read(v) is None  # no work recorded under its name
    v.work["miniblock_decode_roofline.rows"] = [(0.0, 5e8)]
    # 0.5 s at the peak bandwidth over 1 s of the kernel's program
    assert roof.read(v) == pytest.approx(50.0)
