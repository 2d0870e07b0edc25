"""Small cells for the benchmark's CPU tests: the real files under
``bench/`` with the sizes cut so that interpret-mode kernels finish in
seconds.  Imported by the ``test_bench_*`` files."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import control  # noqa: E402
import run  # noqa: E402
from harness.cell import load_cell  # noqa: E402

SEED = 2**31 + 12345  # above 32 signed bits, as a run's seed may be
CPU_PEAKS = {"flops_s": 1e12, "hbm_bytes_s": 1e11}


def tiny_cell(name: str):
    cell = load_cell(name)
    cfg, mix = cell.config, cell.traffic
    if cfg["system"] == "ivf":
        cfg.update(rows=3000, partitions=16, nprobe=4, modes=16)
        mix.update(query_pool=12, warmup={"quiet": 1, "max": 2, "postings": 4})
    else:
        cfg["rows"] = 4000
        cfg["columns"]["embedding"]["modes"] = 16
        mix.update(rows_per_request=64, warmup={"quiet": 2, "max": 4})
    return cell


def run_tiny(name: str, tmp_path, trace: bool = False, seconds: float = 0.5,
             cell=None):
    """One run of a tiny cell on the CPU; the device check is skipped by
    handing ``run.run`` the CPU device, and the CPU's stand-in peaks are
    used for the roofline arithmetic."""
    import jax

    cell = cell or tiny_cell(name)
    lines = []
    orig = run.peaks_for
    run.peaks_for = lambda kind: CPU_PEAKS
    try:
        out = run.run(cell, SEED, seconds, trace, jax.devices()[:1],
                      trace_dir=tmp_path / "trace", say=lines.append)
    finally:
        run.peaks_for = orig
    return out, lines
