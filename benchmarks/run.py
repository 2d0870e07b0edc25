"""Benchmark harness — one function per paper table/figure.

Emits ``name,us_per_call,derived`` CSV rows.  Three result tiers per
DESIGN.md §2.2: counted IOPS/bytes (exact), measured CPU wall-time (real),
modelled NVMe/S3 latency (paper Fig-1 device model applied to the counted
trace).  Dataset sizes are scaled down from the paper's 1 B rows to CPU
scale; rates are per-row so the comparisons carry.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig10 fig13
  PYTHONPATH=src python -m benchmarks.run --store tiered fig11

``--store {flat,tiered,flat-s3,hot}`` picks the storage stack every
benchmark reader is built on: ``flat`` is the seed behaviour (every read
priced on NVMe), ``tiered`` routes reads through the NVMe block cache over
S3 from ``repro.store``, ``flat-s3`` is the cold object store, ``hot`` adds
a RAM tier.  Under a non-flat stack the modelled column is priced with the
store's per-tier accounting (``FileReader.modelled_time``); counted IOPS
stay store-independent, and the measured (CPU) column includes the
simulator's block-classification overhead.  The ``store`` benchmark
reproduces the headline cold-S3 / NVMe-warm / flat-NVMe comparison
regardless of the flag; the ``dataset`` benchmark compares one shared NVMe
budget against per-file split stores over a fragmented dataset
(``BENCH_dataset.json``); the ``ingest`` benchmark compares write-back vs
write-through flush policies on append-heavy and mixed append/take ingest
into a live versioned dataset (``BENCH_ingest.json``).
"""

from __future__ import annotations

import json
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, "src")

from repro.core import arrays as A, types as T
from repro.core.file import FileReader, WriteOptions, write_table
from repro.core.io_sim import NVME, S3, model_time
from repro.data import synth
from repro.obs import Tracer, attribute
from repro.runtime import enable_compile_cache

ROWS = {"scalar": 200_000, "string": 100_000, "scalar-list": 50_000,
        "string-list": 30_000, "vector": 4_000, "vector-list": 1_500,
        "image": 800, "image-list": 300}
TAKE_N = 256  # one paper 'take' op

STORE_SPEC = "flat"  # set by --store; every benchmark reader is built on it
SMOKE = False  # set by --smoke; tiny row counts for CI
TRACER = None  # set by --trace PATH; threaded through every reader
TRACE_PATH = None


def _reader(file_bytes, **kw) -> FileReader:
    return FileReader(file_bytes, store=STORE_SPEC, tracer=TRACER, **kw)


def _emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.2f},{derived}")


def _git_sha():
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10).stdout.strip() or None
    except OSError:
        return None


def _run_meta() -> dict:
    """Run provenance stamped into every BENCH_*.json: without it the perf
    trajectory across PRs is a pile of unlabelled numbers.  The device says
    which clock a measured time was taken on."""
    import jax

    dev = jax.devices()[0]
    return {"git_sha": _git_sha(), "store": STORE_SPEC, "smoke": SMOKE,
            "traced": TRACER is not None, "platform": dev.platform,
            "device_kind": dev.device_kind, "device_count": jax.device_count(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}


def _dump_json(path: str, results: dict) -> None:
    """The single bench artifact write site: stamps run metadata and refuses
    NaN/Infinity (``allow_nan=False`` — non-standard JSON tokens used to
    leak in through empty-cache hit rates)."""
    results.setdefault("meta", {})["run"] = _run_meta()
    with open(path, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True, allow_nan=False)


def _take_bench(arr, opts, n_rows, repeats=3):
    fr = _reader(write_table({"c": arr}, opts))
    rng = np.random.default_rng(0)
    rows = rng.choice(n_rows, min(TAKE_N, n_rows), replace=False)
    fr.take("c", rows[:4])  # warm code paths
    fr.reset_io()
    t0 = time.perf_counter()
    for _ in range(repeats):
        fr.take("c", rows)
    dt = (time.perf_counter() - t0) / repeats
    st = fr.io_stats()
    st.n_iops //= repeats
    st.bytes_read //= repeats
    st.useful_bytes //= repeats
    if STORE_SPEC == "flat":
        t_model = model_time(st, NVME)
    else:
        # price the counted trace on the configured tier stack instead
        t_model = fr.modelled_time() / repeats
    rows_s = len(rows) / max(t_model, dt)  # disk- or cpu-bound, whichever binds
    return dt, st, t_model, rows_s, fr


def _scan_bench(arr, opts, repeats=3):
    fr = _reader(write_table({"c": arr}, opts))
    fr.scan("c")
    fr.reset_io()
    t0 = time.perf_counter()
    for _ in range(repeats):
        fr.scan("c")
    dt = (time.perf_counter() - t0) / repeats
    st = fr.io_stats()
    st.bytes_read //= repeats
    return dt, st, fr


# ---------------------------------------------------------------------------


def fig1_device_model():
    """Fig 1: device characteristics used by the model tier."""
    from repro.core.io_sim import IOStats

    for dev in (NVME, S3):
        for size in [4096, 64 * 1024, 1 << 20]:
            st = IOStats(n_iops=1000, bytes_read=1000 * size,
                         useful_bytes=1000 * size, max_phase=1)
            t = model_time(st, dev)
            _emit(f"fig1/{dev.name}/rand{size//1024}KiB", t / 1000 * 1e6,
                  f"iops={1000/t:.0f}")


def fig10_parquet_random_access():
    """Fig 10: Parquet random access across types + page-size sweep, and the
    §5 headline: optimized config is ~60x the default config."""
    for tname, n in ROWS.items():
        arr = synth.paper_type(tname, n, seed=1)
        dt, st, t_nvme, rows_s, _ = _take_bench(
            arr, WriteOptions("parquet", page_bytes=8192), n)
        _emit(f"fig10/parquet8k/{tname}", dt / TAKE_N * 1e6,
              f"rows_per_s={rows_s:.0f};iops_row={st.n_iops/TAKE_N:.2f};"
              f"amp={st.read_amplification:.1f}")
    # page size sweep on scalars (8KiB .. 1MiB 'default')
    arr = synth.paper_type("scalar", ROWS["scalar"], seed=1)
    base = None
    for ps in [8 << 10, 64 << 10, 256 << 10, 1 << 20]:
        dt, st, t_nvme, rows_s, _ = _take_bench(
            arr, WriteOptions("parquet", page_bytes=ps), ROWS["scalar"])
        if ps == 8 << 10:
            base = rows_s
        _emit(f"fig10/pagesize/{ps>>10}KiB", dt / TAKE_N * 1e6,
              f"rows_per_s={rows_s:.0f}")
    # the 60x claim: default (1MiB pages + dict, cold) vs optimized (8KiB)
    dt_d, st_d, t_d, rows_d, _ = _take_bench(
        arr, WriteOptions("parquet", page_bytes=1 << 20, dict_encode=True),
        ROWS["scalar"])
    _emit("fig10/default_vs_tuned", dt_d / TAKE_N * 1e6,
          f"speedup={base/rows_d:.0f}x;default_rows_s={rows_d:.0f};"
          f"tuned_rows_s={base:.0f}")
    # analytic extrapolation to the paper's 1B-row scale (no coalescing):
    # tuned = one 8KiB IOP/row; default = one 1MiB page + dict page per take
    t_tuned = max(1 / NVME.iops_4k, 8192 / NVME.seq_bw)
    t_default = (1 << 20) / NVME.seq_bw + (1 << 20) * 8 / NVME.seq_bw / TAKE_N
    _emit("fig10/default_vs_tuned_1Brow_model", 0.0,
          f"speedup={t_default/t_tuned:.0f}x;tuned_rows_s={1/t_tuned:.0f};"
          f"default_rows_s={1/t_default:.0f}")


def fig11_encodings_random_access():
    """Fig 11: Arrow-style vs Lance 2.1 (adaptive) random access + nesting."""
    for tname, n in ROWS.items():
        arr = synth.paper_type(tname, n, seed=1)
        for enc, opts in [("arrow", WriteOptions("arrow")),
                          ("lance", WriteOptions("lance"))]:
            dt, st, t_nvme, rows_s, fr = _take_bench(arr, opts, n)
            _emit(f"fig11/{enc}/{tname}", dt / TAKE_N * 1e6,
                  f"nvme_rows_per_s={TAKE_N/max(t_nvme,1e-9):.0f};"
                  f"iops_row={st.n_iops/TAKE_N:.2f};"
                  f"phases={st.max_phase};cache={fr.search_cache_bytes()}")
    # nesting depth: scalar wrapped in k list levels
    take_rows = np.arange(0, 2000, 97)
    for depth in [0, 1, 2, 3]:
        typ = T.int64()
        py = list(range(2000))
        for _ in range(depth):
            typ = T.List(typ)
            py = [[v] for v in py]
        arr = A.from_pylist(py, typ)
        for enc, opts in [("arrow", WriteOptions("arrow")),
                          ("lance-fullzip", WriteOptions("lance-fullzip"))]:
            fr = _reader(write_table({"c": arr}, opts))
            fr.reset_io()
            fr.take("c", take_rows)
            st = fr.io_stats()
            _emit(f"fig11/nesting{depth}/{enc}", 0.0,
                  f"iops_row={st.n_iops/len(take_rows):.2f};phases={st.max_phase}")


def fig12_fullzip_vs_miniblock():
    """Fig 12: full-zip is lighter-weight for random access at all sizes."""
    for width in [8, 32, 128, 512, 2048]:
        n = max(2_000, 200_000 * 8 // width)
        rng = np.random.default_rng(0)
        arr = A.FixedSizeListArray(
            T.FixedSizeList(T.Primitive("float32", nullable=False), width // 4),
            np.ones(n, bool),
            rng.standard_normal((n, width // 4)).astype(np.float32))
        for enc in ["lance-fullzip", "lance-miniblock"]:
            dt, st, t_nvme, rows_s, _ = _take_bench(arr, WriteOptions(enc), n)
            _emit(f"fig12/{enc}/{width}B", dt / TAKE_N * 1e6,
                  f"rows_per_s={rows_s:.0f};cpu_us_row={dt/TAKE_N*1e6:.1f};"
                  f"amp={st.read_amplification:.1f}")


def _lance_codec(sc):
    # the paper's table: names Dict+FSST, prompts/reviews FSST, dates bitpack,
    # code/images/websites LZ4(->zstd stand-in), embeddings none
    return {"names": "fsst_lite", "prompts": "fsst_lite", "reviews": "fsst_lite",
            "code": "zstd_chunk", "images": "zstd_chunk",
            "websites": "zstd_chunk"}.get(sc, "zstd_chunk")


def _raw_bytes(arr):
    if isinstance(arr, A.VarBinaryArray):
        return int(arr.offsets[-1]) + 8 * len(arr)
    if isinstance(arr, (A.FixedSizeListArray, A.PrimitiveArray)):
        return arr.values.nbytes
    if isinstance(arr, A.ListArray):
        return _raw_bytes(arr.child) + 8 * len(arr)
    raise TypeError(type(arr))


def fig13_compression():
    """Fig 13: Lance compresses like Parquet across the scenario corpus."""
    for sc in synth.SCENARIOS:
        n = 2_000 if sc in ("images", "websites", "code") else 20_000
        arr = synth.scenario(sc, n)
        raw = _raw_bytes(arr)
        for enc, opts in [
            ("parquet", WriteOptions("parquet", bytes_codec="zstd_chunk",
                                     dict_encode=sc == "names")),
            ("lance", WriteOptions("lance", bytes_codec="zstd_chunk")),
            ("lance-fsst", WriteOptions("lance", bytes_codec="fsst_lite")),
        ]:
            fr = _reader(write_table({"c": arr}, opts))
            ratio = raw / fr.data_bytes()
            _emit(f"fig13/{enc}/{sc}", 0.0,
                  f"ratio={ratio:.2f};disk_bytes={fr.data_bytes()}")


def fig14_16_full_scan():
    """Fig 14/16: scan throughput, Parquet vs Lance (values/s + disk MB/s)."""
    for sc in ["names", "prompts", "dates", "embeddings"]:
        n = 30_000 if sc != "embeddings" else 4_000
        arr = synth.scenario(sc, n)
        best = {}
        for enc, opts in [
            ("parquet", WriteOptions("parquet", bytes_codec="zstd_chunk")),
            ("lance", WriteOptions("lance", bytes_codec="zstd_chunk")),
        ]:
            dt, st, fr = _scan_bench(arr, opts)
            vals_s = n / dt
            disk_mbs = st.bytes_read / dt / 1e6
            best[enc] = vals_s
            _emit(f"fig16/{enc}/{sc}", dt * 1e6,
                  f"vals_per_s={vals_s:.0f};disk_MBps={disk_mbs:.0f}")
        _emit(f"fig16/normalized/{sc}", 0.0,
              f"lance_over_parquet={best['lance']/best['parquet']:.2f}")


def fig17_scan_decode_cost():
    """Fig 17: mini-block scan decode is vectorized; full-zip unzips
    per-value (CPU-bound)."""
    n = 60_000
    rng = np.random.default_rng(0)
    vals = [bytes(rng.integers(97, 123, 16, dtype=np.uint8)) for _ in range(n)]
    arr = A.VarBinaryArray.build(vals, utf8=True)
    per_val = {}
    for enc in ["lance-miniblock", "lance-fullzip"]:
        dt, st, fr = _scan_bench(arr, WriteOptions(enc), repeats=2)
        per_val[enc] = dt / n * 1e6
        _emit(f"fig17/{enc}/string16B", dt / n * 1e6, f"vals_per_s={n/dt:.0f}")
    _emit("fig17/miniblock_advantage", 0.0,
          f"fullzip_over_miniblock={per_val['lance-fullzip']/per_val['lance-miniblock']:.1f}x")


def fig18_struct_packing():
    """Fig 18: packed structs trade single-field scan for whole-struct take."""
    n = 30_000
    rng = np.random.default_rng(0)
    for k in [2, 3, 4, 5]:
        children = [(f"f{i}", A.PrimitiveArray.build(
            rng.integers(0, 1 << 40, n).astype(np.int64), nullable=False))
            for i in range(k)]
        arr = A.StructArray.build(children, nullable=False)
        rows = rng.choice(n, TAKE_N, replace=False)
        fr = _reader(write_table({"s": arr},
                                  WriteOptions("lance", packed_columns=("s",))))
        fr.reset_io()
        t0 = time.perf_counter()
        fr.take("s", rows)
        dt_p = time.perf_counter() - t0
        st = fr.io_stats()
        t_take_packed = max(model_time(st, NVME), dt_p)
        fr.reset_io()
        t0 = time.perf_counter()
        fr.scan_packed_field("s", ["f0"])
        dt_scan_p = time.perf_counter() - t0
        fr2 = _reader(write_table({"s": arr}, WriteOptions("lance")))
        fr2.reset_io()
        t0 = time.perf_counter()
        fr2.take("s", rows)
        dt_s = time.perf_counter() - t0
        st2 = fr2.io_stats()
        t_take_shred = max(model_time(st2, NVME), dt_s)
        _emit(f"fig18/fields{k}", dt_p * 1e6,
              f"take_rows_s_packed={TAKE_N/t_take_packed:.0f};"
              f"take_rows_s_shredded={TAKE_N/t_take_shred:.0f};"
              f"iops_packed={st.n_iops};iops_shredded={st2.n_iops};"
              f"scan1field_us={dt_scan_p*1e6:.0f}")


def store_tiering():
    """The tiered-store headline: a take-heavy random-access workload priced
    cold from S3, through an NVMe block cache (cold fill then warm hits),
    and on bare NVMe.  The modelled NVMe-warm time must beat cold S3."""
    from repro.store import TieredStore

    n = ROWS["vector"]
    arr = synth.paper_type("vector", n, seed=1)
    fb = write_table({"c": arr}, WriteOptions("lance"))
    rng = np.random.default_rng(0)
    rows = rng.choice(n, TAKE_N, replace=False)

    fr_s3 = FileReader(fb, store="flat-s3")
    fr_s3.take("c", rows)
    t_cold_s3 = fr_s3.modelled_time()
    _emit("store/cold_s3", t_cold_s3 * 1e6,
          f"rows_per_s={TAKE_N/t_cold_s3:.0f}")

    fr = FileReader(fb, store="tiered")
    fr.take("c", rows)
    t_fill = fr.modelled_time()
    miss_stats = {s.name: s for s in fr.tier_stats()}
    _emit("store/tiered_fill", t_fill * 1e6,
          f"rows_per_s={TAKE_N/t_fill:.0f};"
          f"s3_iops={miss_stats['s3'].n_iops}")
    fr.reset_io()
    fr.take("c", rows)
    t_warm = fr.modelled_time()
    warm = {s.name: s for s in fr.tier_stats()}
    nv = warm["nvme_970evo"]
    _emit("store/tiered_warm", t_warm * 1e6,
          f"rows_per_s={TAKE_N/t_warm:.0f};hit_rate={nv.hit_rate:.2f};"
          f"s3_iops={warm['s3'].n_iops}")

    fr_nvme = FileReader(fb)  # flat NVMe
    fr_nvme.take("c", rows)
    t_nvme = fr_nvme.modelled_time()
    _emit("store/flat_nvme", t_nvme * 1e6, f"rows_per_s={TAKE_N/t_nvme:.0f}")

    assert t_warm < t_cold_s3, "NVMe-warm tiered take must beat cold S3"
    _emit("store/warm_over_cold", 0.0,
          f"speedup={t_cold_s3/t_warm:.0f}x;warm_lt_cold={t_warm < t_cold_s3}")

    # capacity-pressured cache: working set larger than the cache forces
    # evictions; hit rate and speedup degrade gracefully
    fr_small = FileReader(fb, store=lambda d: TieredStore.cached(d, cache_bytes=1 << 20))
    for _ in range(2):
        fr_small.take("c", rows)
    ev = {s.name: s for s in fr_small.tier_stats()}["nvme_970evo"]
    _emit("store/tiered_1MiB_cache", fr_small.modelled_time() * 1e6,
          f"hit_rate={ev.hit_rate:.2f};evictions={ev.evictions}")


def take_decode():
    """Random-access hot path trajectory: rows/s and the decode-vs-IO time
    split for the batched take pipeline (mini-block + full-zip) at
    1k/10k/100k random row ids (with duplicates, as a serving workload
    would).  Wall time is decode/orchestration CPU (IO is simulated);
    modelled IO prices the counted trace on the device model.  Results are
    written to BENCH_take.json so future PRs can track the hot path."""
    counts = [64, 256] if SMOKE else [1_000, 10_000, 100_000]
    n = 20_000 if SMOKE else 200_000
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 1 << 20, n).astype(np.int64)
    validity = rng.random(n) > 0.03
    mb = A.PrimitiveArray.build(vals, validity=validity)
    fz = A.FixedSizeListArray(
        T.FixedSizeList(T.Primitive("float32", nullable=False), 32),
        np.ones(n, bool), rng.standard_normal((n, 32)).astype(np.float32))
    # pre-PR reader throughput on these exact datasets/seed (per-row decode
    # loops, measured before the batched pipeline landed) — the trajectory's
    # fixed origin for the >=5x acceptance gate
    baseline = {"miniblock": {"1000": 25780, "10000": 29956},
                "fullzip": {"1000": 48117, "10000": 45494}}
    results = {"meta": {"n_rows": n, "smoke": SMOKE, "store": STORE_SPEC,
                        "row_counts": counts,
                        "baseline_note": "pre-PR rows/s measured on the "
                                         "per-row-loop reader (PR 2 seed)"},
               "pre_pr_baseline": baseline}
    for name, arr, opts in [
        ("miniblock", mb, WriteOptions("lance-miniblock")),
        ("fullzip", fz, WriteOptions("lance-fullzip")),
    ]:
        fr = _reader(write_table({"c": arr}, opts))
        results[name] = {}
        for k in counts:
            rows = rng.integers(0, n, k)
            fr.take("c", rows)  # warm code paths (decode is never cached)
            fr.reset_io()
            t0 = time.perf_counter()
            fr.take("c", rows)
            dt = time.perf_counter() - t0
            st = fr.io_stats()
            if STORE_SPEC == "flat":
                t_io = model_time(st, NVME)
            else:
                t_io = fr.modelled_time()
            rows_s = k / max(dt, t_io)
            cell = {"rows_per_s": round(rows_s), "cpu_decode_s": round(dt, 6),
                    "model_io_s": round(t_io, 6), "n_iops": st.n_iops,
                    "bytes_read": st.bytes_read,
                    "read_amplification": round(st.read_amplification, 3)}
            base = baseline.get(name, {}).get(str(k))
            if base:
                cell["speedup_vs_pre_pr"] = round(rows_s / base, 2)
            results[name][str(k)] = cell
            _emit(f"take_decode/{name}/{k}", dt * 1e6,
                  f"rows_per_s={rows_s:.0f};cpu_decode_s={dt:.4f};"
                  f"model_io_s={t_io:.4f};iops={st.n_iops}"
                  + (f";speedup={rows_s / base:.1f}x" if base else ""))
        fr.drop_caches()
    # variable-width cases (utf8 + nested list): the Fig-17 decode cost the
    # fixed-stride cells above cannot see.  A separate rng keeps the cells
    # above bit-identical to their historical draws.
    rng2 = np.random.default_rng(7)
    n2 = 20_000 if SMOKE else 200_000
    utf8 = _var_utf8(rng2, n2)
    nested = _nested_utf8(rng2, n2 // 4)
    for name, arr, nn in [("fullzip-utf8", utf8, n2),
                          ("fullzip-list", nested, n2 // 4)]:
        fr = _reader(write_table({"c": arr}, WriteOptions("lance-fullzip")))
        results[name] = {}
        for k in counts:
            rows = rng2.integers(0, nn, k)
            fr.take("c", rows)
            fr.reset_io()
            t0 = time.perf_counter()
            fr.take("c", rows)
            dt = time.perf_counter() - t0
            st = fr.io_stats()
            t_io = model_time(st, NVME) if STORE_SPEC == "flat" else fr.modelled_time()
            results[name][str(k)] = {
                "rows_per_s": round(k / max(dt, t_io)),
                "cpu_decode_s": round(dt, 6), "model_io_s": round(t_io, 6),
                "n_iops": st.n_iops, "bytes_read": st.bytes_read,
                "read_amplification": round(st.read_amplification, 3)}
            _emit(f"take_decode/{name}/{k}", dt * 1e6,
                  f"rows_per_s={k / max(dt, t_io):.0f};iops={st.n_iops}")
        fr.drop_caches()
    results["serving_latency"] = _serving_latency_cell(mb)
    results["pallas_fallback_probe"] = _pallas_fallback_probe(rng)
    _dump_json("BENCH_take.json", results)
    _emit("take_decode/written", 0.0, "path=BENCH_take.json")


def _serving_latency_cell(arr) -> dict:
    """Per-request latency attribution over a stream of small takes against
    the tiered store: every queue drain's modelled cost is decomposed onto
    the rows it served (repro.obs.attrib), giving the p50/p99/p999 a serving
    SLO actually cares about — the mean hides the cold-tier tail entirely.
    Deterministic (counted traces x device constants), so bench_gate can
    diff the percentiles exactly."""
    n_req, rows_per_req = (32, 16) if SMOKE else (256, 32)
    rng3 = np.random.default_rng(11)
    fr = FileReader(write_table({"c": arr}, WriteOptions("lance-miniblock")),
                    store="tiered", tracer=TRACER)
    n = len(arr)
    t0 = time.perf_counter()
    for _ in range(n_req):
        fr.take("c", rng3.integers(0, n, rows_per_req))
    dt = time.perf_counter() - t0
    att = attribute(fr.store, queue_depth=fr.scheduler.queue_depth)
    # the acceptance invariant: attributed per-tier sums reproduce each
    # tier's model_time to float exactness (residual is reported, not hidden)
    residual = 0.0
    sums = att.tier_sums()
    devices = [lvl.device for lvl in fr.store.levels] + [fr.store.backing]
    for stats, dev in zip(fr.store.tier_stats(), devices):
        mt = stats.model_time(dev, fr.scheduler.queue_depth)
        if mt > 0:
            residual = max(residual, abs(sums.get(stats.name, 0.0) - mt) / mt)
    # each take declared len(rows) logical requests, so the attributed
    # per-request latency is already per-row
    pct = att.percentiles("take:c") or {}
    per_row = {k: round(v * 1e6, 4) for k, v in pct.items() if k != "count"}
    cell = {"n_takes": n_req, "rows_per_take": rows_per_req, "store": "tiered",
            "per_row_us": per_row, "n_attributed_requests": pct.get("count"),
            "attribution_residual_rel": residual,
            "model_total_s": round(att.total, 6),
            "cpu_wall_s": round(dt, 6)}
    _emit("take_decode/serving_latency", dt * 1e6,
          f"p50_us={per_row.get('p50')};p99_us={per_row.get('p99')};"
          f"p999_us={per_row.get('p999')};residual={residual:.2e}")
    return cell


def _pallas_fallback_probe(rng) -> dict:
    """Force the kernel route off the Pallas path (float values are VPU-only
    in the mini-block gather kernel) and report the structured fallback
    reasons the tracer counted.  Runs against the session tracer when
    --trace is set so the exported Chrome trace carries the instant events;
    otherwise a local tracer keeps the probe self-contained."""
    tr = TRACER if TRACER is not None else Tracer()
    n = 4_096
    arr = A.PrimitiveArray.build(rng.standard_normal(n).astype(np.float32))
    fr = FileReader(write_table({"c": arr}, WriteOptions("lance-miniblock")),
                    store=STORE_SPEC, decode="pallas", tracer=tr)
    fr.take("c", rng.integers(0, n, 64))
    reasons = tr.metrics.counter_values("decode.fallback")
    n_events = sum(1 for e in tr.events
                   if e.get("name") == "pallas_fallback")
    cell = {"reasons": reasons, "n_events": n_events}
    _emit("take_decode/pallas_fallback_probe", 0.0,
          f"n_events={n_events};reasons={len(reasons)}")
    return cell


def _var_utf8(rng, n: int) -> A.VarBinaryArray:
    """Flat utf8, ~16 B average values, 3% nulls — the Fig-17 shape shared
    by the ``take_decode`` variable-width cells and the ``decode`` headline
    (and its embedded pre-PR baseline)."""
    lens = rng.integers(4, 28, n)
    validity = rng.random(n) > 0.03
    kept = np.where(validity, lens, 0)  # nulls occupy no bytes
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(kept, out=offs[1:])
    return A.VarBinaryArray(T.Utf8(True), validity, offs,
                            rng.integers(97, 123, int(offs[-1]), dtype=np.uint8))


def _nested_utf8(rng, n_rows: int) -> A.ListArray:
    """list<utf8> rows (0-8 strings of 2-16 B, null lists and null items):
    variable-width entries behind a repetition index — the shape where the
    per-value walk was the Fig-17 bottleneck for nested data."""
    lvalid = rng.random(n_rows) > 0.05
    lens_l = np.where(lvalid, rng.integers(0, 8, n_rows), 0)
    loffs = np.zeros(n_rows + 1, np.int64)
    np.cumsum(lens_l, out=loffs[1:])
    n_child = int(loffs[-1])
    cvalid = rng.random(n_child) > 0.05
    ckept = np.where(cvalid, rng.integers(2, 16, n_child), 0)
    coffs = np.zeros(n_child + 1, np.int64)
    np.cumsum(ckept, out=coffs[1:])
    child = A.VarBinaryArray(
        T.Utf8(True), cvalid, coffs,
        rng.integers(97, 123, int(coffs[-1]), dtype=np.uint8))
    return A.ListArray.build(child, loffs, validity=lvalid)


def decode_bench():
    """The row-parallel full-zip decode headline (BENCH_decode.json).

    Variable-width full-zip random access is CPU-bound on decode (the
    paper's §6.3/Fig-17 cost): entry positions depend on embedded lengths.
    This benchmark times the row-parallel frontier decode against the
    retained per-value walk (``FullZipReader._decode_entries_walk`` — the
    exact pre-PR decode loop) on the same fetched spans, so the speedup is a
    like-for-like decode comparison, plus the end-to-end take and scan.
    The embedded ``pre_pr_take_baseline`` numbers are full-take rows/s
    measured on the per-value-walk reader immediately before this PR landed
    (same machine, same dataset shapes) — the trajectory's fixed origin.
    """
    counts = [256, 1_024] if SMOKE else [1_000, 10_000]
    n = 20_000 if SMOKE else 200_000
    rng = np.random.default_rng(0)
    utf8 = _var_utf8(rng, n)
    # nested list<utf8>: multi-entry variable-width rows exercise the
    # frontier depth (one vectorized step per entry-per-row)
    n_l = n // 4
    nested = _nested_utf8(rng, n_l)
    # pre-PR full-take rows/s on these exact datasets/seed (per-value-walk
    # reader at the PR-3 tip, flat NVMe store)
    baseline = {"utf8": {"1000": 119618, "10000": 120030},
                "list": {"1000": 68143, "10000": 59884}}
    results = {"meta": {"n_rows": n, "smoke": SMOKE, "store": STORE_SPEC,
                        "row_counts": counts,
                        "baseline_note": "pre-PR full-take rows/s measured on "
                                         "the per-value-walk reader"},
               "pre_pr_take_baseline": baseline}
    import repro.core.fullzip as _fz

    for name, arr, nn in [("utf8", utf8, n), ("list", nested, n_l)]:
        fr = _reader(write_table({"c": arr}, WriteOptions("lance-fullzip")))
        reader = fr._leaf_readers("c")[0]
        m = reader.meta
        results[name] = {}
        for k in counts:
            rows = rng.integers(0, nn, k)
            fr.take("c", rows)  # warm code paths (decode is never cached)
            fr.reset_io()
            t0 = time.perf_counter()
            fr.take("c", rows)
            dt = time.perf_counter() - t0
            st = fr.io_stats()
            t_io = model_time(st, NVME) if STORE_SPEC == "flat" else fr.modelled_time()
            # isolated decode: fetch the unique-row spans once, then time the
            # row-parallel frontier vs the retained per-value walk on the
            # exact same concatenated bytes
            urows = np.unique(rows)
            R = m["R"]
            with fr.scheduler.batch("decode-bench") as io:
                idx, _ = io.read_many(reader.base + urows * R,
                                      np.full(len(urows), 2 * R, np.int64))
                mat = idx.reshape(len(urows), 2 * R)
                lo = _fz._from_le(mat[:, :R]).astype(np.int64)
                hi = _fz._from_le(mat[:, R:]).astype(np.int64)
                spans, _ = io.read_many(
                    reader.base + m["zip_base"] + lo, hi - lo, phase=1)
            seg = np.zeros(len(urows) + 1, np.int64)
            np.cumsum(hi - lo, out=seg[1:])

            def timeit(fn, reps=3):
                fn()
                t0 = time.perf_counter()
                for _ in range(reps):
                    fn()
                return (time.perf_counter() - t0) / reps

            t_new = timeit(lambda: reader._decode_entries(spans, seg_offs=seg))
            t_walk = timeit(lambda: reader._decode_entries_walk(spans))
            cell = {"rows_per_s": round(k / max(dt, t_io)),
                    "cpu_take_s": round(dt, 6), "model_io_s": round(t_io, 6),
                    "n_iops": st.n_iops, "bytes_read": st.bytes_read,
                    "decode_rows_per_s": round(len(urows) / t_new),
                    "walk_rows_per_s": round(len(urows) / t_walk),
                    "decode_speedup_vs_walk": round(t_walk / t_new, 2)}
            base = baseline.get(name, {}).get(str(k))
            if base and not SMOKE:
                cell["take_speedup_vs_pre_pr"] = round(k / max(dt, t_io) / base, 2)
            results[name][str(k)] = cell
            _emit(f"decode/{name}/{k}", dt * 1e6,
                  f"rows_per_s={k / max(dt, t_io):.0f};"
                  f"decode_speedup_vs_walk={t_walk / t_new:.1f}x;"
                  f"iops={st.n_iops}")
        # scan: windowed row-parallel decode vs the walk on the whole column
        fr.scan("c")
        t0 = time.perf_counter()
        fr.scan("c")
        t_scan = time.perf_counter() - t0
        raw = fr.disk.read(reader.base + m["zip_base"], m["zip_bytes"])
        t_walk = time.perf_counter()
        reader._decode_entries_walk(raw, n_hint=m["n_entries"])
        t_walk = time.perf_counter() - t_walk
        results[name]["scan"] = {
            "vals_per_s": round(nn / t_scan),
            "walk_decode_s": round(t_walk, 6), "scan_s": round(t_scan, 6)}
        _emit(f"decode/{name}/scan", t_scan * 1e6,
              f"vals_per_s={nn / t_scan:.0f};walk_decode_s={t_walk:.4f}")
        fr.drop_caches()
    # fused gather route: fixed-stride take through kernels.fullzip_gather
    # (interpret mode on CPU — parity is the point, wall time is not TPU time)
    fz = A.FixedSizeListArray(
        T.FixedSizeList(T.Primitive("float32", nullable=False), 32),
        np.ones(2000, bool),
        rng.standard_normal((2000, 32)).astype(np.float32))
    fb = write_table({"c": fz}, WriteOptions("lance-fullzip"))
    rows = rng.integers(0, 2000, 64 if SMOKE else 1000)
    got_np = _reader(fb, decode="numpy").take("c", rows)
    got_pl = _reader(fb, decode="pallas").take("c", rows)
    gather_ok = bool(np.array_equal(got_np.values, got_pl.values)
                     and np.array_equal(got_np.validity, got_pl.validity))
    results["gather_route"] = {"pallas_bit_identical": gather_ok}
    _emit("decode/gather_route", 0.0, f"pallas_bit_identical={gather_ok}")
    assert gather_ok, "pallas gather route must match the host permutation"
    # the acceptance gate: decode rows/s on the largest variable-width take
    # vs the per-value walk on identical bytes.  (End-to-end take rows/s is
    # additionally capped by the NVMe IO model — ~23 ms for 10k 2-IOP rows —
    # so the decode-vs-walk ratio is the term this PR moves; the per-cell
    # take_speedup_vs_pre_pr tracks the end-to-end trajectory.)  Smoke mode
    # gates a relaxed threshold: tiny takes amortize vectorization worse.
    floor = 2 if SMOKE else 5
    sp = results["utf8"][str(counts[-1])]["decode_speedup_vs_walk"]
    results["headline"] = {
        "gate": f"utf8/{counts[-1]} decode_speedup_vs_walk >= {floor}",
        "decode_speedup_vs_walk": sp,
        "note": "walk = retained pre-PR per-value decode loop "
                "(_decode_entries_walk) timed on the same fetched spans",
    }
    assert sp >= floor, f"row-parallel decode must be >={floor}x the walk, got {sp}x"
    _dump_json("BENCH_decode.json", results)
    _emit("decode/written", 0.0, "path=BENCH_decode.json")


def dataset_take():
    """The multi-file headline: an 8-fragment dataset served take-heavy with
    a *skewed* (hot-fragment) row mix, under one shared NVMe budget vs the
    same budget statically split into per-file stores.  The shared store
    arbitrates the whole budget toward the hot fragments and coalesces
    cross-file spans in one dispatch per phase, so it must win on rows/s and
    on second-pass NVMe hit rate.  Results go to BENCH_dataset.json."""
    from repro.dataset import DatasetReader, write_fragments
    from repro.store import TieredStore

    n_frag = 4 if SMOKE else 8
    per_frag = 1_000 if SMOKE else 6_000
    take_n = 1_200 if SMOKE else 10_000
    n_hot = 2          # fragments receiving the bulk of the traffic
    hot_frac = 0.85
    width = 512        # float32 lanes -> 2 KiB embedding rows (~2 per block)
    n = n_frag * per_frag
    rng = np.random.default_rng(0)
    arr = A.FixedSizeListArray(
        T.FixedSizeList(T.Primitive("float32", nullable=False), width),
        np.ones(n, bool), rng.standard_normal((n, width)).astype(np.float32))
    files = write_fragments({"c": arr}, n_frag, WriteOptions("lance-fullzip"))
    payload = sum(len(f) for f in files)
    # one NVMe budget, sized to hold the hot fragments but not the dataset
    budget = int(1.25 * n_hot * payload / n_frag)
    row_starts = np.arange(n_frag, dtype=np.int64) * per_frag

    def skewed_rows():
        hot = rng.integers(0, n_hot * per_frag, int(take_n * hot_frac))
        cold = rng.integers(0, n, take_n - len(hot))
        return np.concatenate([hot, cold])

    # one row draw per pass, replayed for BOTH configurations, so the
    # shared-vs-split comparison is over identical requests
    pass_rows = [skewed_rows(), skewed_rows()]

    def one_pass(take_fn, readers, rows):
        for r in readers:
            r.reset_io()
        t0 = time.perf_counter()
        take_fn(rows)
        dt = time.perf_counter() - t0
        t_model = sum(r.modelled_time() for r in readers)
        tiers = [s for r in readers for s in r.tier_stats()]
        nvme = [s for s in tiers if s.name == "nvme_970evo"]
        s3 = [s for s in tiers if s.name == "s3"]
        hits, misses = sum(s.hits for s in nvme), sum(s.misses for s in nvme)
        return {
            "rows_per_s": round(take_n / max(dt, t_model)),
            "cpu_s": round(dt, 6), "model_io_s": round(t_model, 6),
            "nvme_hit_rate": round(hits / max(hits + misses, 1), 4),
            "s3_iops": sum(s.n_iops for s in s3),
            "nvme_iops": sum(s.n_iops for s in nvme),
        }

    # shared: the whole dataset behind one cache + scheduler
    shared = DatasetReader(
        files, store=lambda d: TieredStore.cached(d, cache_bytes=budget),
        tracer=TRACER)
    shared_res = {f"pass{i + 1}": one_pass(
        lambda rows: shared.take("c", rows), [shared], pass_rows[i])
        for i in range(2)}

    # per-file: the seed world — N disjoint stores, budget split N ways
    per_file = [
        FileReader(fb, store=lambda d: TieredStore.cached(
            d, cache_bytes=max(budget // n_frag, 4096)))
        for fb in files
    ]

    def per_file_take(rows):
        fi = np.searchsorted(row_starts, rows, side="right") - 1
        for f in np.unique(fi):
            per_file[f].take("c", rows[fi == f] - row_starts[f])

    per_file_res = {f"pass{i + 1}": one_pass(per_file_take, per_file,
                                             pass_rows[i])
                    for i in range(2)}

    results = {
        "meta": {"n_fragments": n_frag, "rows_per_fragment": per_frag,
                 "take_n": take_n, "hot_fragments": n_hot,
                 "hot_fraction": hot_frac, "row_bytes": 4 * width,
                 "payload_bytes": payload, "nvme_budget_bytes": budget,
                 "smoke": SMOKE},
        "shared_store": shared_res,
        "per_file_store": per_file_res,
        "headline": {
            "rows_s_speedup_pass2": round(
                shared_res["pass2"]["rows_per_s"]
                / max(per_file_res["pass2"]["rows_per_s"], 1), 2),
            "s3_iops_saved_pass2": per_file_res["pass2"]["s3_iops"]
            - shared_res["pass2"]["s3_iops"],
        },
    }
    _dump_json("BENCH_dataset.json", results)
    for kind, res in [("shared", shared_res), ("per_file", per_file_res)]:
        for p, cell in res.items():
            _emit(f"dataset/{kind}/{p}", cell["cpu_s"] * 1e6,
                  f"rows_per_s={cell['rows_per_s']};"
                  f"hit_rate={cell['nvme_hit_rate']};s3_iops={cell['s3_iops']}")
    _emit("dataset/headline", 0.0,
          f"speedup_pass2={results['headline']['rows_s_speedup_pass2']}x;"
          f"s3_iops_saved={results['headline']['s3_iops_saved_pass2']};"
          "path=BENCH_dataset.json")
    assert shared_res["pass2"]["rows_per_s"] >= per_file_res["pass2"]["rows_per_s"], \
        "shared store must serve at least per-file rows/s"
    assert shared_res["pass2"]["nvme_hit_rate"] > per_file_res["pass2"]["nvme_hit_rate"], \
        "shared store must warm better than split per-file budgets"


def ingest_bench():
    """The write-path headline (BENCH_ingest.json): append-heavy and mixed
    append/take ingest into a live dataset, write-back vs write-through
    flush under the same NVMe budget.

    Every config appends the same fragments and (in the mixed workload)
    takes the same random rows, committing every ``commit_every`` appends.
    Write-through pays one backing (S3) queue drain per append; write-back
    absorbs appends into the NVMe tier dirty and batches the S3 writes at
    the commit fence / watermark / deadline — same bytes eventually written,
    far fewer S3 round trips, with the bytes-at-risk (``dirty_bytes`` /
    crash-``lost_bytes``) accounting making the durability trade explicit.
    The gate: write-back must beat write-through on mixed append/take
    NVMe-warm throughput (modelled, same budget)."""
    from repro.dataset import DatasetWriter
    from repro.store import TieredStore

    n_appends = 6 if SMOKE else 24
    rows_per = 400 if SMOKE else 2_000
    take_n = 200 if SMOKE else 1_000
    commit_every = 3
    width = 64  # float32 lanes -> 256 B rows
    n_total = n_appends * rows_per
    budget = max(int(1.5 * n_total * width * 4), 1 << 20)

    def run_config(policy, workload):
        rng = np.random.default_rng(0)  # same draws for every config
        w = DatasetWriter(
            store=lambda d: TieredStore.cached(d, cache_bytes=budget),
            flush=policy, opts=WriteOptions("lance-fullzip"), tracer=TRACER)
        n_ops = n_total
        t0 = time.perf_counter()
        for i in range(n_appends):
            vals = rng.standard_normal((rows_per, width)).astype(np.float32)
            arr = A.FixedSizeListArray(
                T.FixedSizeList(T.Primitive("float32", nullable=False), width),
                np.ones(rows_per, bool), vals)
            w.append({"c": arr}, commit=(i + 1) % commit_every == 0)
            if workload == "mixed" and w.version:
                rows = rng.integers(0, w.n_rows, take_n)
                w.take("c", rows)
                n_ops += take_n
        w.commit()
        dt = time.perf_counter() - t0
        t_model = w.modelled_time()
        tiers = {s.name: s for s in w.tier_stats()}
        s3, nvme = tiers["s3"], tiers["nvme_970evo"]
        return {
            "rows_per_s": round(n_ops / max(dt, t_model)),
            "cpu_s": round(dt, 6), "model_io_s": round(t_model, 6),
            "s3_write_iops": s3.write_iops, "s3_flush_iops": s3.flush_iops,
            "s3_bytes_written": s3.bytes_written,
            "s3_read_iops": s3.n_iops,
            "nvme_write_iops": nvme.write_iops,
            "nvme_hit_rate": round(nvme.hit_rate, 4)
            if nvme.hits + nvme.misses else None,
            "peak_dirty_after_run": nvme.dirty_bytes,
            "logical_write_iops": w.write_stats().n_iops,
            "logical_write_bytes": w.write_stats().bytes_read,
        }

    results = {"meta": {"n_appends": n_appends, "rows_per_append": rows_per,
                        "take_n": take_n, "commit_every": commit_every,
                        "row_bytes": width * 4, "nvme_budget_bytes": budget,
                        "smoke": SMOKE}}
    for workload in ("append", "mixed"):
        for policy in ("write-through", "write-back"):
            cell = run_config(policy, workload)
            results[f"{workload}/{policy}"] = cell
            _emit(f"ingest/{workload}/{policy}", cell["cpu_s"] * 1e6,
                  f"rows_per_s={cell['rows_per_s']};"
                  f"s3_write_iops={cell['s3_write_iops']};"
                  f"model_io_s={cell['model_io_s']}")
    wb, wt = results["mixed/write-back"], results["mixed/write-through"]
    results["headline"] = {
        "gate": "mixed write-back rows_per_s > mixed write-through",
        "mixed_speedup": round(wb["rows_per_s"] / max(wt["rows_per_s"], 1), 2),
        "append_speedup": round(
            results["append/write-back"]["rows_per_s"]
            / max(results["append/write-through"]["rows_per_s"], 1), 2),
        "s3_write_iops_saved_mixed": wt["s3_write_iops"] - wb["s3_write_iops"],
    }
    _emit("ingest/headline", 0.0,
          f"mixed_speedup={results['headline']['mixed_speedup']}x;"
          f"append_speedup={results['headline']['append_speedup']}x;"
          "path=BENCH_ingest.json")
    assert wb["rows_per_s"] > wt["rows_per_s"], \
        "write-back must beat write-through on mixed append/take throughput"
    _dump_json("BENCH_ingest.json", results)
    _emit("ingest/written", 0.0, "path=BENCH_ingest.json")


def serve_bench():
    """Multi-tenant serving headline (BENCH_serve.json): Zipf-skewed
    concurrent takers + a write-back ingest tenant over one shared tiered
    store, priced by the scheduler's event-loop serving plane.

    The same executed workload (identical classification, cache state and
    per-tier accounting — both timings are pure overlays on the drain log)
    is priced under interleaved event-loop dispatch and under the old
    serial batch-drain; the gate asserts interleaved wins on p99
    per-request latency.  Tenants carry QoS weights (premium 4x standard)
    and the ingest tenant's append/flush drains share the device queues
    with the reads — the flush-vs-concurrent-reads interleaving the
    event loop exists to fix.  Per-row latency attribution
    (repro.obs.attribute) runs over the same trace with reads and flushes
    in flight together; its per-tier residual against model_time is
    reported, not hidden."""
    from repro.core.io_sim import Degradation
    from repro.dataset import DatasetWriter
    from repro.obs import (NULL_TRACER, BurnWindow, MetricsPlane,
                           SLOMonitor)
    from repro.serve.workload import (TenantSpec, ZipfWorkload, drive,
                                      tenant_summary)
    from repro.store import EventLoop, TieredStore

    n_frag = 4 if SMOKE else 8
    rows_per = 1_000 if SMOKE else 6_000
    n_requests = 96 if SMOKE else 1_500
    arrival_rate = 200.0       # requests per virtual second
    width = 32                 # float32 lanes -> 128 B rows
    qd = 32                    # shallow queue: concurrency must share rounds
    n_total = n_frag * rows_per
    # cache holds ~half the data: the Zipf head goes NVMe-warm, the tail
    # keeps paying S3 round trips — the serving tail the percentiles see
    budget = max(int(0.5 * n_total * width * 4), 1 << 18)

    def table(rng, n):
        vals = rng.standard_normal((n, width)).astype(np.float32)
        arr = A.FixedSizeListArray(
            T.FixedSizeList(T.Primitive("float32", nullable=False), width),
            np.ones(n, bool), vals)
        return {"c": arr}

    rng = np.random.default_rng(7)
    seeds = [write_table(table(rng, rows_per), WriteOptions("lance-fullzip"))
             for _ in range(n_frag)]
    w = DatasetWriter(
        files=seeds,
        store=lambda d: TieredStore.cached(d, cache_bytes=budget),
        flush="write-back", opts=WriteOptions("lance-fullzip"),
        queue_depth=qd, tracer=TRACER)

    tenants = [
        TenantSpec("premium", share=1.0, weight=4.0, rows_per_request=32),
        TenantSpec("standard", share=2.0, weight=1.0, rows_per_request=32),
    ]
    wl = ZipfWorkload(n_rows=w.n_rows, tenants=tenants,
                      n_requests=n_requests, zipf_s=1.05,
                      arrival_rate=arrival_rate, seed=3)
    reqs = wl.generate()
    rng2 = np.random.default_rng(13)
    t0 = time.perf_counter()
    inter, serial, win = drive(
        w, "c", reqs, qos=wl.qos(),
        append_table=lambda: table(rng2, rows_per // 4),
        append_every=max(n_requests // 8, 1), commit_every=2)
    dt = time.perf_counter() - t0

    names = [t.name for t in tenants] + ["ingest"]
    sum_inter = tenant_summary(inter, names)
    sum_serial = tenant_summary(serial, names)
    tiers = {s.name: s for s in w.tier_stats()}
    s3, nvme = tiers["s3"], tiers["nvme_970evo"]

    # attribution exactness with reads and flushes in flight together
    att = attribute(w.store, queue_depth=qd)
    residual = 0.0
    sums = att.tier_sums()
    devices = [lvl.device for lvl in w.store.levels] + [w.store.backing]
    for stats, dev in zip(w.tier_stats(), devices):
        mt = stats.model_time(dev, qd)
        if mt > 0:
            residual = max(residual, abs(sums.get(stats.name, 0.0) - mt) / mt)
    pct = att.percentiles("take:c") or {}
    per_row_us = {k: round(v * 1e6, 4) for k, v in pct.items()
                  if k != "count"}

    p99_i = sum_inter["all"]["p99"]
    p99_s = sum_serial["all"]["p99"]

    # ---- live metrics plane + SLO: healthy re-pricing -------------------
    # Objectives ride on TenantSpec; thresholds derive from the healthy
    # run's own (deterministic, virtual-clock) latencies so the healthy
    # phase never breaches and any post-degradation breach is real signal.
    for spec in tenants:
        healthy = tenant_summary(inter, [spec.name])[spec.name]
        spec.slo_ms = round(healthy["max"] * 1.1, 6)
        spec.slo_target = 0.99 if spec.name == "premium" else 0.95
    slo_windows = (BurnWindow(long_s=0.5, short_s=0.0625,
                              burn_threshold=2.0),)
    plane_h = MetricsPlane(window=0.25, n_windows=8, rel_err=0.01)
    slo_tracer = TRACER if TRACER is not None else NULL_TRACER
    slo_h = SLOMonitor(wl.slo_objectives(), windows=slo_windows,
                       tracer=slo_tracer, registry=plane_h.registry,
                       plane=plane_h)
    inter_sampled = win.run("interleaved", plane=plane_h, slo=slo_h)
    # hard contract: sampling is read-only — completions bit-identical
    assert inter_sampled.completions == inter.completions, \
        "metrics plane/SLO sampling must not perturb event-loop timing"
    assert not slo_h.alerts, \
        "healthy run must not breach (objectives derived from its own max)"

    # ---- mid-run NVMe degradation + detection gates ---------------------
    # NVMe "grey failure": 200x latency, 1% throughput from t_deg onward.
    # The factors are deliberately strong — S3's 30 ms round trips dominate
    # healthy latency, so a mild NVMe stutter hides inside the S3 tail;
    # this is the firmware-stall / thermal-throttle shape where the fast
    # tier becomes the bottleneck.
    t_deg = round(inter.makespan * 0.5, 6)
    fault = Degradation(start=t_deg, latency_factor=200.0,
                        throughput_factor=0.01)
    devices = w.scheduler._devices()
    nvme_dev = next(d for d in devices if d.name.startswith("nvme"))
    deg_devices = [d.with_fault(fault) if d is nvme_dev else d
                   for d in devices]
    plane_d = MetricsPlane(window=0.25, n_windows=8, rel_err=0.01)
    slo_d = SLOMonitor(wl.slo_objectives(), windows=slo_windows,
                       tracer=slo_tracer, registry=plane_d.registry,
                       plane=plane_d)
    deg = EventLoop(deg_devices, queue_depth=qd, qos=wl.qos(),
                    plane=plane_d, slo=slo_d).run(win.jobs,
                                                  mode="interleaved")
    sum_deg = tenant_summary(deg, names)
    alert = slo_d.first_alert("premium")
    detect_bound_s = 1.0  # gated: breach must fire within this much
    assert alert is not None, \
        "NVMe degradation must fire slo.breach.premium"
    detect_delay = alert.at - t_deg
    assert 0.0 <= detect_delay <= detect_bound_s, \
        f"premium burn alert took {detect_delay:.3f}s virtual " \
        f"(bound {detect_bound_s}s after degradation at t={t_deg}s)"
    util = plane_d.series[f"tier.{nvme_dev.name}.utilization"]
    pre = util.between(0.0, t_deg)
    post = util.between(t_deg, float("inf"))
    pre_util = sum(pre) / len(pre) if pre else 0.0
    post_util = sum(post) / len(post) if post else 0.0
    assert post_util >= 0.9 and post_util > pre_util, \
        f"degraded NVMe utilization must saturate " \
        f"(pre={pre_util:.3f}, post={post_util:.3f})"
    if TRACER is not None and TRACER.enabled:
        plane_d.to_trace(TRACER)  # virtual-clock counter tracks

    # ---- closed-loop arrival comparison cell ----------------------------
    # Same tenants and Zipf skew, fixed client population with think time.
    # Coordinated omission: under load the closed loop throttles its own
    # arrivals, so its percentiles are not comparable to open-loop ones as
    # measurements of the same server — the cell reports both to show the
    # contrast, the open-loop numbers stay the headline.
    w2 = DatasetWriter(
        files=seeds,
        store=lambda d: TieredStore.cached(d, cache_bytes=budget),
        flush="write-back", opts=WriteOptions("lance-fullzip"),
        queue_depth=qd, tracer=TRACER)
    wl_c = ZipfWorkload(n_rows=w2.n_rows, tenants=tenants,
                        n_requests=n_requests, zipf_s=1.05, seed=3,
                        arrival="closed", think_time=0.02,
                        clients_per_tenant=4)
    inter_c, serial_c, _win_c = drive(w2, "c", wl_c.generate(),
                                      qos=wl_c.qos(), think_time=0.02)
    sum_closed = tenant_summary(inter_c, names)

    results = {
        "meta": {"n_fragments": n_frag, "rows_per_fragment": rows_per,
                 "n_requests": n_requests, "arrival_rate_per_s": arrival_rate,
                 "queue_depth": qd, "nvme_budget_bytes": budget,
                 "zipf_s": wl.zipf_s, "smoke": SMOKE,
                 "cpu_wall_s": round(dt, 6)},
        "workload": {
            "n_jobs": len(inter.completions),
            "n_take_requests": n_requests,
            "n_flush_drains": sum(
                1 for c in inter.completions if c.label.startswith("flush:")),
        },
        "interleaved_ms": sum_inter,
        "serial_ms": sum_serial,
        "tier_occupancy": inter.tiers,
        "counted": {
            "s3_iops": s3.n_iops, "s3_bytes_read": s3.bytes_read,
            "s3_write_iops": s3.write_iops,
            "s3_rmw_iops": s3.rmw_iops, "s3_rmw_bytes": s3.rmw_bytes,
            "nvme_iops": nvme.n_iops, "nvme_write_iops": nvme.write_iops,
            "nvme_hit_rate": round(nvme.hit_rate, 4)
            if nvme.hits + nvme.misses else None,
            "logical_read_iops": w.io_stats().n_iops,
            "logical_read_bytes": w.io_stats().bytes_read,
            "logical_write_iops": w.write_stats().n_iops,
        },
        "attribution": {"per_row_us": per_row_us,
                        "n_attributed_requests": pct.get("count"),
                        "residual_rel": residual},
        "slo": {
            "objectives": {t.name: {"slo_ms": t.slo_ms,
                                    "target": t.slo_target}
                           for t in tenants},
            "burn_window": {"long_s": slo_windows[0].long_s,
                            "short_s": slo_windows[0].short_s,
                            "threshold": slo_windows[0].burn_threshold},
            "healthy_breaches": slo_h.breach_counts(),
            "degraded": {
                "t_degradation_s": t_deg,
                "latency_factor": fault.latency_factor,
                "throughput_factor": fault.throughput_factor,
                "first_premium_alert_t": round(alert.at, 6),
                "detection_delay_s": round(detect_delay, 6),
                "detection_bound_s": detect_bound_s,
                "breaches": slo_d.breach_counts(),
                "nvme_utilization_pre": round(pre_util, 6),
                "nvme_utilization_post": round(post_util, 6),
                "table": slo_d.table(),
            },
        },
        "metrics_plane": plane_d.export(max_points=64),
        "closed_loop": {
            "arrival": "closed", "think_time_s": wl_c.think_time,
            "clients_per_tenant": wl_c.clients_per_tenant,
            "interleaved_ms": sum_closed,
            "makespan_s": round(inter_c.makespan, 6),
            "open_vs_closed_p99_ms": {
                "open": round(p99_i, 6),
                "closed": round(sum_closed["all"]["p99"], 6),
            },
            "caveat": "closed-loop percentiles hide coordinated omission; "
                      "not comparable to open-loop as server measurements",
        },
        "headline": {
            "gate": "interleaved event-loop p99 < serial batch-drain p99",
            "p50_interleaved_ms": round(sum_inter["all"]["p50"], 6),
            "p99_interleaved_ms": round(p99_i, 6),
            "p999_interleaved_ms": round(sum_inter["all"]["p999"], 6),
            "p50_serial_ms": round(sum_serial["all"]["p50"], 6),
            "p99_serial_ms": round(p99_s, 6),
            "p999_serial_ms": round(sum_serial["all"]["p999"], 6),
            "p99_speedup_serial_over_interleaved": round(p99_s / p99_i, 3),
            "p99_premium_ms": round(sum_inter["premium"]["p99"], 6),
            "p99_standard_ms": round(sum_inter["standard"]["p99"], 6),
        },
    }
    _emit("serve/latency", dt * 1e6,
          f"p99_interleaved_ms={p99_i:.3f};p99_serial_ms={p99_s:.3f};"
          f"speedup={p99_s / p99_i:.2f}x;jobs={len(inter.completions)};"
          f"residual={residual:.2e}")
    assert p99_i < p99_s, \
        "event-loop interleaved dispatch must beat serial batch-drain " \
        f"on p99 per-request latency ({p99_i:.3f} ms vs {p99_s:.3f} ms)"
    # QoS weights (premium 4x) are reported, not asserted: p99 for either
    # tenant is dominated by whether its rank-99 request hit a cold S3 row
    # (one 30 ms round trip), which weights cannot buy off — they only cut
    # queueing delay under round contention.
    _dump_json("BENCH_serve.json", results)
    _emit("serve/written", 0.0, "path=BENCH_serve.json")
    with open("BENCH_serve.prom", "w") as f:
        f.write(plane_d.prometheus_text())
    _emit("serve/slo", detect_delay * 1e6,
          f"detect_delay_s={detect_delay:.4f};"
          f"nvme_util_post={post_util:.3f};"
          f"breaches={slo_d.breach_counts()};path=BENCH_serve.prom")


def chaos_bench():
    """Fault-tolerant serving headline (BENCH_chaos.json): the Zipf
    multi-tenant workload of the serve bench driven through scripted fault
    scenarios, priced by the event loop's recovery layer (retry/backoff,
    tier failover, SLO-driven shedding).

    One captured service window is re-priced per scenario — classification,
    cache state and logical accounting are identical across all of them,
    only the fault schedule and recovery knobs differ (``window.run`` is
    pure).  Scenarios and their gates:

    * **healthy** — the recovery layer compiled in on healthy tiers is
      bit-identical to the bare event loop (ARCHITECTURE.md contract #8);
    * **transient** — 5% NVMe op errors over the middle half of the run:
      retries + failover keep premium availability >= 99.9%;
    * **blackout** — NVMe never comes back from t=0.3*makespan: failover
      re-homes every exhausted unit on S3 (zero failed requests); the
      ablation with ``failover=False`` must fail requests, or the gate is
      vacuous;
    * **correlated brownout** — one TransientErrors window stamped on NVMe
      *and* S3 (shared switch/AZ shape): retries ride it out;
    * **shed drill** — a controlled overload (premium + 2x standard at a
      rate only a healthy NVMe sustains) through a mid-run NVMe slowdown:
      the burn-driven Shedder must trip exactly once (hysteresis + hold-
      down, no flapping), reject only standard, keep premium availability
      at 100%, pull premium burn back under the page threshold after one
      settle interval, and bound recovery after the fault clears.  The
      drill uses synthetic fixed-shape drains so the overload margin is
      exact — the gate is about the control loop, not cache luck.
    """
    from repro.core.io_sim import (Blackout, CorrelatedFault, Degradation,
                                   TransientErrors)
    from repro.dataset import DatasetWriter
    from repro.obs import (BurnWindow, MetricsPlane, Shedder, SLObjective,
                           SLOMonitor)
    from repro.serve.workload import (FaultScenario, TenantSpec,
                                      ZipfWorkload, drive, run_scenario,
                                      tenant_summary)
    from repro.store import EventLoop, QoS, RetryPolicy, TieredStore, build_job
    from repro.store.stats import DrainRecord

    n_frag = 4 if SMOKE else 8
    rows_per = 800 if SMOKE else 4_000
    n_requests = 72 if SMOKE else 600
    width = 32
    qd = 32
    n_total = n_frag * rows_per
    budget = max(int(0.5 * n_total * width * 4), 1 << 18)

    def table(rng, n):
        vals = rng.standard_normal((n, width)).astype(np.float32)
        arr = A.FixedSizeListArray(
            T.FixedSizeList(T.Primitive("float32", nullable=False), width),
            np.ones(n, bool), vals)
        return {"c": arr}

    rng = np.random.default_rng(7)
    seeds = [write_table(table(rng, rows_per), WriteOptions("lance-fullzip"))
             for _ in range(n_frag)]
    w = DatasetWriter(
        files=seeds,
        store=lambda d: TieredStore.cached(d, cache_bytes=budget),
        flush="write-back", opts=WriteOptions("lance-fullzip"),
        queue_depth=qd, tracer=TRACER)
    tenants = [
        TenantSpec("premium", share=1.0, weight=4.0, priority=1,
                   rows_per_request=32),
        TenantSpec("standard", share=2.0, weight=1.0, rows_per_request=32),
    ]
    wl = ZipfWorkload(n_rows=w.n_rows, tenants=tenants,
                      n_requests=n_requests, zipf_s=1.05,
                      arrival_rate=200.0, seed=3)
    t0 = time.perf_counter()
    healthy, _serial, win = drive(w, "c", wl.generate(), qos=wl.qos())
    dt = time.perf_counter() - t0
    names = [t.name for t in tenants]
    M = healthy.makespan
    devices = w.scheduler._devices()
    nvme_name = next(d.name for d in devices if d.name.startswith("nvme"))
    s3_name = w.store.backing.name

    # ---- healthy-path bit-identity (contract #8) ------------------------
    # drive() priced with the scheduler's compiled-in RetryPolicy; the bare
    # loop with no policy must produce the same bits on healthy tiers.
    bare = EventLoop(devices, queue_depth=qd, qos=wl.qos()).run(win.jobs)
    assert bare.completions == healthy.completions, \
        "recovery layer must be invisible on healthy tiers"
    assert healthy.availability() == 1.0

    def counters_of(res, prefix):
        return {k: v for k, v in sorted(res.counters.items())
                if k.startswith(prefix)}

    def cell(res):
        return {
            "makespan_s": round(res.makespan, 6),
            "availability": round(res.availability(), 6),
            "availability_premium": round(res.availability("premium"), 6),
            "n_failed": len(res.errors),
            "counters": {k: v for k, v in sorted(res.counters.items())},
            "premium_p99_ms": (tenant_summary(res, names)["premium"]["p99"]
                               if res.availability("premium") > 0 else None),
        }

    # ---- scenario: transient NVMe errors --------------------------------
    sc_t = FaultScenario(
        "transient_nvme",
        faults=((nvme_name, TransientErrors(0.25 * M, 0.75 * M,
                                            error_prob=0.05, seed=11)),),
        description="5% op errors on the cache tier, middle half of run")
    res_t = run_scenario(win, sc_t, qos=wl.qos())
    avail_premium_t = res_t.availability("premium")
    assert avail_premium_t >= 0.999, \
        f"premium availability {avail_premium_t} < 99.9% under " \
        "transient NVMe errors (retry/failover must absorb them)"
    assert res_t.counters.get(f"retry.{nvme_name}", 0) > 0
    # recovery is priced, not free — but under contention a backed-off
    # unit frees round slots for other jobs, so the *global* makespan can
    # move either way by round-granularity slack; availability is the gate

    # ---- scenario: NVMe blackout, failover on/off -----------------------
    black = Blackout(0.3 * M)  # never comes back
    sc_b_on = FaultScenario("blackout_failover",
                            faults=((nvme_name, black),))
    sc_b_off = FaultScenario("blackout_no_failover",
                             faults=((nvme_name, black),),
                             retry=RetryPolicy(failover=False))
    res_b_on = run_scenario(win, sc_b_on, qos=wl.qos())
    res_b_off = run_scenario(win, sc_b_off, qos=wl.qos())
    assert len(res_b_on.errors) == 0, \
        "failover to S3 must absorb a permanent NVMe blackout"
    assert res_b_on.counters.get(f"failover.{nvme_name}", 0) > 0
    assert len(res_b_off.errors) > 0, \
        "ablation must fail requests, else the failover gate is vacuous"

    # ---- scenario: correlated NVMe+S3 brownout --------------------------
    cf = CorrelatedFault(TransientErrors(0.25 * M, 0.6 * M,
                                         error_prob=0.03, seed=5),
                         (nvme_name, s3_name))
    sc_c = FaultScenario(
        "correlated_brownout",
        faults=tuple((n, cf.fault) for n in cf.devices),
        description="one error window stamped on NVMe and S3 together")
    res_c = run_scenario(win, sc_c, qos=wl.qos())
    avail_c = res_c.availability()
    assert avail_c >= 0.999, \
        f"availability {avail_c} < 99.9% under correlated brownout"

    # ---- scenario: SLO-driven shed drill --------------------------------
    # Controlled overload: 64-op single-tier drains, one premium + two
    # standard arrivals per 300 us slot.  A healthy NVMe round at qd=64
    # services one job per ~90 us; the 2x degraded tier can only sustain
    # the premium stream alone, so shedding standard is exactly the relief
    # that restores the premium SLO.
    n_drill = 300
    drill_jobs = []
    seq = 0
    from repro.core.io_sim import NVME as NVME_DEV, S3 as S3_DEV
    drill_devices = [NVME_DEV, S3_DEV]
    for i in range(n_drill):
        for tenant in ("premium", "standard", "standard"):
            seq += 1
            rec = DrainRecord(f"{tenant}/{i}", 1,
                              {0: ({0: 64}, {0: 64 * 4096})})
            drill_jobs.append(build_job(rec, drill_devices, tenant=tenant,
                                        submit=i * 3e-4, seq=seq))
    drill_qos = QoS(priority={"premium": 1})
    healthy_d = EventLoop(drill_devices, queue_depth=64,
                          qos=drill_qos).run(drill_jobs)
    Md = healthy_d.makespan
    obj_s = healthy_d.percentiles("premium")["p99"] * 5.0
    burn_win = BurnWindow(long_s=Md / 8, short_s=Md / 64, burn_threshold=2.0)
    deg = Degradation(0.2 * Md, 0.8 * Md, latency_factor=2.0,
                      throughput_factor=1.0)
    drill_faulted = [drill_devices[0].with_fault(deg), drill_devices[1]]

    def drill(shed_on):
        mon = SLOMonitor({"premium": SLObjective(obj_s, target=0.99)},
                         windows=(burn_win,))
        sh = Shedder(mon, protect=("premium",), shed=("standard",),
                     on_burn=4.0, off_burn=1.0,
                     hold_s=Md / 4) if shed_on else None
        plane = MetricsPlane(window=Md / 16, n_windows=8, rel_err=0.01)
        res = EventLoop(drill_faulted, queue_depth=64, qos=drill_qos,
                        retry=RetryPolicy(), plane=plane, slo=mon,
                        shedder=sh).run(drill_jobs)
        return res, sh, plane

    res_on, sh, plane_on = drill(True)
    res_off, _, _ = drill(False)

    def burn_at(res, t):
        """Offline premium burn over the long window ending at ``t``."""
        bad = tot = 0
        for c in res.completions:
            if c.tenant != "premium" or c.error == "shed":
                continue
            if t - burn_win.long_s <= c.done <= t:
                tot += 1
                bad += (c.error is not None) or (c.latency > obj_s)
        return (bad / tot) / 0.01 if tot else 0.0

    assert sh.trips == 1, \
        f"shedder tripped {sh.trips}x: hysteresis + hold-down must " \
        "prevent flapping"
    assert res_on.counters.get("shed.standard", 0) > 0
    assert "shed.premium" not in res_on.counters
    assert res_on.availability("premium") == 1.0
    settle = sh.engaged_at[0] + 3.0 * burn_win.long_s
    burn_on = burn_at(res_on, settle)
    burn_off = burn_at(res_off, settle)
    page_burn = 4.0
    assert burn_on < page_burn, \
        f"premium burn {burn_on} still above page threshold " \
        f"{page_burn} one settle interval after shedding engaged"
    assert burn_off > page_burn, \
        "unshedded ablation must stay above the page threshold, " \
        "else the shedding gate is vacuous"

    def recovery_after(res, t_end):
        last = max((c.done for c in res.completions
                    if c.tenant == "premium" and c.error != "shed"
                    and (c.error is not None or c.latency > obj_s)),
                   default=t_end)
        return max(0.0, last - t_end)

    rec_on = recovery_after(res_on, deg.end)
    rec_off = recovery_after(res_off, deg.end)
    rec_bound = 0.1 * Md
    assert rec_on <= rec_bound, \
        f"premium recovery {rec_on}s after fault end exceeds {rec_bound}s"
    assert res_on.makespan < res_off.makespan
    if TRACER is not None and TRACER.enabled:
        plane_on.to_trace(TRACER)

    fault_summary = {
        "availability_premium_transient": round(avail_premium_t, 6),
        "availability_correlated": round(avail_c, 6),
        "blackout_failed_with_failover": len(res_b_on.errors),
        "blackout_failed_without_failover": len(res_b_off.errors),
        "blackout_failovers": res_b_on.counters.get(
            f"failover.{nvme_name}", 0),
        "transient_retries": res_t.counters.get(f"retry.{nvme_name}", 0),
        "shed_trips": sh.trips,
        "shed_standard": res_on.counters.get("shed.standard", 0),
        "shed_premium": res_on.counters.get("shed.premium", 0),
        "premium_burn_after_settle_shed": round(burn_on, 6),
        "premium_burn_after_settle_noshed": round(burn_off, 6),
        "recovery_s_with_shedding": round(rec_on, 6),
        "recovery_s_without_shedding": round(rec_off, 6),
    }
    results = {
        "meta": {"n_fragments": n_frag, "rows_per_fragment": rows_per,
                 "n_requests": n_requests, "queue_depth": qd,
                 "nvme_budget_bytes": budget, "smoke": SMOKE,
                 "n_drill_requests": 3 * n_drill,
                 "cpu_wall_s": round(dt, 6)},
        "healthy": {
            "makespan_s": round(M, 6),
            "bit_identical_with_recovery_layer": True,
            "interleaved_ms": tenant_summary(healthy, names),
        },
        "scenarios": {
            "transient_nvme": cell(res_t),
            "blackout_failover": cell(res_b_on),
            "blackout_no_failover": cell(res_b_off),
            "correlated_brownout": cell(res_c),
            "shed_drill": {
                "makespan_healthy_s": round(Md, 6),
                "objective_s": round(obj_s, 9),
                "burn_window_s": {"long": round(burn_win.long_s, 9),
                                  "short": round(burn_win.short_s, 9)},
                "degradation": {"start_s": round(deg.start, 6),
                                "end_s": round(deg.end, 6),
                                "latency_factor": deg.latency_factor},
                "engaged_at_s": round(sh.engaged_at[0], 6),
                "released_at_s": (round(sh.released_at[0], 6)
                                  if sh.released_at else None),
                "with_shedding": cell(res_on),
                "without_shedding": cell(res_off),
            },
        },
        "fault": fault_summary,
        "headline": {
            "gate": "premium availability >= 99.9% under transient errors; "
                    "zero failed under blackout with failover; shedding "
                    "holds premium burn under the page threshold",
            **fault_summary,
        },
    }
    _dump_json("BENCH_chaos.json", results)
    _emit("chaos/transient", res_t.makespan * 1e6,
          f"avail_premium={avail_premium_t:.6f};"
          f"retries={fault_summary['transient_retries']}")
    _emit("chaos/blackout", res_b_on.makespan * 1e6,
          f"failed_on={len(res_b_on.errors)};"
          f"failed_off={len(res_b_off.errors)};"
          f"failovers={fault_summary['blackout_failovers']}")
    _emit("chaos/shed", res_on.makespan * 1e6,
          f"trips={sh.trips};shed={fault_summary['shed_standard']};"
          f"burn_on={burn_on:.3f};burn_off={burn_off:.3f};"
          f"recovery_s={rec_on:.6f}")
    _emit("chaos/written", dt * 1e6, "path=BENCH_chaos.json")


def search_bench():
    """Vector retrieval headline (BENCH_search.json): IVF index stored *as
    dataset fragments* serving Zipf-skewed ANN queries through the shared
    tiered store, scored by the Pallas distance/top-k kernel.

    The corpus is a mixture of Gaussians — IVF's recall story depends on
    the data having partition structure (isotropic noise has none: every
    partition boundary cuts through true neighbourhoods) — and it is
    written in *partition-clustered row order* (docs sorted by mode).
    That layout is the point, not a convenience: a posting list over a
    clustered corpus is a handful of contiguous row runs, so the
    candidate fetch coalesces into big extent reads priced at sequential
    bandwidth.  Scattered postings pay the device's 4 KiB read floor per
    row, which costs *more* than scanning everything — an index over an
    unclustered corpus loses to brute force on this device model, and
    should.

    Queries are perturbed copies of stored docs drawn by Zipf popularity,
    driven through a service window so every search step — centroid take,
    posting take, candidate take, winner take — is priced per request by
    the event loop.  The serving tier is sized to the dataset (NVMe holds
    data + index after the index build's training scan and one warmup
    batch; S3 stays the durable origin), so the measured pass is steady
    state.  Gates:

    * **recall@k >= 0.9** against exact float64 brute force at
      ``nprobe``/``n_partitions`` probing;
    * **search QPS > full-scan QPS** — the ablation answers the same query
      stream by taking every row on an identically provisioned store (what
      brute force costs); probing ``nprobe/n_partitions`` of the corpus
      must beat reading all of it, or the index is decoration;
    * **warm repeat is NVMe-served** — re-running the last query touches
      only cached blocks (index reads warm the same budget as data reads).
    """
    from repro.dataset import DatasetWriter, IvfIndex, write_fragments
    from repro.serve.engine import Retriever
    from repro.serve.workload import TenantSpec, ZipfWorkload, tenant_summary
    from repro.store import TieredStore

    n_frag = 4 if SMOKE else 8
    rows_per = 3_200 if SMOKE else 8_000
    dim = 64
    n_partitions = 32 if SMOKE else 64
    nprobe = 4 if SMOKE else 8
    k = 10
    n_requests = 48 if SMOKE else 256
    qd = 32
    n_docs = n_frag * rows_per
    # serving tier sized to the dataset: NVMe holds data + index, S3 is
    # the durable origin paid once (by the build scan and the warmup)
    budget = 2 * n_docs * dim * 4

    # clustered corpus: one Gaussian mode per eventual partition, means
    # far apart relative to the within-mode spread, so a query near a
    # stored doc keeps its true neighbours inside a handful of partitions.
    # Rows are *sorted by mode* — partition-clustered layout — so each
    # k-means posting list is a few contiguous row runs.
    rng = np.random.default_rng(11)
    means = 4.0 * rng.standard_normal((n_partitions, dim)).astype(np.float32)
    modes = np.sort(rng.integers(0, n_partitions, n_docs))
    vecs = means[modes] \
        + 0.25 * rng.standard_normal((n_docs, dim)).astype(np.float32)
    emb = A.FixedSizeListArray.build(vecs)
    seeds = write_fragments({"embedding": emb}, n_frag, WriteOptions("lance"))
    w = DatasetWriter(
        files=seeds,
        store=lambda d: TieredStore.cached(d, cache_bytes=budget),
        queue_depth=qd, tracer=TRACER)
    t0 = time.perf_counter()
    ivf = IvfIndex.build(w, "embedding", n_partitions=n_partitions,
                         n_fragments=2, seed=0)
    build_stats = w.io_stats()
    retr = Retriever(w.reader(), "embedding", index=ivf)

    wl = ZipfWorkload(n_rows=n_docs,
                      tenants=[TenantSpec("search", rows_per_request=1)],
                      n_requests=n_requests, zipf_s=1.05,
                      arrival_rate=2_000.0, seed=3)
    reqs = wl.generate()
    qrng = np.random.default_rng(5)
    queries = [vecs[int(req.rows[0])]
               + 0.05 * qrng.standard_normal(dim).astype(np.float32)
               for req in reqs]
    # warmup: one batched search over the whole query set promotes every
    # probed partition, posting run and winner block into the NVMe tier —
    # the measured pass below is the steady-state serving regime
    retr.search(np.stack(queries), k=k, nprobe=nprobe)
    w.reset_io()
    got = []
    with w.scheduler.service_window(wl.qos()) as win:
        for i, req in enumerate(reqs):
            with win.request(tenant="search", at=req.at,
                             request=f"search/{i}"):
                res = retr.search(queries[i], k=k, nprobe=nprobe)
            got.append(res.ids[0])
        inter = win.run("interleaved")
        serial = win.run("serial")
    dt = time.perf_counter() - t0
    st = w.io_stats()
    tiers = {s.name: s for s in w.tier_stats()}
    s3, nvme = tiers["s3"], tiers["nvme_970evo"]

    # exact recall@k against float64 brute force (per query, so the full
    # run never materialises an (n_requests, n_docs) distance matrix)
    v64 = vecs.astype(np.float64)
    hits = 0
    for q, ids in zip(queries, got):
        d = ((v64 - q.astype(np.float64)) ** 2).sum(-1)
        top = set(np.argsort(d, kind="stable")[:k].tolist())
        hits += sum(int(i) in top for i in ids if i >= 0)
    recall = hits / (n_requests * k)

    # warm repeat: the last query's blocks are the most recently used —
    # serving it again must touch NVMe only (shared index + data budget)
    w.reset_io()
    retr.search(queries[-1], k=k, nprobe=nprobe)
    wtiers = {s.name: s for s in w.tier_stats()}
    warm_hit = wtiers["nvme_970evo"].hit_rate
    warm_s3 = wtiers["s3"].n_iops

    # full-scan ablation: same query stream answered by taking every row
    # on an identically provisioned (and identically warmed) fresh store
    n_abl = n_requests if SMOKE else min(n_requests, 64)
    w2 = DatasetWriter(
        files=seeds,
        store=lambda d: TieredStore.cached(d, cache_bytes=budget),
        queue_depth=qd, tracer=TRACER)
    all_rows = np.arange(n_docs, dtype=np.int64)
    w2.take("embedding", all_rows)  # warm: the scan set is NVMe-resident too
    w2.reset_io()
    with w2.scheduler.service_window(wl.qos()) as win2:
        for i, req in enumerate(reqs[:n_abl]):
            with win2.request(tenant="search", at=req.at,
                              request=f"scan/{i}"):
                w2.take("embedding", all_rows)
        inter_fs = win2.run("interleaved")
    qps_search = n_requests / inter.makespan
    qps_scan = n_abl / inter_fs.makespan
    sum_inter = tenant_summary(inter, ["search"])

    results = {
        "meta": {"n_docs": n_docs, "dim": dim, "n_fragments": n_frag,
                 "n_requests": n_requests, "queue_depth": qd,
                 "nvme_budget_bytes": budget, "zipf_s": wl.zipf_s,
                 "smoke": SMOKE, "cpu_wall_s": round(dt, 6)},
        "index": {
            "n_partitions": n_partitions, "nprobe": nprobe, "k": k,
            "index_rows": n_partitions,
            "index_versions": len(ivf.writer.versions),
            "build_logical_iops": build_stats.n_iops,
            "build_logical_bytes": build_stats.bytes_read,
        },
        "counted": {
            "logical_iops": st.n_iops,
            "logical_bytes": st.bytes_read,
            "iops_per_query": round(st.n_iops / n_requests, 4),
            "s3_iops": s3.n_iops, "s3_bytes_read": s3.bytes_read,
            "nvme_iops": nvme.n_iops,
            "nvme_hit_rate": round(nvme.hit_rate, 4)
            if nvme.hits + nvme.misses else None,
        },
        "warm_repeat": {
            "nvme_hit_rate": round(warm_hit, 4),
            "s3_iops": warm_s3,
        },
        "latency": {"interleaved_ms": sum_inter,
                    "serial_all_p99_ms":
                        tenant_summary(serial, ["search"])["all"]["p99"]},
        "fullscan_ablation": {
            "n_requests": n_abl,
            "makespan_s": round(inter_fs.makespan, 6),
            "logical_iops": w2.io_stats().n_iops,
            "logical_bytes": w2.io_stats().bytes_read,
        },
        "headline": {
            "gate": "recall@k >= 0.9; search qps > full-scan qps; "
                    "warm repeat NVMe-served",
            "recall_at_k": round(recall, 6),
            "search_qps": round(qps_search, 3),
            "fullscan_qps": round(qps_scan, 3),
            "qps_search_over_fullscan": round(qps_search / qps_scan, 3),
            "p50_search_ms": round(sum_inter["all"]["p50"], 6),
            "p99_search_ms": round(sum_inter["all"]["p99"], 6),
            "makespan_s": round(inter.makespan, 6),
            "warm_nvme_hit_rate": round(warm_hit, 4),
        },
    }
    assert recall >= 0.9, \
        f"IVF recall@{k} must stay >= 0.9 at nprobe={nprobe}/" \
        f"{n_partitions} on clustered data (got {recall:.4f})"
    assert qps_search > qps_scan, \
        f"index-served QPS must beat the full-scan ablation " \
        f"({qps_search:.2f} vs {qps_scan:.2f})"
    assert warm_hit == 1.0 and warm_s3 == 0, \
        f"warm repeat must be fully NVMe-served " \
        f"(hit_rate={warm_hit:.4f}, s3_iops={warm_s3})"
    _emit("search/recall", dt * 1e6,
          f"recall_at_{k}={recall:.4f};nprobe={nprobe}/{n_partitions};"
          f"iops_per_query={st.n_iops / n_requests:.1f}")
    _emit("search/qps", inter.makespan * 1e6,
          f"search_qps={qps_search:.1f};fullscan_qps={qps_scan:.1f};"
          f"speedup={qps_search / qps_scan:.1f}x;"
          f"warm_nvme_hit_rate={warm_hit:.2f}")
    _dump_json("BENCH_search.json", results)
    _emit("search/written", 0.0, "path=BENCH_search.json")


def kernel_bench():
    """Device decode paths: ref-oracle throughput on CPU + kernel validation
    (interpret mode executes the kernel body; wall-time is not TPU time)."""
    import jax
    import jax.numpy as jnp

    from repro.core.compression import bitpack
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    n, bits = 1 << 20, 11
    v = rng.integers(0, 1 << bits, n, dtype=np.uint64)
    words = jnp.asarray(ops.pack_words(bitpack(v, bits)))
    f = jax.jit(lambda w: ops.bitunpack(w, n, bits, use_pallas=False))
    f(words).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        f(words).block_until_ready()
    dt = (time.perf_counter() - t0) / 10
    _emit("kernel/bitunpack_ref_jit", dt * 1e6, f"Mvals_per_s={n/dt/1e6:.0f}")
    got = np.asarray(ops.bitunpack(words, n, bits))  # pallas interpret
    assert (got == v).all()
    _emit("kernel/bitunpack_pallas_validated", 0.0, "allclose=True")

    zipped = jnp.asarray(rng.integers(0, 256, (100_000, 64), dtype=np.uint8))
    rows = jnp.asarray(rng.integers(0, 100_000, 4096).astype(np.int32))
    g = jax.jit(lambda z, r: ops.fullzip_gather(z, r, use_pallas=False))
    g(zipped, rows).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        g(zipped, rows).block_until_ready()
    dt = (time.perf_counter() - t0) / 20
    _emit("kernel/fullzip_gather_ref_jit", dt * 1e6,
          f"Mrows_per_s={4096/dt/1e6:.1f}")


def loader_bench():
    """Training input pipeline: tokens/s through the Lance scan loader."""
    from repro.data.loader import TokenLoader, write_token_file

    fb = write_token_file(n_rows=512, seq_len=512, vocab=32_000)
    loader = TokenLoader(fb, batch=8, seq_len=512)
    try:
        next(iter(loader))
        t0 = time.perf_counter()
        n = 0
        for i, b in enumerate(loader):
            n += b["tokens"].size
            if i >= 20:
                break
        dt = time.perf_counter() - t0
        _emit("loader/tokens", dt / 20 * 1e6, f"Mtok_per_s={n/dt/1e6:.1f}")
    finally:
        loader.close()


ALL = [fig1_device_model, fig10_parquet_random_access,
       fig11_encodings_random_access, fig12_fullzip_vs_miniblock,
       fig13_compression, fig14_16_full_scan, fig17_scan_decode_cost,
       fig18_struct_packing, store_tiering, take_decode, decode_bench,
       dataset_take, ingest_bench, serve_bench, chaos_bench, search_bench,
       kernel_bench, loader_bench]


def _bench_names():
    """Every name a positional arg may use: full function names plus their
    leading-word tags (``take`` selects ``take_decode``)."""
    names = set()
    for fn in ALL:
        names.add(fn.__name__)
        names.add(fn.__name__.split("_")[0])
    return names


def _parse_args(argv):
    global STORE_SPEC, SMOKE, TRACER, TRACE_PATH
    want = set()
    it = iter(argv)
    for a in it:
        if a == "--store":
            STORE_SPEC = next(it, None)
            if STORE_SPEC is None:
                raise SystemExit("--store requires a value (flat|tiered|flat-s3|hot)")
        elif a.startswith("--store="):
            STORE_SPEC = a.split("=", 1)[1]
        elif a == "--trace":
            TRACE_PATH = next(it, None)
            if TRACE_PATH is None:
                raise SystemExit("--trace requires an output path")
        elif a.startswith("--trace="):
            TRACE_PATH = a.split("=", 1)[1]
        elif a == "--smoke":
            SMOKE = True
        elif a == "--list":
            for fn in ALL:
                print(f"{fn.__name__.split('_')[0]:12s} {fn.__name__}")
            raise SystemExit(0)
        elif a.startswith("-"):
            raise SystemExit(f"unknown option {a}")
        else:
            want.add(a)
    if STORE_SPEC not in ("flat", "tiered", "flat-s3", "hot"):
        raise SystemExit(f"--store must be flat|tiered|flat-s3|hot, got {STORE_SPEC}")
    # a typo'd benchmark name used to select nothing and exit 0 — a CI run
    # that silently measured nothing looked green
    unknown = want - _bench_names()
    if unknown:
        avail = ", ".join(sorted(fn.__name__ for fn in ALL))
        raise SystemExit(
            f"unknown benchmark(s): {', '.join(sorted(unknown))}\n"
            f"available: {avail}  (or their first-word tags; see --list)")
    if TRACE_PATH is not None:
        TRACER = Tracer()
    return want


def main() -> None:
    want = _parse_args(sys.argv[1:])
    enable_compile_cache()
    print("name,us_per_call,derived")
    for fn in ALL:
        tag = fn.__name__.split("_")[0]
        if want and tag not in want and fn.__name__ not in want:
            continue
        fn()
    if TRACER is not None:
        n = TRACER.export(TRACE_PATH)
        _emit("trace/written", 0.0, f"path={TRACE_PATH};events={n}")


if __name__ == "__main__":
    main()
