"""Traffic as data: each mix names its generator file, which draws the same
requests from the same seed; the search pool is cut into batches of the
mix's size; warm-up reaches every padded shape of the pool; a program
loaded inside the window is flagged; and a search that leaves out half of
its batch is not correct."""

import itertools
import json

import numpy as np
import pytest

from bench_tiny import BENCH, SEED, run_tiny, tiny_cell
from harness.cell import reports

SEARCH, WIDE = "search.sift1m-ivf1024", "take-wide.sift1m-vectors"


@pytest.mark.parametrize("mix", sorted(p.stem for p in
                                       (BENCH / "traffic").glob("*.json")))
def test_mix_names_its_generator(mix):
    spec = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    assert (BENCH / "traffic" / f"{spec['generator']}.py").is_file()


def test_uniform_rows_follow_the_seed():
    cell = tiny_cell(WIDE)
    data = {"n_rows": 4000}
    gen, mix = cell.generator, cell.traffic

    def first(seed, warmup=False):
        return [r["rows"] for r in itertools.islice(
            gen.requests(mix, data, seed, warmup=warmup), 3)]

    a, b = first(SEED), first(SEED)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], first(SEED + 1)[0])
    assert not np.array_equal(a[0], first(SEED, warmup=True)[0])
    assert all(len(x) == 64 and x.min() >= 0 and x.max() < 4000 for x in a)


@pytest.mark.parametrize("per_request", [1, 4])
def test_query_pool_serves_every_query_in_batches(per_request):
    cell = tiny_cell(SEARCH)
    mix = dict(cell.traffic, queries_per_request=per_request)
    data = {"queries": np.arange(12 * 3, dtype=np.float32).reshape(12, 3)}
    gen = cell.generator
    pool = gen.pool(mix, data, SEED)
    assert [len(r["queries"]) for r in pool] == \
        [per_request] * (12 // per_request)
    seen = np.concatenate([r["queries"] for r in pool])
    assert sorted(seen) == list(range(12))
    for r in pool:
        assert np.array_equal(r["vectors"], data["queries"][r["queries"]])
    window = list(itertools.islice(gen.requests(mix, data, SEED), len(pool)))
    warm = list(itertools.islice(gen.requests(mix, data, SEED, True),
                                 len(pool)))
    assert [list(r["queries"]) for r in window] == \
        [list(r["queries"]) for r in warm[::-1]]
    other = gen.pool(mix, data, SEED + 1)
    assert [list(r["queries"]) for r in other] != \
        [list(r["queries"]) for r in pool]


def test_warm_up_covers_every_shape_of_the_pool():
    cell = tiny_cell(SEARCH)
    data = cell.system.generate(cell.config, cell.traffic, SEED)
    sut = cell.system.build(cell.config, data, SEED)
    pool = cell.generator.pool(cell.traffic, data, SEED)
    keys, parts = cell.system.shape_keys(sut, pool)
    assert all(len(p) <= cell.config["nprobe"] for p in parts)
    chosen = cell.system.warm(sut, pool, cell.traffic["warmup"])
    ids = {tuple(r["queries"]) for r in chosen}
    covered = set().union(*(k for r, k in zip(pool, keys)
                            if tuple(r["queries"]) in ids))
    assert covered == set().union(*keys)
    assert len(chosen) <= len(set().union(*keys))


def test_batched_search_is_correct(tmp_path):
    cell = tiny_cell(SEARCH)
    cell.traffic["queries_per_request"] = 3
    out, _ = run_tiny(SEARCH, tmp_path, seconds=1.0, cell=cell)
    assert out["correct"] and out["failed"] == 0
    assert out["checks"]["value_mismatches"]["value"] == 0


def test_half_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    from repro.serve.engine import Retriever

    orig = Retriever.search

    def halved(self, query, *args, **kwargs):
        q = np.atleast_2d(query)
        return orig(self, q[: max(1, len(q) // 2)], *args, **kwargs)

    monkeypatch.setattr(Retriever, "search", halved)
    cell = tiny_cell(SEARCH)
    cell.traffic["queries_per_request"] = 4
    out, _ = run_tiny(SEARCH, tmp_path, seconds=1.0, cell=cell)
    assert not out["correct"]
    assert out["checks"]["missed_neighbors"]["value"] >= 0.5


def test_loads_in_the_window_are_flagged(tmp_path):
    import jax

    jax.clear_caches()  # so that the window compiles what it runs
    cell = tiny_cell(WIDE)
    cell.traffic["warmup"] = {"quiet": 0, "max": 0}
    out, lines = run_tiny(WIDE, tmp_path, cell=cell)
    assert out["correct"] and out["window_loads"]
    assert any(s.startswith("WARNING: warm-up missed") for s in lines)


def test_a_metric_without_workloads_is_in_every_cell():
    assert reports({"name": "setup_s"}, WIDE)
    assert reports({"name": "m", "workloads": [WIDE]}, WIDE)
    assert not reports({"name": "m", "workloads": [SEARCH]}, WIDE)
