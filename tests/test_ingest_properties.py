"""Property-based crash-consistency tests.

The flush-then-commit fence's contract, stated as a property: for ANY
interleaving of appends (committed or staged), commits, interrupted flushes
(a crash after any prefix of the flush's dispatched extents), and crashes,
every manifest version that was ever committed remains readable with exactly
the rows it committed.
Example-based equivalents live in ``test_ingest.py``.
"""

import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import arrays as A
from repro.core.file import WriteOptions
from repro.dataset import DatasetWriter
from repro.store import FlushPolicy, SimulatedCrash, TieredStore

# one scripted ingest step: (op, size-ish argument)
#   append  — stage a fragment of `arg` rows (committed if arg is odd)
#   commit  — durability fence, possibly interrupted after `arg` flush extents
#   crash   — tear unflushed state, rewind to the last committed version
_STEP = st.one_of(
    st.tuples(st.just("append"), st.integers(2, 40)),
    st.tuples(st.just("commit"), st.integers(0, 3)),
    st.tuples(st.just("crash"), st.just(0)),
)


@settings(max_examples=30, deadline=None)
@given(
    script=st.lists(_STEP, min_size=1, max_size=10),
    mode=st.sampled_from(["write-back", "flush-on-evict", "write-through"]),
    cache_blocks=st.integers(4, 64),
    interrupt=st.booleans(),
)
def test_any_crash_prefix_keeps_every_committed_version_readable(
        script, mode, cache_blocks, interrupt):
    w = DatasetWriter(
        store=lambda d: TieredStore.cached(d, cache_bytes=cache_blocks * 4096),
        flush=FlushPolicy(mode, deadline_batches=3),
        opts=WriteOptions("lance"))
    next_val = 0            # appended values are globally sequential ints
    committed_rows = 0      # mirror of the last committed row count
    version_rows = []       # version v committed version_rows[v-1] rows

    def check_all_versions():
        assert w.version == len(version_rows)
        assert w.n_rows == committed_rows
        for v, n in enumerate(version_rows, start=1):
            r = w.reader(v)
            assert r.n_rows == n
            # spot-check the decoded rows, including both edges
            rows = np.unique(np.clip([0, n // 2, n - 1], 0, n - 1))
            assert A.to_pylist(r.take("c", rows)) == rows.tolist()

    for op, arg in script:
        if op == "append":
            n = arg
            table = {"c": A.PrimitiveArray.build(
                np.arange(next_val, next_val + n, dtype=np.int64),
                nullable=False)}
            w.append(table, commit=bool(arg % 2))
            next_val += n
            if arg % 2:
                committed_rows = next_val
                version_rows.append(committed_rows)
        elif op == "commit":
            if interrupt:
                w.flush_policy.fail_after = arg
            try:
                m = w.commit()
            except SimulatedCrash:
                # interrupted fence: the new version must NOT exist and the
                # torn state must rewind cleanly
                w.flush_policy.fail_after = None
                w.simulate_crash()
                next_val = committed_rows
            else:
                if m is not None:  # None: still-empty dataset
                    committed_rows = m.n_rows
                    if m.version > len(version_rows):
                        version_rows.append(committed_rows)
            w.flush_policy.fail_after = None
        else:  # crash
            w.simulate_crash()
            next_val = committed_rows
        check_all_versions()

    # full-scan audit at the end: the latest version holds exactly the
    # sequential prefix that survived every crash
    if version_rows:
        assert A.to_pylist(w.scan("c")) == list(range(committed_rows))
