"""Property tests for the log-bucket histogram and its windowed ring.

The properties are the tentpole contracts stated in
``repro/obs/timeseries.py``:

* merge is exact, associative, and commutative — merging histograms is
  indistinguishable (bucket-for-bucket) from observing the concatenated
  population in any order;
* ``quantile(q)`` is within ``rel_err`` relative of the exact nearest-rank
  value over the observed samples, for every q and every rel_err;
* window rotation never loses counts: at all times
  ``total.count == dropped + live counts``, under arbitrary (including
  out-of-order) virtual timestamps.
"""

from hypothesis import given, settings, strategies as st

from repro.obs.metrics import percentile
from repro.obs.timeseries import LogBucketHistogram, WindowedHistogram

# latency/occupancy-like magnitudes: non-negative, wide dynamic range
values = st.floats(min_value=0.0, max_value=1e6, allow_nan=False,
                   allow_infinity=False)
value_lists = st.lists(values, min_size=0, max_size=200)
rel_errs = st.sampled_from([0.05, 0.01, 0.001])


def _fill(xs, rel_err):
    h = LogBucketHistogram(rel_err)
    for x in xs:
        h.observe(x)
    return h


def _same(a, b):
    return (a.buckets == b.buckets and a.zero_count == b.zero_count
            and a.count == b.count and a.min == b.min and a.max == b.max
            and abs(a.sum - b.sum) <= 1e-9 * max(abs(a.sum), abs(b.sum), 1.0))


@settings(max_examples=100, deadline=None)
@given(xs=value_lists, ys=value_lists, rel_err=rel_errs)
def test_merge_commutes_and_equals_concatenation(xs, ys, rel_err):
    ab = _fill(xs, rel_err).merge(_fill(ys, rel_err))
    ba = _fill(ys, rel_err).merge(_fill(xs, rel_err))
    cat = _fill(xs + ys, rel_err)
    assert _same(ab, ba)
    assert _same(ab, cat)


@settings(max_examples=100, deadline=None)
@given(xs=value_lists, ys=value_lists, zs=value_lists, rel_err=rel_errs)
def test_merge_is_associative(xs, ys, zs, rel_err):
    left = _fill(xs, rel_err).merge(_fill(ys, rel_err)) \
                             .merge(_fill(zs, rel_err))
    right_inner = _fill(ys, rel_err).merge(_fill(zs, rel_err))
    right = _fill(xs, rel_err).merge(right_inner)
    assert _same(left, right)


@settings(max_examples=100, deadline=None)
@given(xs=st.lists(values, min_size=1, max_size=200),
       q=st.floats(min_value=0.0, max_value=100.0),
       rel_err=rel_errs)
def test_quantile_within_relative_error_of_nearest_rank(xs, q, rel_err):
    h = _fill(xs, rel_err)
    exact = percentile(xs, q)
    approx = h.quantile(q)
    # 1e-9 absolute slack covers float round-off in gamma powers near zero
    assert abs(approx - exact) <= rel_err * abs(exact) + 1e-9


@settings(max_examples=100, deadline=None)
@given(obs=st.lists(st.tuples(st.floats(min_value=0.0, max_value=100.0,
                                        allow_nan=False),
                              values),
                    min_size=0, max_size=300),
       window=st.sampled_from([0.25, 1.0, 3.0]),
       n_windows=st.integers(min_value=1, max_value=8))
def test_window_rotation_never_loses_counts(obs, window, n_windows):
    w = WindowedHistogram(window=window, n_windows=n_windows, rel_err=0.01)
    for i, (t, v) in enumerate(obs):
        w.observe(t, v)
        live = w.live_count  # lazy expiry may move counts into dropped
        assert w.total.count == w.dropped + live == i + 1


@settings(max_examples=50, deadline=None)
@given(obs=st.lists(st.tuples(st.floats(min_value=0.0, max_value=10.0,
                                        allow_nan=False),
                              values),
                    min_size=1, max_size=200))
def test_windowed_quantile_matches_merged_population(obs):
    # a horizon wide enough to hold every observation: merged() must see
    # the full population, and its quantiles obey the bucket bound
    w = WindowedHistogram(window=1.0, n_windows=11, rel_err=0.01)
    for t, v in obs:
        w.observe(t, v)
    assert w.dropped == 0 and w.live_count == len(obs)
    xs = [v for _, v in obs]
    exact = percentile(xs, 99)
    assert abs(w.quantile(99) - exact) <= 0.01 * abs(exact) + 1e-9
