"""Traced runs: host spans on the profiler's clock, and the reduction of the
profiler's trace to the numbers that per-layer metrics read.

In a traced run the program's own ``Tracer`` spans (``search``,
``dataset.take:*``, ``drain:*``, ...) and the spans the harness opens around
calls into the program (named by the metric files' ``WRAP``) are written as
``jax.profiler.TraceAnnotation`` events, so host spans and device operations
share one clock.  :func:`load` reads the ``.xplane.pb`` file back into a
:class:`TraceView`; the metric files read only that view.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import importlib
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "(no span)"


# -- host spans ---------------------------------------------------------------

class _Annotation:
    """A profiler annotation that also takes the ``set()`` calls the
    program makes on its spans."""

    __slots__ = ("_ann",)

    def __init__(self, name: str):
        import jax

        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        return self._ann.__exit__(*exc)

    def set(self, **_) -> None:
        pass


def profiler_tracer():
    """A tracer for the program whose spans are profiler annotations.

    Counters (``decode.fallback.*``) are kept in its ``metrics``; events
    are not kept in memory."""
    from repro.obs import Tracer

    class ProfilerTracer(Tracer):
        def span(self, name, cat="io", tid=None, **args):
            return _Annotation(name)

        def track(self, key):
            return self.tid

        def instant(self, *args, **kwargs):
            pass

        def counter(self, *args, **kwargs):
            pass

    return ProfilerTracer()


def _resolve(target: str) -> Tuple[object, str]:
    """``"pkg.mod:Name.attr"`` -> (owner object, attribute name)."""
    mod, _, path = target.partition(":")
    owner = importlib.import_module(mod)
    *parents, attr = path.split(".")
    for p in parents:
        owner = getattr(owner, p)
    return owner, attr


@contextlib.contextmanager
def wrapped(spans: Dict[str, str], hooks: Dict[str, List[Callable]],
            work: Dict[str, list]):
    """Open a span named by ``spans``' key around each call of the program
    callable its value names, for the duration of the block.

    The span waits for the call's device results (``block_until_ready``), so
    it covers padding, packing, transfers and the wait.  After the span,
    each hook ``(metric_name, fn)`` registered for the callable is given
    ``(args, kwargs, result)`` and its return value is appended to
    ``work[metric_name]``."""
    import jax

    saved = []
    try:
        for name, target in spans.items():
            owner, attr = _resolve(target)
            orig = getattr(owner, attr)
            its_hooks = hooks.get(target, [])

            def make(orig=orig, name=name, its_hooks=its_hooks):
                @functools.wraps(orig)
                def call(*args, **kwargs):
                    with _Annotation(name):
                        out = orig(*args, **kwargs)
                        jax.block_until_ready(out)
                    for metric, fn in its_hooks:
                        work[metric].append(fn(args, kwargs, out))
                    return out
                return call

            saved.append((owner, attr, orig))
            setattr(owner, attr, make())
        yield
    finally:
        for owner, attr, orig in reversed(saved):
            setattr(owner, attr, orig)


@contextlib.contextmanager
def profiled(log_dir: str):
    """Profile the block (no Python tracer) and mark it as the window."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with _Annotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


# -- reduction ----------------------------------------------------------------

@dataclass
class TraceView:
    """What one traced window holds, in seconds on the profiler's clock.

    ``spans``: host spans ``(name, start, end)`` of the thread that ran the
    window; ``device``: operations ``(name, start, end)`` of one chip's
    ``XLA Ops`` line; ``modules``: executions of jitted programs
    ``(name, start, end)`` from its ``XLA Modules`` line; all clipped to the
    window.  ``work``: per-metric records from the metric files' work hooks;
    ``n_requests``: requests completed in the window."""

    window: Tuple[float, float]
    spans: List[Tuple[str, float, float]]
    device: List[Tuple[str, float, float]]
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    n_requests: int = 0
    work: Dict[str, list] = field(default_factory=dict)
    peaks: Dict[str, float] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def matching(self, prefixes: Sequence[str]) -> list:
        return [s for s in self.spans if s[0].startswith(tuple(prefixes))]

    def span_s(self, prefixes: Sequence[str]) -> float:
        """Seconds covered by spans whose name starts with a prefix (each
        instant counted once)."""
        return union_length([(a, b) for _, a, b in self.matching(prefixes)])

    def self_s(self, prefixes: Sequence[str],
               children: Sequence[str]) -> float:
        """Seconds inside spans named by ``prefixes`` that no span named by
        ``children`` covers (the outer spans' self time)."""
        outer = self.matching(prefixes)
        if not outer:
            return 0.0
        a, b = np.array([(x, y) for _, x, y in outer]).T
        kids = merged([(x, y) for _, x, y in self.matching(children)])
        return float((b - a).sum() - covered(kids, a, b).sum())

    def per_request_ms(self, seconds: float) -> Optional[float]:
        if self.n_requests <= 0:
            return None
        return 1e3 * seconds / self.n_requests

    @property
    def busy_s(self) -> float:
        return union_length([(a, b) for _, a, b in self.device])

    def module_s(self, programs: Sequence[str]) -> float:
        """Summed device time of the executions of the jitted programs named
        (``jit_<function>``)."""
        return sum(b - a for n, a, b in self.modules
                   if n.split("(")[0] in programs)

    def roofline_pct(self, metric: str, programs: Sequence[str]
                     ) -> Optional[float]:
        """Least time for the recorded work over the device time of the
        kernel's programs, in percent; ``None`` when either is absent."""
        t = self.module_s(programs)
        calls = self.work.get(metric) or []
        if t <= 0 or not calls:
            return None
        pf, pb = self.peaks["flops_s"], self.peaks["hbm_bytes_s"]
        least = sum(max(f / pf, b / pb) for f, b in calls)
        return 100.0 * least / t

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """Intervals of the window in which no device operation ran."""
        lo, hi = self.window
        gaps, cur = [], lo
        for a, b in merged([(a, b) for _, a, b in self.device]):
            if a > cur:
                gaps.append((cur, min(a, hi)))
            cur = max(cur, b)
        if cur < hi:
            gaps.append((cur, hi))
        return [(a, b) for a, b in gaps if b > a]

    def timeline(self) -> List[Tuple[float, float, str]]:
        """The window cut into segments, each named by the innermost host
        span open in it (host spans of one thread nest), or
        ``"(no span)"``."""
        lo, hi = self.window
        segs, stack, cur = [], [], lo

        def advance(t):
            nonlocal cur
            while stack and stack[-1][2] <= t:
                if stack[-1][2] > cur:
                    segs.append((cur, stack[-1][2], stack[-1][0]))
                    cur = stack[-1][2]
                stack.pop()
            if t > cur:
                segs.append((cur, t, stack[-1][0] if stack else NO_SPAN))
                cur = t

        for span in sorted(self.spans, key=lambda s: (s[1], -s[2])):
            advance(span[1])
            stack.append(span)
        advance(hi)
        return segs

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device operations that took most time, and the idle time by
        the innermost host span open during it."""
        ops = defaultdict(float)
        for n, a, b in self.device:
            ops[op_label(n)] += b - a
        idle = defaultdict(float)
        gaps = self.idle_gaps()
        i = 0
        for a, b, name in self.timeline():
            while i < len(gaps) and gaps[i][1] <= a:
                i += 1
            j = i
            while j < len(gaps) and gaps[j][0] < b:
                idle[name] += min(b, gaps[j][1]) - max(a, gaps[j][0])
                j += 1
        rank = lambda d: [[k, v] for k, v in  # noqa: E731
                          sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(ops), "idle_gaps": rank(idle)}


def op_label(name: str) -> str:
    """An ``XLA Ops`` event name without operands and layouts:
    ``%fusion.1 = u32[262144] fusion``."""
    m = re.match(r"(%\S+) = (\(.*?\)|\S+) ([\w-]+)",
                 re.sub(r"\{[^}]*\}", "", name))
    return " ".join([m.group(1), "=", m.group(2), m.group(3)]) if m else name


def merged(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_length(intervals) -> float:
    return sum(b - a for a, b in merged(intervals))


def covered(disjoint: List[Tuple[float, float]], a: np.ndarray,
            b: np.ndarray) -> np.ndarray:
    """Length of ``[a_i, b_i)`` that the sorted, disjoint intervals cover."""
    if not disjoint:
        return np.zeros(len(a))
    s, e = np.array(disjoint).T
    cum = np.concatenate([[0.0], np.cumsum(e - s)])

    def upto(x):  # covered length of (-inf, x)
        i = np.searchsorted(s, x, side="right")  # intervals starting <= x
        last = np.clip(i - 1, 0, None)
        part = np.where(i > 0, np.clip(x - s[last], 0, (e - s)[last]), 0.0)
        return cum[last] * (i > 0) + part

    return upto(b) - upto(a)


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def load(log_dir: str, device_id: int = 0) -> TraceView:
    """Read the newest ``.xplane.pb`` under ``log_dir``: host spans of the
    thread that opened the window span, and one TPU's operations and
    program executions."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no profile written under {log_dir}")
    pd = ProfileData.from_file(files[-1])

    def events(line):
        return [(ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]

    spans, lines = None, {}
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = events(line)
                if any(n == WINDOW_SPAN for n, _, _ in evs):
                    spans = evs
        elif plane.name == f"{DEVICE_PLANE}{device_id}":
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = events(line)
    if spans is None:
        raise RuntimeError("the trace holds no window span")
    lo, hi = next((a, b) for n, a, b in spans if n == WINDOW_SPAN)

    def clip(evs):
        return [(n, *c) for n, a, b in evs if n != WINDOW_SPAN
                for c in [_clip(a, b, lo, hi)] if c]

    return TraceView((lo, hi), clip(spans), clip(lines.get(OPS_LINE, [])),
                     clip(lines.get(MODULES_LINE, [])))
