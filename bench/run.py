#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on, which
must hold a TPU with as many chips as the cell asks for (otherwise it exits
with code 1 and prints no result).  Set-up makes the cell's data from the
seed, builds the system under test through the program's own entry points
and warms up every program the traffic uses; then one client drives a
closed loop for ``--seconds``.  After the window every answer is compared
with the plain reference (``bench/reference/``), and the last line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and, traced, ``breakdown``), the numbers
compared beside their limits last under ``checks``.  The same numbers are
the last lines of standard error.

``--trace 0`` reports the cell's end-to-end metrics (host clock, profiler
off); ``--trace 1`` profiles the window and reports its per-layer metrics,
reduced from the trace by the files under ``bench/metrics/``.
"""

import time

T0 = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import trace as tr  # noqa: E402
from harness.cell import Cell, load_cell  # noqa: E402

CACHE_DIR = BENCH / ".jax_cache"  # fixed: the path is part of the cache key
TRACE_DIR = BENCH / ".trace"


class NoChip(RuntimeError):
    pass


def require_chips(n: int) -> list:
    """The TPU devices a cell runs on; raises :class:`NoChip` without them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:n]


class Loads:
    """Programs the backend produced (compiled or loaded from the
    persistent cache) since this object was made, by name."""

    def __init__(self):
        import jax

        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def n(self) -> int:
        return len(self.names)

    def _on(self, event: str, duration: float, fun_name: str = "?",
            **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(fun_name)


@dataclass
class Window:
    """What the measured window did, on the host clock."""

    setup_s: float
    window_s: float = 0.0
    latencies_s: list = field(default_factory=list)
    units: int = 0
    reqs: list = field(default_factory=list)
    results: list = field(default_factory=list)
    failed: int = 0

    @property
    def n_requests(self) -> int:
        return len(self.latencies_s)


def warm_up(cell: Cell, sut, data: dict, seed: int, loads: Loads) -> list:
    """Serve the requests the system picks to reach every padded shape of a
    finite pool of traffic (``warm`` in the system, ``pool`` in the
    generator, where both are given), then warm-up requests until ``quiet``
    in a row load no new program (or ``max`` of them); returns the programs
    each request loaded."""
    quiet, most = (int(cell.traffic["warmup"][k]) for k in ("quiet", "max"))
    gen, mix = cell.generator, cell.traffic
    first = []
    if hasattr(cell.system, "warm") and hasattr(gen, "pool"):
        first = cell.system.warm(sut, gen.pool(mix, data, seed),
                                 mix["warmup"])
    stream = gen.requests(mix, data, seed, warmup=True)
    loaded = []

    def serve(req):
        before = loads.n
        cell.system.serve(sut, req)
        loaded.append(loads.n - before)

    for req in first:
        serve(req)
    tail = loaded[len(first):]
    while len(tail) < most and (len(tail) < quiet or any(tail[-quiet:])):
        serve(next(stream))
        tail = loaded[len(first):]
    return loaded


def drive(cell: Cell, sut, data: dict, seed: int, seconds: float,
          win: Window) -> None:
    """Closed loop, one client: the next request goes when the last one
    returned, until ``seconds`` have passed; the window ends when the last
    request returns."""
    stream = cell.generator.requests(cell.traffic, data, seed)
    clock = time.perf_counter
    start = clock()
    end = start + seconds
    while clock() < end:
        req = next(stream)
        t = clock()
        try:
            res, n = cell.system.serve(sut, req)
        except Exception:  # a failed request is counted, and not correct
            traceback.print_exc()
            res, n = None, 0
            win.failed += 1
        win.latencies_s.append(clock() - t)
        win.units += n
        win.reqs.append(req)
        win.results.append(res)
    win.window_s = clock() - start


def hooks_of(cell: Cell) -> tuple:
    """Spans to open around program callables (``WRAP``: span name ->
    ``"module:attr"``), and work hooks (``WORK_OF`` + ``work``), as the
    cell's metric files declare them."""
    spans, hooks = {}, {}
    for name, mod in cell.metric_files.items():
        for span, target in getattr(mod, "WRAP", {}).items():
            if spans.setdefault(span, target) != target:
                raise ValueError(f"span {span!r} names two callables")
        if hasattr(mod, "WORK_OF"):
            hooks.setdefault(mod.WORK_OF, []).append((name, mod.work))
    targets = list(spans.values())
    if len(set(targets)) != len(targets):
        raise ValueError("a callable is named by two spans")
    missing = set(hooks) - set(targets)
    if missing:
        raise ValueError(f"work hooks on unwrapped callables: {missing}")
    return spans, hooks


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def run(cell: Cell, seed: int, seconds: float, trace: bool, devs: list,
        trace_dir: Path = TRACE_DIR,
        say=lambda s: print(s, file=sys.stderr, flush=True)) -> dict:
    """Set up, warm up, drive the window, check; returns the result line."""
    loads = Loads()
    t = [time.perf_counter()]
    data = cell.system.generate(cell.config, cell.traffic, seed)
    t.append(time.perf_counter())
    tracer = tr.profiler_tracer() if trace else None
    sut = cell.system.build(cell.config, data, seed, tracer)
    t.append(time.perf_counter())
    spans, hooks = hooks_of(cell) if trace else ({}, {})
    work = {name: [] for name in cell.metric_files}
    with tr.wrapped(spans, hooks, work):
        warm = warm_up(cell, sut, data, seed, loads)
        for v in work.values():
            v.clear()
        win = Window(setup_s=time.perf_counter() - T0)
        loads0 = loads.n
        shutil.rmtree(trace_dir, ignore_errors=True)
        profile = (tr.profiled(str(trace_dir)) if trace
                   else contextlib.nullcontext())
        with profile:
            drive(cell, sut, data, seed, seconds, win)
    window_loads = loads.names[loads0:]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    say(f"set-up phases: start {t[0] - T0:.3f} s, generate "
        f"{t[1] - t[0]:.3f} s, build {t[2] - t[1]:.3f} s, warm-up "
        f"{T0 + win.setup_s - t[2]:.3f} s")
    say(f"warm-up: {len(warm)} requests, programs loaded by each: {warm}")
    say(f"set-up {win.setup_s:.3f} s; window "
        f"{win.window_s:.3f} s, {win.n_requests} requests, {win.units} units, "
        f"{win.failed} failed; programs loaded in the window: "
        f"{len(window_loads)}")
    if window_loads:
        say(f"WARNING: warm-up missed {len(window_loads)} program(s), loaded "
            f"inside the timed window: {window_loads}")
    fallbacks = {}
    if tracer is not None:
        fallbacks = {k: v for k, v in tracer.metrics.counter_values(
            "decode.fallback").items() if v}
        say(f"decode.fallback counters: {fallbacks or 'none moved'}")

    # the reference runs once the program's state is gone
    got = [cell.system.plain(r) if r is not None else None
           for r in win.results]
    del sut, tracer, win.results
    gc.collect()
    served = [(q, g) for q, g in zip(win.reqs, got) if g is not None]
    numbers = cell.reference.check(data, [q for q, _ in served],
                                   [g for _, g in served])
    limits = cell.config["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    say("checked: " + ", ".join(f"{k} {v}" for k, v in numbers.items()
                                if k not in limits))
    correct = (win.failed == 0 and win.n_requests > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": win.n_requests,
           "failed": win.failed, "window_loads": window_loads}
    if trace:
        view = tr.load(str(trace_dir))
        view.n_requests = win.n_requests
        view.work = work
        view.peaks = peaks_for(dev.device_kind)
        metrics = {}
        for m in cell.per_layer:
            v = cell.metric_files[m["name"]].read(view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=view.busy_s, window_s=view.window_s)
        breakdown = view.breakdown()
        if fallbacks:
            breakdown["fallbacks"] = [[k, v] for k, v in fallbacks.items()]
        if window_loads:
            breakdown["window_loads"] = window_loads
        out.update(metrics=metrics, device=device, breakdown=breakdown)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {}
        for m in cell.end_to_end:
            v = cell.metric_files[m["name"]].read(win)
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out.update(metrics=metrics, device=device)
    out["checks"] = checks
    for k, c in checks.items():
        say(f"check {k}: {c['value']} (limit {c['limit']})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # before JAX is imported: the cache lives in the checkout, and the TPU
    # runtime writes no logs to a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = load_cell(args.workload, ROOT)
    try:
        devs = require_chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    from repro.runtime import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    out = run(cell, args.seed, args.seconds, bool(args.trace), devs)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
