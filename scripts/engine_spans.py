"""What the engine-loop spans show and what they cost, on one benchmark cell:

    python3 scripts/engine_spans.py --workload <cell> --seed <n> --seconds <s> \\
        [--trace 1 --events OUT.json.gz] [--telemetry 0]

from the root of a checkout, on the chip. It runs the cell as
``python3 -m bench.run`` does (``bench.run.run_cell``), through
``Options.engine_hook``, which times every ``Engine._iterate`` and, with
``--telemetry 0``, rebuilds the engine's telemetry disabled. The last line
of standard output is one JSON object: the run's result, and the host time
of the window's decode-only iterations (no prefill dispatch in them), and
the share of prefill rows without a prompt token (``prefill_rows_skipped``
over the rows dispatched) in set-up and in the window. With
``--trace 1`` it also holds ``bench/phases.py``'s summary of the traced
window (the engine's host time per iteration, the device's idle time by
host phase) and, with ``--events``, writes the trace's events (the format of
``bench/tests/data/*.json.gz``).
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quartiles(xs):
    if len(xs) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    return {"n": len(xs), "q1": q1, "median": q2, "q3": q3, "mean": statistics.fmean(xs)}


def measure(cell, seed: int, seconds: float, trace: bool, telemetry: bool,
            events_path=None) -> dict:
    import jax

    from bench import phases, run, trace_reduce

    iterations = []   # (begin, end, prefill dispatches, rows skipped in it)
    rows = {}         # chunk rows per prefill dispatch (slots x chunk)
    marks = {}        # when the profiler started and stopped

    def hook(eng):
        if not telemetry:
            eng.config = eng.config.replace(telemetry=False)
            eng.reset_stats()
        inner = eng._iterate
        rows["per_dispatch"] = eng.slots * eng.chunk

        def iterate(sched):
            n, t0 = eng.stats["prefill_dispatches"], time.perf_counter()
            k = eng.stats["prefill_rows_skipped"]
            inner(sched)
            iterations.append((t0, time.perf_counter(),
                               eng.stats["prefill_dispatches"] - n,
                               eng.stats["prefill_rows_skipped"] - k))

        eng._iterate = iterate

    start, stop, reduce_dir = (jax.profiler.start_trace, jax.profiler.stop_trace,
                               trace_reduce.reduce_dir)
    summary = {}

    def start_trace(d, *a, **k):
        start(d, *a, **k)
        marks["open"] = time.perf_counter()

    def stop_trace():
        marks["close"] = time.perf_counter()
        stop()

    def keep(directory):
        events = trace_reduce.load_events(trace_reduce.find_xplane(directory))
        summary.update(phases.summary(events))
        if events_path:
            with gzip.open(events_path, "wt") as f:
                json.dump(events, f)
        return trace_reduce.reduce(events)

    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace
    trace_reduce.reduce_dir = keep
    try:
        result = run.run_cell(cell, seed, seconds, trace, run.Options(engine_hook=hook))
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
        trace_reduce.reduce_dir = reduce_dir
    if trace:
        t_open, t_close = marks["open"], marks["close"]
    else:  # the window opens at set-up's end and closes with the last iteration
        t_open = run.T_START + result["metrics"]["setup_s"]["value"]
        t_close = float("inf")
    inside = [it for it in iterations if it[0] >= t_open and it[1] <= t_close]
    setup = [it for it in iterations if it[1] <= t_open]

    def skipped(its):
        dispatched = sum(it[2] for it in its) * rows["per_dispatch"]
        n = sum(it[3] for it in its)
        return {"prefill_dispatches": sum(it[2] for it in its),
                "rows_dispatched": dispatched, "prefill_rows_skipped": n,
                "share": n / dispatched if dispatched else None}

    out = {"workload": cell.name, "seed": seed, "trace": int(trace),
           "telemetry": int(telemetry), "result": result,
           "decode_iteration_s": _quartiles([it[1] - it[0] for it in inside
                                             if not it[2]]),
           "iterations": len(inside),
           # prefill rows with no prompt token, attended as q_pos = -1
           "rows_skipped": {"setup": skipped(setup), "window": skipped(inside)}}
    if trace:
        out["phases"] = summary
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--telemetry", type=int, choices=(0, 1), default=1)
    ap.add_argument("--events", default=None,
                    help="with --trace 1: write the trace's events here (.json.gz)")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run

    cell = run.load_cell(args.workload)
    # the compile cache bench.run uses: a fixed path inside the checkout
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from repro.launch.device import enable_compile_cache

    enable_compile_cache()
    out = measure(cell, args.seed, args.seconds, bool(args.trace),
                  bool(args.telemetry), args.events)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
