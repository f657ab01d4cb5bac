"""bench/phases.py: the engine's host time per iteration and the device's
idle time by host phase, on a made-up trace and on a recorded one."""
import pytest

from bench import layer_metrics, phases
from bench import trace_reduce as tr

US = 1000  # ns


def op(t, d, plane="/device:TPU:0"):
    return {"plane": plane, "line": tr.OP_LINE, "name": "fusion.1", "t": t * US,
            "d": d * US, "kernel": False}


def host(name, t0, t1):
    return {"name": name, "t": t0 * US, "d": (t1 - t0) * US}


def engine_trace():
    """A 1,000 us window, two engine iterations and the start of a third.
    Device idle: [20, 120], [240, 340], [360, 610], [880, 995]."""
    return {
        "device": [op(-50, 70), op(120, 120), op(340, 20), op(610, 270), op(995, 50)],
        "host": [
            host("bench.window", 0, 1000),
            host("bench.iterate", 0, 480),
            host("serve.iterate", 10, 470),
            host("serve.admit", 10, 30),
            host("serve.decode_step", 40, 300),
            host("serve.decode_step.args", 40, 60),
            host("serve.decode_step.launch", 60, 100),
            host("serve.decode_step.wait", 100, 250),
            host("serve.occupancy", 300, 460),
            host("serve.occupancy.wait", 320, 400),
            host("serve.occupancy.wait", 330, 350),   # nested in the one above
            host("serve.occupancy.wait", 380, 420),   # overlaps it
            host("bench.iterate", 500, 990),
            host("serve.iterate", 500, 980),
            host("serve.prefill_chunk", 510, 900),
            host("serve.prefill_chunk.args", 510, 520),
            host("serve.prefill_chunk.launch", 520, 600),
            host("serve.prefill_chunk.wait", 600, 890),
            host("serve.iterate", 990, 1100),         # cut by the window's end
        ],
    }


def test_idle_intervals():
    got = [t for gap in phases.idle(engine_trace()) for t in gap]
    assert got == pytest.approx([20e-6, 120e-6, 240e-6, 340e-6, 360e-6, 610e-6,
                                 880e-6, 995e-6])


def test_engine_host_ms_subtracts_the_union_of_waits():
    # [10, 470]: 460 less the waits [100, 250] and [320, 420] = 210;
    # [500, 980]: 480 less [600, 890] = 190; [990, 1100] is not whole
    spans = phases.host_spans(engine_trace())
    assert phases.engine_host_ms(spans, 1000e-6) == pytest.approx(0.2)


def test_idle_by_phase_cuts_each_gap_at_the_spans():
    t = engine_trace()
    got = phases.idle_by_phase(phases.host_spans(t), phases.idle(t))
    want = {
        "serve.admit": 10, "serve.decode_step.args": 20, "serve.decode_step.launch": 40,
        "serve.decode_step.wait": 30, "serve.decode_step": 50, "serve.occupancy": 60,
        "serve.occupancy.wait": 80, "serve.prefill_chunk.args": 10,
        "serve.prefill_chunk.launch": 80, "serve.prefill_chunk.wait": 20,
        "serve.prefill_chunk": 10, "serve.iterate": 115, "bench.iterate": 20,
        "bench.window": 20,
    }
    assert got == pytest.approx({k: v * 1e-6 for k, v in want.items()})


def test_idle_host_share_counts_engine_work_outside_waits():
    # idle inside serve.iterate and in no wait: 80 + 70 + 150 + 95 of 1,000 us
    s = phases.summary(engine_trace())
    assert s["idle_host_share"] == pytest.approx(39.5)
    assert s["idle_s"] == pytest.approx(565e-6)
    assert s["iterations"] == 2 and s["engine_host_ms"] == pytest.approx(0.2)


def test_the_reduction_names_gaps_by_phase():
    """The reduction's breakdown names each gap by the span over its middle,
    which is now a phase of the engine loop."""
    got = sorted((n, round(s * 1e6)) for n, s in tr.reduce(engine_trace()).gaps)
    assert got == [("bench.window", 250), ("serve.decode_step", 100),
                   ("serve.decode_step.launch", 100), ("serve.iterate", 115)]


def test_nothing_is_read_without_a_device():
    t = engine_trace()
    t["device"] = []
    assert phases.idle(t) is None
    s = phases.summary(t)
    assert "idle_s" not in s and s["engine_host_ms"] == pytest.approx(0.2)


# --------------------------------------------------------------------------- #
# a recorded trace with the engine-loop spans: qwen3-1.7b.longdoc on one TPU
# v5e, cut to one prefill and two decode executions
# (bench/tests/data/trace_qwen3_longdoc_spans.json.gz)
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def recorded():
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_qwen3_longdoc_spans.json.gz")
    return tr.read_events(path)


def _sweep(events, lo, hi, count_device):
    """Time in [lo, hi] (ns) with no device op running (if ``count_device``),
    the host inside a serve.iterate and in no .wait: one pass over every
    edge, independent of bench/phases.py."""
    edges = []
    for d in events["device"]:
        if count_device and d["line"] == tr.OP_LINE and d["plane"] == "/device:TPU:0":
            edges += [(d["t"], "dev", 1), (d["t"] + d["d"], "dev", -1)]
    for h in events["host"]:
        kind = ("it" if h["name"] == "serve.iterate" else
                "wait" if h["name"].startswith("serve.") and h["name"].endswith(".wait")
                else None)
        if kind:
            edges += [(h["t"], kind, 1), (h["t"] + h["d"], kind, -1)]
    edges.append((hi, "dev", 0))
    depth = {"dev": 0, "it": 0, "wait": 0}
    total, prev = 0, lo
    for t, kind, step in sorted(edges, key=lambda e: (e[0], e[2])):
        t = min(max(t, lo), hi)
        if depth["dev"] == 0 and depth["it"] > 0 and depth["wait"] == 0:
            total += t - prev
        prev = t
        depth[kind] += step
    return total * 1e-9


def test_recorded_idle_host_share(recorded):
    w0, w1 = phases.window(recorded)
    s = phases.summary(recorded)
    want = 100.0 * _sweep(recorded, w0, w1, True) / ((w1 - w0) * 1e-9)
    assert s["idle_host_share"] == pytest.approx(want)
    assert 0.0 < s["idle_host_share"] < 100.0 * s["idle_s"] / s["window_s"]
    assert s["idle_s"] == pytest.approx(tr.reduce(recorded).window_s
                                        - tr.reduce(recorded).busy_s)


def test_recorded_engine_host_ms(recorded):
    w0, w1 = phases.window(recorded)
    its = [h for h in recorded["host"] if h["name"] == "serve.iterate"
           and w0 <= h["t"] and h["t"] + h["d"] <= w1]
    assert its
    want = 1e3 * sum(_sweep(recorded, h["t"], h["t"] + h["d"], False)
                     for h in its) / len(its)
    assert phases.summary(recorded)["engine_host_ms"] == pytest.approx(want)


def test_recorded_gaps_carry_phase_names(recorded):
    red = tr.reduce(recorded)
    # the two longest: the prefill's launch blocks behind the slot reset,
    # the decode's behind the prefill
    assert {n for n, _ in red.gaps[:2]} == {"serve.prefill_chunk.launch",
                                           "serve.decode_step.launch"}


def test_every_reader_reads_the_recorded_span_trace(recorded):
    """Each per-layer metric of the cell finds its numbers in a chip trace
    of the program with its engine-loop spans (the work records here are
    made up; the trace is real)."""
    from bench import run

    cell = run.load_cell("qwen3-1.7b.longdoc")
    its = [run.Iteration(0.0, 1.0, [(8192, 8704, False), (512, 1024, False)],
                         [9000, 12000]),
           run.Iteration(1.0, 1.1, [], [9001, 12001, 7000])]
    ctx = layer_metrics.Context(cell=cell, reduced=tr.reduce(recorded),
                                iterations=its, chips=1, device_kind="TPU v5 lite")
    got = layer_metrics.read_all(cell.per_layer, ctx)
    assert set(got) == set(cell.per_layer)
    for name, v in got.items():
        assert 0.0 < v < 100.0 or name.endswith("_ms"), (name, v)
