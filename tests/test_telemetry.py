"""Telemetry conformance suite (DESIGN.md §13).

Pins the observability contract of the serving stack:

  * typed metrics — the registry's declared-at-init discipline (undeclared
    names raise ``UndeclaredMetric``, the regression for the old ad-hoc
    ``Engine.stats`` dict any component could mint keys into), bounded
    histogram reservoirs, gauge peaks, and the ``StatsView`` compatibility
    facade;
  * observer effect — token streams are bit-identical with telemetry on vs
    off, for the plain AND the speculative engine (the clock never touches
    numerics), and the disabled path really is a no-op (no stamps, no
    reservoir growth, no trace events);
  * request-lifecycle tracing — stamps are monotonic
    (submit <= admit <= prefill_done <= first_token <= complete), TTFT
    decomposes into queue + prefill + first-decode, and the exported
    Chrome-trace event stream is well-formed (schema, monotonic ts,
    matched begin/end) and survives a JSONL round-trip;
  * occupancy — the uniform cache occupancy keys are populated for all
    three cache families (ring-paged, recurrent-state, hybrid-window).
"""
import contextlib
import glob
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import get_model, init_params
from repro.serve import (Engine, EngineConfig, Request, SamplingParams,
                         UndeclaredMetric)
from repro.serve import engine as engine_mod
from repro.serve import speculative
from repro.serve.cache import paged, protocol
from repro.serve.telemetry import (MetricsRegistry, StatsView,
                                   validate_chrome_events)


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen3-1.7b")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(get_model(cfg).param_specs(cfg), jax.random.PRNGKey(0))


def _requests():
    """Ragged mix with readmission pressure (4 requests, 2 slots below)."""
    return [
        Request(prompt=np.arange(1, 20), max_new_tokens=6,
                sampling=SamplingParams(temperature=0.9, seed=7)),
        Request(prompt=np.array([5, 11, 2]), max_new_tokens=4),
        Request(prompt=np.arange(2, 12), max_new_tokens=5,
                sampling=SamplingParams(temperature=1.0, top_k=5, seed=3)),
        Request(prompt=np.array([9]), max_new_tokens=3),
    ]


def _engine(cfg, params, **kw):
    base = dict(slots=2, max_len=64, chunk=8)
    base.update(kw)
    return Engine(cfg, params, EngineConfig(**base))


def _tracer_spans(tel):
    """(name, begin s, end s) of the engine-lane spans, in recording order
    (``Tracer.span`` appends each begin event right before its end)."""
    evs = list(tel.trace.events)
    return [(b["name"], b["ts"] * 1e-6, e["ts"] * 1e-6)
            for b, e in zip(evs, evs[1:])
            if b["ph"] == "B" and b["tid"] == tel.ENGINE_TID]


# --------------------------------------------------------------------------- #
# typed metrics registry
# --------------------------------------------------------------------------- #
def test_undeclared_metric_raises(cfg, params):
    """Regression: components can no longer invent stats keys.

    The pre-telemetry engine's ``stats`` was a plain dict, so a typo'd or
    invented key silently forked the schema; every surface must now raise.
    """
    eng = _engine(cfg, params)
    with pytest.raises(UndeclaredMetric):
        eng.stats["invented_key"]
    with pytest.raises(UndeclaredMetric):
        eng.stats["invented_key"] = 1
    with pytest.raises(UndeclaredMetric):
        eng.telemetry.metrics.inc("invented_key")
    with pytest.raises(UndeclaredMetric):
        eng.telemetry.metrics.observe("invented_seconds", 0.1)
    # UndeclaredMetric is a KeyError: dict-era callers catching KeyError
    # (or using ``"x" in stats``) keep working
    assert issubclass(UndeclaredMetric, KeyError)
    assert "invented_key" not in eng.stats


def test_reset_stats_declares_every_writer_key(cfg, params):
    """Every counter any component writes — including the speculative keys
    SpecDecoder increments — exists (zeroed) right after reset_stats."""
    eng = _engine(cfg, params, spec_k=2)
    eng.run(_requests())
    assert eng.stats["spec_rounds"] > 0
    eng.reset_stats()
    for key in ("prefill_dispatches", "decode_dispatches", "prefill_tokens",
                "prefill_rows_skipped", "generated_tokens", "requests_completed", "spec_rounds",
                "draft_dispatches", "verify_dispatches", "spec_drafted_tokens",
                "spec_accepted_tokens", "spec_emitted_tokens"):
        assert eng.stats[key] == 0, key
    assert eng.stats["decode_step_seconds"] == []
    # and the engine can serve again with the fresh registry
    done = eng.run(_requests()[:1])
    assert len(done) == 1 and eng.stats["spec_rounds"] >= 0


def test_prefill_rows_skipped_counts_rows_without_a_prompt_token(cfg, params):
    """``prefill_rows_skipped`` adds ``slots * chunk - num_valid.sum()`` per
    prefill dispatch — the rows that attend as q_pos = -1 — and is in the
    engine's stats, with telemetry on and off (a plain counter)."""
    for telemetry in (True, False):
        eng = _engine(cfg, params, slots=3, telemetry=telemetry)
        plans, prefill = [], eng._prefill

        def spy(params, tree, tokens, num_valid, prefill=prefill, plans=plans):
            plans.append(np.asarray(num_valid))
            return prefill(params, tree, tokens, num_valid)

        eng._prefill = spy
        eng.run(_requests())
        want = sum(eng.slots * eng.chunk - int(nv.sum()) for nv in plans)
        assert len(plans) == eng.stats["prefill_dispatches"] > 1
        assert eng.stats["prefill_rows_skipped"] == want > 0
        assert eng.stats["prefill_tokens"] + want == \
            len(plans) * eng.slots * eng.chunk
        snap = eng.telemetry.metrics.snapshot()["counters"]
        assert snap["prefill_rows_skipped"] == want


def test_registry_types_and_bounds():
    m = MetricsRegistry()
    m.declare_counter("n")
    m.declare_histogram("lat", maxlen=4)
    m.declare_gauge("occ")
    with pytest.raises(ValueError, match="declared twice"):
        m.declare_counter("n")
    for i in range(10):  # reservoir stays bounded; count/sum stay exact
        m.observe("lat", float(i))
    h = m.get("lat")
    assert len(h.reservoir) == 4 and h.count == 10 and h.total == 45.0
    m.set_gauge("occ", 3.0)
    m.set_gauge("occ", 1.0)
    assert m.get("occ").value == 1.0 and m.get("occ").peak == 3.0
    with pytest.raises(TypeError, match="histogram"):
        m.inc("lat")
    view = StatsView(m)
    view["n"] += 2  # the legacy read-modify-write idiom
    assert view["n"] == 2
    with pytest.raises(TypeError, match="observe-only"):
        view["lat"] = [1.0]


def test_snapshot_json_roundtrip_and_prometheus(cfg, params):
    eng = _engine(cfg, params)
    eng.run(_requests())
    snap = eng.telemetry.snapshot()
    assert json.loads(json.dumps(snap)) == snap
    assert snap["tags"]["family"] == cfg.family
    assert snap["counters"]["requests_completed"] == 4
    for name in ("ttft_seconds", "inter_token_seconds", "queue_wait_seconds",
                 "prefill_seconds", "decode_step_seconds"):
        h = snap["histograms"][name]
        assert set(h) == {"count", "sum", "mean", "p50", "p90", "p99", "max"}
    assert snap["histograms"]["ttft_seconds"]["count"] == 4
    assert snap["histograms"]["ttft_seconds"]["p99"] > 0
    text = eng.telemetry.prometheus_text()
    assert "mra_serve_requests_completed 4" in text
    assert 'mra_serve_ttft_seconds{quantile="0.99"}' in text
    assert "mra_serve_cache_pages_live" in text


def test_prefill_dispatches_are_timed(cfg, params):
    """Each prefill dispatch opens one ``serve.prefill_chunk`` span holding
    one ``serve.prefill_chunk.launch`` child, on the engine lane."""
    eng = _engine(cfg, params)
    eng.run(_requests())
    spans = _tracer_spans(eng.telemetry)
    outer = [sp for sp in spans if sp[0] == "serve.prefill_chunk"]
    launch = [sp for sp in spans if sp[0] == "serve.prefill_chunk.launch"]
    assert len(outer) == len(launch) == eng.stats["prefill_dispatches"] > 0
    for (_, a0, a1), (_, b0, b1) in zip(outer, launch):
        assert a0 <= b0 <= b1 <= a1


# --------------------------------------------------------------------------- #
# observer effect: telemetry never changes tokens
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("spec_k", [0, 2])
def test_tokens_bit_identical_with_telemetry_on_vs_off(cfg, params, spec_k):
    on = _engine(cfg, params, spec_k=spec_k, telemetry=True).run(_requests())
    off = _engine(cfg, params, spec_k=spec_k, telemetry=False).run(_requests())
    by = {len(r.prompt): r.out for r in off}
    for r in on:
        np.testing.assert_array_equal(r.out, by[len(r.prompt)])


def test_batched_equals_solo_under_telemetry(cfg, params):
    """Batched serving with full telemetry == solo serving with it disabled:
    tracing composes with the engine's core conformance guarantee."""
    batched = _engine(cfg, params, telemetry=True).run(_requests())
    by = {len(r.prompt): r.out for r in batched}
    for req in _requests():
        solo = _engine(cfg, params, telemetry=False).run([req])[0]
        np.testing.assert_array_equal(solo.out, by[len(solo.prompt)])


def test_disabled_path_is_noop(cfg, params, monkeypatch):
    """telemetry=False: counters keep counting (engine bookkeeping), but no
    stamps, no reservoir growth, no gauges, no trace events, and no span
    reaches the profiler."""
    opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name, **kw: opened.append(name))
    eng = _engine(cfg, params, telemetry=False)
    done = eng.run(_requests())
    assert opened == []
    assert eng.stats["requests_completed"] == 4
    assert eng.stats["generated_tokens"] > 0
    assert eng.stats["decode_step_seconds"] == []
    snap = eng.telemetry.snapshot()
    assert snap["histograms"]["ttft_seconds"]["count"] == 0
    assert snap["gauges"]["cache_pages_live"]["peak"] == 0.0
    assert len(eng.telemetry.trace.events) == 0
    assert all(r.trace is None for r in done)


# --------------------------------------------------------------------------- #
# request-lifecycle tracing
# --------------------------------------------------------------------------- #
def test_lifecycle_stamps_and_ttft_decomposition(cfg, params):
    eng = _engine(cfg, params)
    done = eng.run(_requests())
    for r in done:
        tr = r.trace
        assert tr is not None
        assert (tr.submit <= tr.admit <= tr.prefill_done
                <= tr.first_token <= tr.complete)
        assert len(tr.token_times) == r.max_new_tokens
        assert tr.token_times == sorted(tr.token_times)
        assert len(tr.inter_token) == r.max_new_tokens - 1
        # TTFT decomposes exactly: queue wait + prefill + first-decode gap
        parts = (tr.queue_wait + (tr.prefill_done - tr.admit)
                 + (tr.first_token - tr.prefill_done))
        assert abs(tr.ttft - parts) < 1e-9
        assert tr.ttft > 0


def test_trace_events_well_formed_and_jsonl_roundtrip(cfg, params, tmp_path):
    """The exported trace is valid Chrome-trace JSONL: schema keys present,
    timestamps monotonic, every begin matched by an end — including with
    degenerate (slotless) requests in the mix."""
    eng = _engine(cfg, params, spec_k=2)
    eng.run(_requests()
            + [Request(prompt=np.array([], np.int32), max_new_tokens=2)])
    events = eng.telemetry.trace.chrome_events()
    validate_chrome_events(events)
    names = {e["name"] for e in events}
    assert {"request", "queued", "prefill", "decode", "serve.prefill_chunk",
            "serve.draft", "serve.verify"} <= names
    # request-lifecycle spans live on the slot lanes, engine-loop spans on
    # the engine lane, so Perfetto shows per-slot timelines under the loop
    assert {e["tid"] for e in events if e["name"] == "request"} \
        <= set(range(eng.slots))
    assert all(e["tid"] == eng.telemetry.ENGINE_TID
               for e in events if e["name"].startswith("serve."))
    path = tmp_path / "trace.jsonl"
    n = eng.telemetry.trace.export_jsonl(str(path))
    from repro.serve.telemetry import load_trace_jsonl
    loaded = load_trace_jsonl(str(path))
    assert len(loaded) == n
    validate_chrome_events(loaded)


def test_spec_acceptance_series_per_slot(cfg, params):
    eng = _engine(cfg, params, spec_k=2)
    done = eng.run(_requests())
    series = eng.telemetry.snapshot()["series"]["spec_accept_by_slot"]
    assert series, "speculative engine recorded no per-slot acceptance"
    assert set(series) <= {str(s) for s in range(eng.slots)}
    total = sum(v for vs in series.values() for v in vs)
    assert total == eng.stats["spec_accepted_tokens"]
    # the per-request acceptance trace mirrors the slot series
    assert sum(a for r in done for a in r.trace.spec_accepts) == total


# --------------------------------------------------------------------------- #
# engine-loop spans, on the tracer and in the profiler's trace
# --------------------------------------------------------------------------- #
PHASES = ("serve.admit", "serve.prefill_chunk", "serve.decode_step",
          "serve.spec_round", "serve.occupancy")


def _parent(name):
    """The span a span must lie inside: phases in ``serve.iterate``, draft
    and verify in ``serve.spec_round``, ``serve.<phase>.<part>`` in its
    phase."""
    if name in PHASES:
        return "serve.iterate"
    if name in ("serve.draft", "serve.verify"):
        return "serve.spec_round"
    head = name.rsplit(".", 1)[0]
    assert head in PHASES, name
    return head


@pytest.fixture(scope="module", params=[0, 2], ids=["plain", "spec_k=2"])
def profiled(request, cfg, params, tmp_path_factory):
    """A tiny engine run under ``jax.profiler`` on the CPU: its tracer spans,
    the profiler's ``serve.*`` host spans, the number of ``_iterate`` calls,
    and where each host read of a device array happened."""
    spec_k = request.param
    _engine(cfg, params, spec_k=spec_k).run(_requests())  # compile first
    eng = _engine(cfg, params, spec_k=spec_k)
    calls, stack, reads = [0], [], []
    inner_iterate, inner_span = eng._iterate, eng.telemetry.span

    def iterate(sched):
        calls[0] += 1
        inner_iterate(sched)

    @contextlib.contextmanager
    def span(name, **args):
        stack.append(name)
        try:
            with inner_span(name, **args):
                yield
        finally:
            stack.pop()

    class Numpy:  # numpy, noting each device array the loop reads back
        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, a, *args, **kw):
            if isinstance(a, jax.Array) and stack:
                reads.append(stack[-1])
            return np.asarray(a, *args, **kw)

    eng._iterate, eng.telemetry.span = iterate, span
    d = str(tmp_path_factory.mktemp(f"profile_{spec_k}"))
    with pytest.MonkeyPatch.context() as mp:
        for mod in (engine_mod, speculative, paged, protocol):
            mp.setattr(mod, "np", Numpy())
        jax.profiler.start_trace(d)
        try:
            eng.run(_requests())
        finally:
            jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    prof = sorted((e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                  for plane in jax.profiler.ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith("serve."))
    return dict(spans=_tracer_spans(eng.telemetry), prof=prof, iterations=calls[0],
                reads=reads, spec_k=spec_k)


def test_one_iterate_span_per_engine_iteration(profiled):
    n = profiled["iterations"]
    assert n > 0
    assert sum(sp[0] == "serve.iterate" for sp in profiled["spans"]) == n
    assert sum(sp[0] == "serve.iterate" for sp in profiled["prof"]) == n


def test_every_span_lies_inside_its_parent(profiled):
    spans = profiled["spans"]
    names = {sp[0] for sp in spans}
    assert {"serve.iterate", "serve.admit", "serve.prefill_chunk.args",
            "serve.prefill_chunk.launch", "serve.prefill_chunk.sample",
            "serve.prefill_chunk.wait", "serve.occupancy.wait"} <= names
    if profiled["spec_k"]:
        assert {"serve.spec_round", "serve.spec_round.wait", "serve.draft",
                "serve.verify"} <= names
    else:
        assert {"serve.decode_step.args", "serve.decode_step.launch",
                "serve.decode_step.wait"} <= names
        assert "serve.spec_round" not in names
    for name, t0, t1 in spans:
        if name == "serve.iterate":
            continue
        outer = _parent(name)
        assert any(p0 <= t0 <= t1 <= p1 for p, p0, p1 in spans if p == outer), \
            (name, outer)
        if name.endswith(".wait"):  # a wait lies inside its dispatch span
            assert outer in ("serve.prefill_chunk", "serve.decode_step",
                             "serve.spec_round", "serve.occupancy")


def test_every_host_read_in_the_loop_is_a_wait(profiled):
    """Each device array the engine loop reads back sits in a ``.wait``."""
    assert profiled["reads"]
    assert all(name.endswith(".wait") for name in profiled["reads"]), \
        sorted(set(profiled["reads"]))


def test_tracer_spans_map_onto_the_profiler_by_one_offset(profiled):
    """The tracer's clock is process-wide: one offset maps every tracer span
    onto its profiler copy, within 0.5 ms over the whole run. The tracer
    stamps just outside the annotation, so with ``c`` the offset each copy
    starts at least ``c`` after its tracer span starts and ends at most
    ``c`` after it ends; such a ``c`` exists for every span at once."""
    prof, mine = {}, {}
    for name, t0, t1 in profiled["prof"]:
        prof.setdefault(name, []).append((t0, t1))
    for name, t0, t1 in profiled["spans"]:
        mine.setdefault(name, []).append((t0, t1))
    assert set(mine) == set(prof)
    starts, ends = [], []
    for name, spans in mine.items():
        assert len(spans) == len(prof[name]), name
        for (t0, t1), (p0, p1) in zip(sorted(spans), prof[name]):
            starts.append(p0 - t0)
            ends.append(p1 - t1)
    assert max(ends) - min(starts) < 5e-4


# --------------------------------------------------------------------------- #
# occupancy across the three cache families
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("arch,evicting", [
    ("qwen3-1.7b", True),        # RingPagedKVCache: ring eviction
    ("rwkv6-7b", False),         # RecurrentStateCache: state absorbs history
    ("recurrentgemma-9b", True),  # HybridWindowCache: window slides
])
def test_cache_occupancy_gauges_all_families(arch, evicting):
    cfg = get_smoke_config(arch)
    params = init_params(get_model(cfg).param_specs(cfg), jax.random.PRNGKey(0))
    # generate past the window so the paged/hybrid backends actually evict
    eng = Engine(cfg, params, EngineConfig(slots=2, max_len=32, chunk=8))
    eng.run([Request(prompt=np.arange(1, 9), max_new_tokens=30),
             Request(prompt=np.array([3, 4, 5]), max_new_tokens=4)])
    g = eng.telemetry.snapshot()["gauges"]
    for key in ("cache_slots_active", "cache_tokens_live", "cache_pages_live",
                "cache_tokens_evicted", "slots_free", "slots_decode",
                "queue_depth"):
        assert key in g, key
    assert g["cache_slots_active"]["peak"] == 2
    assert g["cache_tokens_live"]["peak"] > 0
    assert g["slots_free"]["value"] == 2  # all drained at completion
    if evicting:
        assert g["cache_pages_live"]["peak"] > 0
        assert g["cache_tokens_evicted"]["peak"] > 0
    else:
        assert g["cache_pages_live"]["peak"] == 0.0
        assert g["cache_tokens_evicted"]["peak"] == 0.0
