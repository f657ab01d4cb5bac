"""Host phases of the engine loop against the device's idle time, from the
events of one profiler trace (``trace_reduce.load_events``' output).

The engine opens one ``serve.iterate`` span per ``Engine._iterate`` and one
``serve.<phase>`` span per phase inside it; a span whose name ends in
``.wait`` is the host blocked on the device (``serve/telemetry.py``).
Inside the host span ``bench.window``:

- ``engine_host_ms``: the mean, over the ``serve.iterate`` spans wholly
  inside the window, of each span's duration less the union of the
  ``.wait`` spans in it: the host's own time per engine iteration.
- ``idle_by_phase``: every idle interval of the device (the plane the
  reduction takes its gaps from) cut at each host span's ends, each piece
  named by the innermost host span over it.
- ``idle_host_share``: 100 x the idle time in which the host is inside a
  ``serve.iterate`` and in no ``.wait`` span, over the window: the part of
  ``device_idle_share`` that the engine's own host work causes.

``trace_reduce.Reduction`` keeps neither the host spans nor the idle
intervals, so no per-layer reader can call these functions yet;
``scripts/engine_spans.py`` reads them from a traced run of a cell.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench.trace_reduce import DEVICE_PLANE, OP_LINE, _union

ITERATE = "serve.iterate"
WAIT = ".wait"

Span = Tuple[str, float, float]  # name, start s, end s from the window's start


def window(events: dict, name: str = "bench.window") -> Tuple[int, int]:
    wins = [h for h in events["host"] if h["name"] == name]
    if len(wins) != 1:
        raise ValueError(f"expected one host span {name!r}, found {len(wins)}")
    return wins[0]["t"], wins[0]["t"] + wins[0]["d"]


def host_spans(events: dict) -> List[Span]:
    """Every host span that overlaps the window (the window's own left
    out), in seconds from the window's start, sorted by start."""
    w0, w1 = window(events)
    return sorted(((h["name"], (h["t"] - w0) * 1e-9, (h["t"] + h["d"] - w0) * 1e-9)
                   for h in events["host"]
                   if h["d"] > 0 and h["t"] < w1 and h["t"] + h["d"] > w0
                   and h["name"] != "bench.window"),
                  key=lambda sp: (sp[1], -sp[2]))


def idle(events: dict) -> Optional[List[Tuple[float, float]]]:
    """Idle intervals of the first device plane in the window, in seconds
    from its start; None where no device ran an op."""
    w0, w1 = window(events)
    planes = sorted({d["plane"] for d in events["device"]
                     if d["line"] == OP_LINE and DEVICE_PLANE.match(d["plane"])})
    if not planes:
        return None
    busy = _union((max(d["t"], w0), min(d["t"] + d["d"], w1))
                  for d in events["device"]
                  if d["plane"] == planes[0] and d["line"] == OP_LINE
                  and d["t"] < w1 and d["t"] + d["d"] > w0)
    out, prev = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > prev:
            out.append(((prev - w0) * 1e-9, (s - w0) * 1e-9))
        prev = max(prev, e)
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in _union(intervals))


def engine_host_ms(spans: List[Span], window_s: float) -> Optional[float]:
    """Mean host time per ``serve.iterate`` wholly inside the window, less
    the union of the ``.wait`` spans in it, in ms."""
    its = [(s, e) for n, s, e in spans if n == ITERATE and s >= 0.0 and e <= window_s]
    waits = [(s, e) for n, s, e in spans if n.startswith("serve.") and n.endswith(WAIT)]
    if not its:
        return None
    own = [(e - s) - _length([(max(a, s), min(b, e)) for a, b in waits if a < e and b > s])
           for s, e in its]
    return 1e3 * sum(own) / len(own)


def _pieces(spans: List[Span], gaps):
    """(piece start, piece end, the spans over it) for every piece of
    ``gaps`` cut at the spans' ends."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    opens, closes = defaultdict(list), defaultdict(list)
    for i, (_, s, e) in enumerate(spans):
        opens[s].append(i)
        closes[e].append(i)
    active, over = set(), []   # over[k]: the spans over [cuts[k], cuts[k + 1]]
    for t in cuts:
        active.difference_update(closes[t])
        active.update(opens[t])
        over.append([spans[i] for i in active])
    for a, b in gaps:
        inner = cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)]
        for x, y in zip([a] + inner, inner + [b]):
            k = bisect.bisect_right(cuts, (x + y) / 2) - 1
            yield x, y, over[k] if 0 <= k < len(cuts) - 1 else []


def idle_by_phase(spans: List[Span], gaps) -> Dict[str, float]:
    """Idle seconds by the innermost host span over them (``bench.window``
    where none is)."""
    out = defaultdict(float)
    for x, y, over in _pieces(spans, gaps):
        name = min(over, key=lambda sp: sp[2] - sp[1])[0] if over else "bench.window"
        out[name] += y - x
    return dict(out)


def idle_host_seconds(spans: List[Span], gaps) -> float:
    """Idle seconds inside a ``serve.iterate`` and in no ``.wait`` span."""
    return sum(y - x for x, y, over in _pieces(spans, gaps)
               if any(n == ITERATE for n, _, _ in over)
               and not any(n.startswith("serve.") and n.endswith(WAIT) for n, _, _ in over))


def summary(events: dict) -> dict:
    """Every number above for one trace, for PERF.md's breakdown."""
    w0, w1 = window(events)
    win = (w1 - w0) * 1e-9
    spans = host_spans(events)
    out = {"window_s": win,
           "iterations": sum(n == ITERATE and s >= 0.0 and e <= win for n, s, e in spans),
           "engine_host_ms": engine_host_ms(spans, win)}
    gaps = idle(events)
    if gaps is not None:
        by = idle_by_phase(spans, gaps)
        out.update(idle_s=sum(by.values()),
                   idle_host_share=100.0 * idle_host_seconds(spans, gaps) / win,
                   idle_by_phase=dict(sorted(by.items(), key=lambda kv: -kv[1])))
    return out
