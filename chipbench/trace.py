"""Profiler trace -> device busy time, idle share, kernel time, breakdown.

A traced run wraps its window in ``jax.profiler`` and its own calls (the
window, each task, step or pass) in ``TraceAnnotation`` spans named
``chipbench.*``.  :func:`events_from_xplane` reads the ``.xplane.pb`` the
profiler writes into a plain form,

    {"devices": {plane name: [[op name, start_ns, dur_ns], ...]},
     "modules": {plane name: [[module name, start_ns, dur_ns], ...]},
     "host":    [[span name, start_ns, dur_ns], ...]}

(host spans are the ``chipbench.*`` annotations only), and
:func:`summarize` reduces that form; tests feed it a recorded fixture.
"""

from __future__ import annotations

import glob
import os
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]              # [start_ns, end_ns)
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@contextmanager
def annotate(name: str, on: bool) -> Iterator[None]:
    """A ``chipbench.<name>`` host span in the trace when ``on``."""
    if not on:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield


@contextmanager
def capture(out_dir: str) -> Iterator[None]:
    import jax
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(out_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return found[-1]


def events_from_xplane(path: str) -> Dict[str, Any]:
    """The device ops, device modules and ``chipbench.*`` host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "modules": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                key = {OPS_LINE: "devices", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                out[key].setdefault(plane.name, []).extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return out


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def window_of(ev: Dict[str, Any]) -> Interval:
    """The traced window: the ``chipbench.window`` span."""
    spans = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0]


def busy(ev: Dict[str, Any], window: Interval) -> Dict[str, int]:
    """Per device: ns inside the window in which any op ran."""
    out = {}
    for dev, ops in ev["devices"].items():
        u = clip(union([(s, s + d) for _, s, d in ops]), *window)
        out[dev] = sum(e - s for s, e in u)
    return out


def kernel_ns(ev: Dict[str, Any], window: Interval, match,
              key: str = "devices") -> Tuple[int, int]:
    """(summed device ns, event count) of the events ``match(name)``
    accepts, inside the window, over every device."""
    total = count = 0
    for evs in ev[key].values():
        for name, s, d in evs:
            if match(name) and s >= window[0] and s + d <= window[1]:
                total += d
                count += 1
    return total, count


def host_span_at(spans: Sequence[Tuple[str, int, int]], t: int) -> str:
    """The innermost (latest-starting) host span covering time ``t``."""
    best: Optional[Tuple[int, str]] = None
    for name, s, d in spans:
        if s <= t < s + d and (best is None or s > best[0]):
            best = (s, name)
    return best[1][len(SPAN_PREFIX):] if best else "outside-spans"


def idle_gaps(ev: Dict[str, Any], window: Interval
              ) -> List[Tuple[str, int]]:
    """Idle gaps of the first device inside the window, each named by the
    host span the harness was in at the gap's midpoint, longest first."""
    if not ev["devices"]:
        return []
    dev = sorted(ev["devices"])[0]
    u = clip(union([(s, s + d) for _, s, d in ev["devices"][dev]]), *window)
    gaps, t = [], window[0]
    for s, e in u + [(window[1], window[1])]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    host = [h for h in ev["host"] if h[0] != WINDOW_SPAN]
    named = [(host_span_at(host, (s + e) // 2), e - s) for s, e in gaps]
    return sorted(named, key=lambda x: -x[1])


def top_ops(ev: Dict[str, Any], window: Interval, n: int = 10
            ) -> List[Tuple[str, int]]:
    tot: Dict[str, int] = {}
    for evs in ev["devices"].values():
        for name, s, d in evs:
            if s >= window[0] and s + d <= window[1]:
                tot[name] = tot.get(name, 0) + d
    return sorted(tot.items(), key=lambda x: -x[1])[:n]


def summarize(ev: Dict[str, Any]) -> Dict[str, Any]:
    """busy_s (averaged over the devices), window_s, idle share and the
    breakdown (top device ops, longest idle gaps by host span)."""
    w = window_of(ev)
    window_s = (w[1] - w[0]) / 1e9
    b = busy(ev, w)
    busy_s = sum(b.values()) / max(1, len(b)) / 1e9
    gaps = idle_gaps(ev, w)
    by_span: Dict[str, int] = {}
    for name, ns in gaps:
        by_span[name] = by_span.get(name, 0) + ns
    return {
        "window": w,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": (1.0 - busy_s / window_s
                       if window_s > 0 and ev["devices"] else None),
        "idle_by_span_s": {k: v / 1e9 for k, v in by_span.items()},
        "breakdown": {
            "device_ops": [[n, ns / 1e9] for n, ns in top_ops(ev, w)],
            "idle_gaps": [[n, ns / 1e9] for n, ns in gaps[:10]],
        },
    }
