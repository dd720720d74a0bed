"""Reduction of a profiler trace (``.xplane.pb``) to the window's device
numbers.

A trace gives, for each chip, the intervals in which an XLA operation ran
(the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), and the
benchmark's own host spans (``bench/...`` annotations on the host plane):
``bench/window`` around the measured window, and inside it a span around
each right-hand side's creation and each ``solve`` call.

- busy: the union of a chip's operation intervals inside the window, so
  operations that overlap (an asynchronous collective beside a fusion)
  count once; averaged over the chips.
- exchange: the union of the intervals of collective-permute operations
  (the neighbourhood exchange's rounds), averaged over the chips.
- device operations: each operation name's summed time, per chip.
- idle gaps: the stretches of the first chip's window in which no
  operation ran, each named by the innermost host span around its middle.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
EXCHANGE_OP = "collective-permute"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
TOP = 10


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(text: str) -> str:
    """An operation's own HLO name: ``fusion.5`` of the event text
    ``%fusion.5 = f32[...] fusion(...)``, which also names its operands."""
    if text.startswith("%") and " = " in text:
        return text[1:text.index(" = ")]
    return text


def load(path: str) -> Tuple[Dict[int, List[Interval]], List[Interval]]:
    """(operations by chip id, bench host spans) of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Interval]] = {}
    spans: List[Interval] = []
    planes = []
    for plane in data.planes:
        lines = list(plane.lines)
        planes.append(f"{plane.name}: {[ln.name for ln in lines]}")
        m = DEVICE_PLANE.match(plane.name)
        for line in lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    (op_name(ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns)
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    if not devices:
        raise ValueError("no device operations in the trace; planes: "
                         + "; ".join(planes))
    return devices, spans


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(ops: List[Interval], w0: float, w1: float) -> List[Interval]:
    return [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]


def _covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def _label(t: float, spans: List[Interval]) -> str:
    inner = [(e - s, n) for n, s, e in spans if s <= t <= e]
    return min(inner)[1] if inner else "outside bench spans"


def reduce(devices: Dict[int, List[Interval]], spans: List[Interval]) -> dict:
    """Seconds of the window, busy, exchange; top operations and gaps.

    ``exchange_s`` is None where no chip ran a collective-permute."""
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in the trace")
    w0, w1 = windows[0]
    n_chips = len(devices)
    busy = exch = 0.0
    by_name: Dict[str, float] = {}
    seen_exchange = False
    for ops in devices.values():
        ops = _clip(ops, w0, w1)
        busy += _covered([(s, e) for _, s, e in ops])
        ex = [(s, e) for n, s, e in ops if EXCHANGE_OP in n]
        seen_exchange |= bool(ex)
        exch += _covered(ex)
        for n, s, e in ops:
            by_name[n] = by_name.get(n, 0.0) + (e - s)
    first = union([(s, e) for _, s, e in _clip(devices[min(devices)], w0, w1)])
    edges = [w0] + [t for iv in first for t in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / n_chips / 1e9,
        "exchange_s": exch / n_chips / 1e9 if seen_exchange else None,
        "chips": n_chips,
        "device_ops": [[n, t / n_chips / 1e9] for n, t in ops_top],
        "idle_gaps": [[_label(0.5 * (s + e), spans), (e - s) / 1e9]
                      for s, e in gaps[:TOP]],
    }


def read(trace_dir: str) -> dict:
    """:func:`reduce` of the newest trace under ``trace_dir``."""
    return reduce(*load(find(trace_dir)))


def per_vcycle_ms(seconds: Optional[float], vcycles: int) -> Optional[float]:
    return None if seconds is None or vcycles == 0 else seconds / vcycles * 1e3
