"""Device time of the V-cycle step by the program's own named scopes, and
the device's idle time by the program's own host spans, from one profiler
trace (``.xplane.pb``).

The step (``amg_vcycle_step``) puts its operations under named scopes:
``outer`` (the step's own residual, norm and update) or a level ``L<k>``;
inside a level one phase of ``pre``, ``residual``, ``restrict``,
``prolong``, ``post`` and ``coarse``; ``spmv`` around every distributed
SpMV, ``exchange`` around its halo exchange, and ``step_<name>`` around
each step of the exchange's plan.  A TPU trace names an operation by its
HLO text only, so its scope is read from the compiled module's HLO text
(``metadata={op_name="jit(amg_vcycle_step)/L0/pre/spmv/..."}``).  XLA gives
a fusion the ``op_name`` of its root: where it fuses an SpMV's last step
into the smoother's update, that time counts to the smoother, not to
``spmv``.  Operations XLA adds itself (the copies that prefetch operands)
have no scope and count as ``unscoped``.

Each instant a chip is busy counts once, to the operation that started
last among those running, so the kinds (``spmv``, ``exchange``, ``other``)
add up to the busy time.  ``exchange`` is the time under ``exchange``,
``spmv`` the rest under ``spmv``.  Idle time is named by the innermost host
span around it among ``bench/``, ``amg/`` and ``py/`` (``repro.obs`` spans
land there when obs is enabled under the profiler).  Seconds are averaged
over the chips.
"""
from __future__ import annotations

import bisect
import heapq
import re
from typing import Dict, List, Optional, Tuple

from bench import xplane

SPAN_PREFIXES = ("bench/", "amg/", "py/")
PHASES = ("pre", "residual", "restrict", "prolong", "post", "coarse")
KINDS = ("spmv", "exchange", "other")
LEVEL = re.compile(r"^(outer|L\d+)$")
INSTR = re.compile(r"^\s*(?:ROOT )?%([^ ]+) = .*?op_name=\"([^\"]*)\"")
MODULE = re.compile(r"^HloModule ([^ ,]+)")
MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
OUTSIDE = "outside spans"
#: the host spans whose device idle time is not the loop's: a call's
#: placing of b and x0 and its unpacking of x
CALL_ENDS = ("amg/place", "amg/unpack")
SOLVE_SPAN = "amg/solve"

Key = Tuple[str, str, str, str]            # (level, phase, kind, plan step)


def op_scopes(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, operation name -> ``op_name``) of a compiled module's
    HLO text, as ``Compiled.as_text()`` gives it."""
    m = MODULE.match(hlo_text)
    if m is None:
        raise ValueError("no HloModule line in the HLO text")
    ops = {}
    for line in hlo_text.splitlines():
        i = INSTR.match(line)
        if i:
            ops[i.group(1)] = i.group(2)
    return m.group(1), ops


def key(op_name: Optional[str]) -> Key:
    """(level, phase, kind, plan step) of an ``op_name``; an operation
    under no ``outer`` or ``L<k>`` scope is ``unscoped``."""
    parts = (op_name or "").split("/")
    lv = next((i for i, p in enumerate(parts) if LEVEL.match(p)), None)
    if lv is None:
        return (UNSCOPED, "", "other", "")
    phase = parts[lv + 1] if lv + 1 < len(parts) else ""
    kind = ("exchange" if "exchange" in parts
            else "spmv" if "spmv" in parts else "other")
    step = next((p for p in parts if p.startswith("step_")), "")
    return (parts[lv], phase if phase in PHASES else "", kind, step)


def load(path: str, module: str, scopes: Dict[str, str]):
    """(operations by chip id as ``(key, start_ns, end_ns)``, host spans)
    of one trace file.  An operation outside the runs of ``module`` on the
    ``XLA Modules`` line is ``unscoped``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List] = {}
    spans: List[xplane.Interval] = []
    for plane in data.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        lines = {ln.name: ln for ln in plane.lines}
        if m and xplane.OPS_LINE in lines:
            runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in lines[MODULES_LINE].events
                          if ev.name.split("(")[0] == module)
            starts = [r[0] for r in runs]
            ops = []
            for ev in lines[xplane.OPS_LINE].events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                r = bisect.bisect_right(starts, s) - 1
                inside = r >= 0 and e <= runs[r][1]
                name = xplane.op_name(ev.name)
                ops.append((key(scopes.get(name) if inside else None), s, e))
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.name.startswith(SPAN_PREFIXES))
    if not devices:
        raise ValueError("no device operations in the trace")
    return devices, spans


def owned(ops) -> Dict[Key, float]:
    """Busy nanoseconds per key, each instant counted once: to the
    operation that started last among those running then."""
    ops = sorted(ops, key=lambda o: o[1])
    edges = sorted({t for _, s, e in ops for t in (s, e)})
    out: Dict[Key, float] = {}
    heap: List = []                  # (-start, -index, end, key)
    i = 0
    for t0, t1 in zip(edges, edges[1:]):
        while i < len(ops) and ops[i][1] <= t0:
            k, s, e = ops[i]
            heapq.heappush(heap, (-s, -i, e, k))
            i += 1
        while heap and heap[0][2] <= t0:
            heapq.heappop(heap)
        if heap:
            k = heap[0][3]
            out[k] = out.get(k, 0.0) + (t1 - t0)
    return out


def labelled(spans: List[xplane.Interval], w0: float, w1: float):
    """The window cut at every span edge: ``(start, end, label)`` pieces,
    each labelled by the innermost span around it."""
    edges = sorted({w0, w1} | {t for _, s, e in spans for t in (s, e)
                               if w0 < t < w1})
    out = []
    for a, b in zip(edges, edges[1:]):
        inner = [(e - s, n) for n, s, e in spans if s <= a and b <= e]
        out.append((a, b, min(inner)[1] if inner else OUTSIDE))
    return out


def _overlap(a: List[Tuple[float, float]], b: list) -> list:
    """Where two sorted lists of disjoint intervals overlap; a piece keeps
    what follows the interval's ends in ``b`` (a label)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e) + tuple(b[j][2:]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _cover(pieces) -> float:
    return sum(e - s for s, e in pieces)


def reduce(devices, spans: List[xplane.Interval]) -> dict:
    """Seconds per chip, inside the ``bench/window`` span: busy time by
    kind, level, level and kind, phase and exchange plan step; idle time by
    the innermost host span; the loop's idle time (inside ``amg/solve``,
    outside ``amg/place`` and ``amg/unpack``); the longest idle gaps."""
    windows = [(s, e) for n, s, e in spans if n == xplane.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {xplane.WINDOW_SPAN} spans")
    w0, w1 = windows[0]
    pieces = labelled(spans, w0, w1)
    solve = xplane.union([(s, e) for n, s, e in spans if n == SOLVE_SPAN])
    ends = xplane.union([(s, e) for n, s, e in spans if n in CALL_ENDS])
    n_chips = len(devices)
    by_key: Dict[Key, float] = {}
    idle_by_span: Dict[str, float] = {}
    loop_idle = 0.0
    gaps = []
    for chip in sorted(devices):
        ops = xplane._clip(devices[chip], w0, w1)
        for k, t in owned(ops).items():
            by_key[k] = by_key.get(k, 0.0) + t
        busy = xplane.union([(s, e) for _, s, e in ops])
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for s, e, label in _overlap(idle, pieces):
            idle_by_span[label] = idle_by_span.get(label, 0.0) + (e - s)
        in_solve = _overlap(idle, solve)
        loop_idle += _cover(in_solve) - _cover(_overlap(in_solve, ends))
        if chip == min(devices):
            gaps = sorted(idle, key=lambda g: g[0] - g[1])[:xplane.TOP]

    def sec(ns: float) -> float:
        return ns / n_chips / 1e9

    def total(pick) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for k, t in by_key.items():
            name = pick(k)
            if name:
                out[name] = out.get(name, 0.0) + sec(t)
        return dict(sorted(out.items()))

    def label_at(t: float) -> str:
        return next((lb for a, b, lb in pieces if a <= t <= b), OUTSIDE)

    kinds, levels = total(lambda k: k[2]), total(lambda k: k[0])
    return {
        "chips": n_chips,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sec(sum(by_key.values())),
        "kind_s": {k: kinds.get(k, 0.0) for k in KINDS},
        "level_s": levels,
        "level_kind_s": {lv: total(lambda k, lv=lv: k[2] if k[0] == lv
                                   else None) for lv in levels},
        "phase_s": total(lambda k: f"{k[0]}/{k[1]}" if k[1] else None),
        "exchange_step_s": total(lambda k: k[3]),
        "idle_by_span_s": {n: sec(t) for n, t in sorted(
            idle_by_span.items(), key=lambda kv: -kv[1])},
        "loop_idle_s": sec(loop_idle),
        "idle_gaps": [[label_at(0.5 * (s + e)), (e - s) / 1e9]
                      for s, e in gaps],
    }


def read(trace_dir: str, hlo_text: str) -> dict:
    """:func:`reduce` of the newest trace under ``trace_dir``, scoped by the
    compiled step's HLO text."""
    module, scopes = op_scopes(hlo_text)
    return reduce(*load(xplane.find(trace_dir), module, scopes))
