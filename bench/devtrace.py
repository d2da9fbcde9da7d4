"""Reduction of a profiler trace to the device numbers of a run.

A trace is read into plain events ``(plane, line, name, start_ns, dur_ns)``
(:func:`load`), so the reduction (:func:`reduce`) runs on a recorded list
as well as on a fresh ``.xplane.pb``.  On a TPU each chip is a plane
``/device:TPU:<n>`` with an ``XLA Ops`` line (one event per operation run)
and an ``XLA Modules`` line (one event per program run).  The measured
window is the host annotation the benchmark opens around it
(:data:`WINDOW`), on the trace's own clock.

* busy: the union of the operation intervals inside the window, per chip,
  averaged over the chips that ran anything;
* module time: the durations of the module events whose name contains a
  given part (the level loop's jit);
* top operations: device self time (less the operations nested in it)
  summed per operation name, on the first chip;
* idle gaps: the intervals of the window in which the first chip ran
  nothing, named by the innermost host span that covers each gap's middle.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")

Event = Tuple[str, str, str, float, float]


def load(path: str) -> List[Event]:
    """The events of an ``.xplane.pb`` file (device planes and the host's
    annotations only)."""

    from jax.profiler import ProfileData

    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(_DEVICE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and ev.name != WINDOW:
                    continue
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def window(events: Iterable[Event]) -> Tuple[float, float]:
    """(start, end) of the measured window on the trace clock."""

    for plane, _line, name, start, dur in events:
        if name == WINDOW and not _DEVICE.match(plane):
            return start, start + dur
    raise ValueError(f"the trace holds no {WINDOW!r} annotation")


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(events: Iterable[Event], lo: float, hi: float):
    for plane, line, name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield plane, line, name, a, b


def _short(name: str) -> str:
    """An HLO operation's name without its signature (``%fusion.12``)."""

    return name.split(" = ", 1)[0]


def _self_times(ops: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Time per operation name less the time of the operations nested in
    it (a ``while`` holds its body's operations on the same line)."""

    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, start, time of nested ops]

    def close(entry):
        name, end, start, child = entry
        out[name] = out.get(name, 0.0) + (end - start) - child
        if stack:
            stack[-1][3] += end - start

    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        stack.append([name, b, a, 0.0])
    while stack:
        close(stack.pop())
    return out


def _name_gaps(gaps, host_spans, top: int) -> List[list]:
    """Idle time summed by the innermost host span covering each gap's
    middle ("no span" where none does), the largest first."""

    import numpy as np

    if not gaps:
        return []
    mids = np.array([0.5 * (a + b) for a, b in gaps])
    lengths = np.array([b - a for a, b in gaps])
    names = ["no span"]
    who = np.zeros(len(gaps), np.int64)
    depth = np.full(len(gaps), -1)
    order = np.argsort(mids)
    sorted_mids = mids[order]
    for name, a, b, d in host_spans:
        i, j = np.searchsorted(sorted_mids, [a, b], side="left")
        idx = order[i:j]
        deeper = idx[depth[idx] < d]
        if deeper.size:
            names.append(name)
            who[deeper] = len(names) - 1
            depth[deeper] = d
    named: Dict[str, float] = {}
    for k, length in zip(who.tolist(), lengths.tolist()):
        named[names[k]] = named.get(names[k], 0.0) + length
    return [
        [n, s * 1e-9]
        for n, s in sorted(named.items(), key=lambda kv: -kv[1])[:top]
    ]


def reduce(
    events: Sequence[Event],
    module_part: str,
    host_spans: Sequence[Tuple[str, float, float, int]] = (),
    top: int = 10,
) -> Optional[Dict[str, object]]:
    """The device numbers of the window, or None when no device ran in it.

    ``host_spans``: (name, start_ns, end_ns, depth) on the trace clock, to
    name the idle gaps."""

    lo, hi = window(events)
    busy: Dict[str, List[Tuple[float, float]]] = {}
    ops: List[Tuple[str, float, float]] = []
    modules: List[float] = []
    has_ops = {p for p, line, *_ in events if line == OPS_LINE}
    first_chip = min(has_ops, default=None)
    for plane, line, name, a, b in _clip(events, lo, hi):
        if not _DEVICE.match(plane):
            continue
        if line == MODULES_LINE:
            if module_part in name:
                modules.append(b - a)
            if plane in has_ops:
                continue
        busy.setdefault(plane, []).append((a, b))
        if line == OPS_LINE and plane == first_chip:
            ops.append((_short(name), a, b))
    if not busy:
        return None
    unions = {p: _union(iv) for p, iv in busy.items()}
    busy_ns = sum(
        sum(b - a for a, b in u) for u in unions.values()
    ) / len(unions)
    first = unions[sorted(unions)[0]]
    gaps, t = [], lo
    for a, b in first + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    op_time = _self_times(ops)
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "chips": len(unions),
        "module_s": [d * 1e-9 for d in modules],
        "device_ops": [
            [n, s * 1e-9]
            for n, s in sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": _name_gaps(gaps, host_spans, top),
    }
