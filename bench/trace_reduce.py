"""From a profiler trace to the traced window's device numbers.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps two lists of ``[name, start_ns, duration_ns]``: the device's XLA
operations (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane) and the
harness's own host spans (``jax.profiler.TraceAnnotation`` names in
``HOST_SPANS``). ``reduce_events`` works on those lists alone, so a small
recorded trace checks it without a chip (``bench/tests``).

Within the ``window`` span:

* busy: the union of the intervals in which an operation ran on the device,
  averaged over the devices that ran any;
* per operation: self time (duration minus the operations nested in it),
  summed by name;
* layout copies: self time of operations whose opcode is ``copy`` or
  ``transpose`` (XLA's layout changes, as the v5e trace names them);
* idle gaps: each stretch with no operation on the device, labelled with
  the host span that overlaps it most (``idle`` where none does).
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

HOST_SPANS = ("window", "generator", "dispatch", "wait")
LAYOUT_OPCODES = ("copy", "transpose")
# the v5e trace names an op by its HLO text: "%copy.164 = pred[4,4853,128]
# {2,0,1:T(4,128)(4,1)S(1)} copy(pred[...] %get-tuple-element.4033)"
HLO = re.compile(r"^%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
TILING = re.compile(r":T\([^}]*")


def op_label(name: str) -> tuple[str, str]:
    """(label, opcode) of a device event: the op's name, opcode and result
    shape with the tiling dropped, or the name as it is."""
    m = HLO.match(name)
    if not m:
        return name, name.split(".")[0]
    op, shape, opcode = m.groups()
    shape = "(tuple)" if shape.startswith("(") else TILING.sub("", shape)
    return f"{op} {opcode} {shape}"[:120], opcode


def load_events(logdir: str | Path) -> dict:
    from jax.profiler import ProfileData

    files = sorted(Path(logdir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(str(files[-1]))
    device: dict[str, list] = {}
    host = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.setdefault(plane.name, []).extend(
                        [ev.name, ev.start_ns, ev.duration_ns]
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events if ev.name in HOST_SPANS)
    return {"device": {k: v for k, v in device.items() if v}, "host": host}


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    out.sort(key=lambda e: (e[1], -e[2]))
    return out


def _self_times(ops) -> dict:
    """Self time per name of (name, start, end) intervals, nested ones
    subtracted from the interval that holds them."""
    totals: dict[str, float] = defaultdict(float)
    stack: list[list] = []          # [name, end, child time]
    for name, a, b in ops:
        while stack and stack[-1][1] <= a:
            done = stack.pop()
            totals[done[0]] += done[3] - done[2]
        if stack:
            stack[-1][2] += min(b, stack[-1][1]) - a
        stack.append([name, b, 0, b - a])
    for done in stack:
        totals[done[0]] += done[3] - done[2]
    return totals


def _union(ops) -> list[tuple]:
    merged: list[list] = []
    for _, a, b in ops:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def _label(gap, spans) -> str:
    a, b = gap
    best, over = "idle", 0
    for name, s, e in spans:
        o = min(b, e) - max(a, s)
        if o > over:
            best, over = name, o
    return best


def reduce_events(events: dict) -> dict:
    windows = [e for e in events["host"] if e[0] == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    _, lo, dur = max(windows, key=lambda e: e[2])
    hi = lo + dur
    spans = [e for e in _clip(events["host"], lo, hi) if e[0] != "window"]
    busy, op_ns, gaps = [], defaultdict(float), defaultdict(float)
    copy_ns = 0.0
    for ops in events["device"].values():
        ops = _clip(ops, lo, hi)
        if not ops:
            continue
        intervals = _union(ops)
        busy.append(sum(b - a for a, b in intervals))
        for name, t in _self_times(ops).items():
            label, opcode = op_label(name)
            op_ns[label] += t
            copy_ns += t if opcode in LAYOUT_OPCODES else 0.0
        edges = [lo] + [x for iv in intervals for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[_label((a, b), spans)] += b - a
    if not busy:
        return {"window_s": dur / 1e9, "busy_s": 0.0, "devices": 0,
                "op_s": {}, "layout_copy_s": 0.0, "idle_s": {}}
    n = len(busy)
    op_s = {k: v / n / 1e9 for k, v in op_ns.items()}
    return {
        "window_s": dur / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "devices": n,
        "op_s": op_s,
        "layout_copy_s": copy_ns / n / 1e9,
        "idle_s": {k: v / n / 1e9 for k, v in gaps.items()},
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    def largest(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]
    return {"device_ops": largest(reduced["op_s"]),
            "idle_gaps": largest(reduced["idle_s"])}
