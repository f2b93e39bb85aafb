"""The reduction from trace events to the per-layer numbers and the
breakdown: on hand-made events whose answers are known, and on a slice of
a trace recorded on a TPU v5e (``data/v5e_trace_slice.json.gz``)."""

from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

from trace_reduce import breakdown, reduce_events

MS = 1_000_000  # ns
RECORDED = Path(__file__).resolve().parent / "data" / "v5e_trace_slice.json.gz"


def made_up():
    # window 0-100 ms; device: a while loop 10-50 holding fusion 10-20 and
    # copy 25-45; a transpose 60-70; then nothing until the end
    device = [["while.1", 10 * MS, 40 * MS], ["fusion.3", 10 * MS, 10 * MS],
              ["copy.7", 25 * MS, 20 * MS], ["transpose.2", 60 * MS, 10 * MS]]
    host = [["window", 0, 100 * MS], ["dispatch", 0, 9 * MS],
            ["wait", 50 * MS, 10 * MS], ["generator", 70 * MS, 30 * MS]]
    return {"device": {"/device:TPU:0": device}, "host": host}


def test_busy_idle_and_self_times():
    r = reduce_events(made_up())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.05)          # 10-50 and 60-70
    assert r["op_s"]["while.1"] == pytest.approx(0.01)  # 40 - 10 - 20
    assert r["op_s"]["fusion.3"] == pytest.approx(0.01)
    assert r["layout_copy_s"] == pytest.approx(0.03)   # copy + transpose
    assert r["idle_s"] == pytest.approx(
        {"dispatch": 0.01, "wait": 0.01, "generator": 0.03})


def test_readers_and_breakdown():
    from harness import load_reader
    ctx = {"trace": reduce_events(made_up()), "steps": 1000}
    assert load_reader("device_idle_pct")(ctx)[0] == pytest.approx(50.0)
    assert load_reader("device_us_per_step")(ctx)[0] == pytest.approx(50.0)
    assert load_reader("layout_copy_pct")(ctx)[0] == pytest.approx(60.0)
    b = breakdown(ctx["trace"])
    assert b["device_ops"][0] == ["copy.7", pytest.approx(0.02)]
    assert b["idle_gaps"][0] == ["generator", pytest.approx(0.03)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_device_events_reads_nothing():
    from harness import load_reader
    events = made_up()
    events["device"] = {}
    ctx = {"trace": reduce_events(events), "steps": 1000}
    for name in ("device_idle_pct", "device_us_per_step", "layout_copy_pct"):
        assert load_reader(name)(ctx) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_v5e_slice(recorded):
    """Busy time, copy time and per-step time of a real trace agree with a
    plain recount: a 10 ns timeline for busy, summed durations for the
    copies (leaf ops, so their duration is their self time)."""
    import numpy as np
    from harness import load_reader
    r = reduce_events(recorded)
    (_, lo, dur), = [e for e in recorded["host"] if e[0] == "window"]
    ops, = recorded["device"].values()
    timeline = np.zeros(int(dur // 10) + 1, bool)
    copy_ns = 0
    for name, start, d in ops:
        a, b = max(start, lo), min(start + d, lo + dur)
        if b > a:
            timeline[int(a - lo) // 10:int(b - lo + 9) // 10] = True
            if name.startswith(("%copy.", "%transpose.")):
                copy_ns += b - a
    assert r["busy_s"] == pytest.approx(timeline.sum() * 1e-8, rel=1e-3)
    assert r["layout_copy_s"] == pytest.approx(copy_ns * 1e-9, rel=1e-6)
    # a 4-volume fleet step under a GC tick: the copies are most of it
    steps = sum(1 for name, *_ in ops if name.startswith("%while.155 "))
    ctx = {"trace": r, "steps": steps}
    assert 100 <= load_reader("device_us_per_step")(ctx)[0] <= 250
    assert 40 <= load_reader("layout_copy_pct")(ctx)[0] <= 90
    idle = sum(r["idle_s"].values())
    assert load_reader("device_idle_pct")(ctx)[0] == pytest.approx(
        100 * idle / r["window_s"])
    top = breakdown(r)
    assert top["device_ops"][0][0].startswith("copy.")
    assert " copy " in top["device_ops"][0][0]
    # the window opens with the device waiting for the first chunk
    assert top["idle_gaps"][0][0] == "generator"
