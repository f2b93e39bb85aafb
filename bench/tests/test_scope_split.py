"""Device time by named scope (``scopes.py``): the map from HLO
instructions to scopes, the split and the tick count on hand-made events,
the readers of the per-layer metrics, and the small cell's compiled chunk.
"""

from __future__ import annotations

import copy
import gzip
import json
from pathlib import Path

import pytest

from harness import load_reader
from scopes import (TICK_BODY, UNSCOPED, instruction, scope_line, scope_map,
                    scope_of, scope_split)
from trace_reduce import reduce_events

MS = 1_000_000  # ns
RECORDED = (Path(__file__).resolve().parent / "data"
            / "v5e_zipf_scope_slice.json.gz")
NAMES = ("user_write", "gc_tick", "guard", "select", "rewrite", "merge")
SIX = {"user_write", "gc_tick", "gc_tick/guard", "gc_tick/select",
       "gc_tick/rewrite", "gc_tick/merge"}
PER_STEP = ("user_write_us_per_step", "gc_tick_us_per_step")
PER_TICK = ("gc_select_us_per_tick", "gc_rewrite_us_per_tick",
            "gc_merge_us_per_tick", "gc_tick_occupancy_pct")
BODY = "jit(f)/while/body/closed_call/gc_tick/while/body"

# a module as the TPU compiler leaves it: a fusion, a loop, a sort and a
# reshape it made with no op_name, a copy in the tick's body with none, an
# outer loop whose op_name names no scope
HLO = f"""HloModule jit_f, is_scheduled=true

%fused_computation.1 (param_0: s32[8]) -> s32[64] {{
  %param_0 = s32[8]{{0}} parameter(0)
  %reshape.1 = s32[8]{{0}} reshape(%param_0), metadata={{op_name="{BODY}/rewrite/vmap()/squeeze"}}
  %transpose.1 = s32[8]{{0}} transpose(%reshape.1), metadata={{op_name="{BODY}/rewrite/vmap()/squeeze"}}
  ROOT %scatter.1 = s32[64]{{0}} scatter(%transpose.1), to_apply=%region_1
}}

%wide.body (p: (u32[])) -> (u32[]) {{
  %p = (u32[]) parameter(0)
  ROOT %add.9 = u32[] add(%p, %p), metadata={{op_name="{BODY}/rewrite/vmap()/add"}}
}}

%wide2.body (p: (u32[])) -> (u32[]) {{
  %p.4 = (u32[]) parameter(0)
  ROOT %add.10 = u32[] add(%p.4, %p.4)
}}

%tick.body (p: (s32[64])) -> (s32[64]) {{
  %p.1 = (s32[64]) parameter(0)
  %select_n.3 = s32[8]{{0}} select(%p.1), metadata={{op_name="{BODY}/select/vmap(jit(_where))/select_n"}}
  %reshape.6 = s32[8]{{0}} reshape(%select_n.3)
  %fusion.7 = s32[64]{{0}} fusion(%reshape.6), kind=kCustom, calls=%fused_computation.1, backend_config={{"x":[]}}
  %copy-start.2 = s32[64]{{0}} copy-start(%fusion.7)
  %while.9 = (u32[]) while(%p.1), condition=%wide.cond, body=%wide.body
  %while.10 = (u32[]) while(%p.1), condition=%wide.cond, body=%wide2.body
  %sort.5 = s32[64]{{0}} sort(%fusion.7), dimensions={{0}}, to_apply=%compare
  %select.4 = s32[64]{{0}} select(%sort.5), metadata={{op_name="{BODY}/merge/jit(_where)/select_n"}}
  ROOT %tuple.1 = (s32[64]) tuple(%select.4)
}}

%tick.cond (p: (s32[64])) -> pred[] {{
  %p.2 = (s32[64]) parameter(0)
  ROOT %gt.1 = pred[] compare(%p.2), direction=GT, metadata={{op_name="jit(f)/while/body/closed_call/gc_tick/while/cond/guard/gt"}}
}}

%step.body (p: (s32[64])) -> (s32[64]) {{
  %p.3 = (s32[64]) parameter(0)
  %fusion.2 = s32[64]{{0}} fusion(%p.3), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(f)/while/body/closed_call/user_write/vmap()/scatter"}}
  %while.2 = (s32[64]) while(%fusion.2), condition=%tick.cond, body=%tick.body, metadata={{op_name="jit(f)/while/body/closed_call/gc_tick/while"}}
  ROOT %constant.1 = s32[] constant(0), metadata={{op_name="jit(f)/while/body/closed_call"}}
}}

ENTRY %main.1 (a: s32[64]) -> s32[64] {{
  %a = s32[64]{{0}} parameter(0)
  ROOT %while.1 = (s32[64]) while(%a), condition=%c, body=%step.body, metadata={{op_name="jit(f)/while"}}
}}
"""


def test_scope_of_matches_whole_segments():
    assert scope_of(f"{BODY}/rewrite/vmap()/gather", NAMES) == \
        "gc_tick/rewrite"
    assert scope_of(f"{BODY}/select/vmap(jit(_where))/select_n", NAMES) == \
        "gc_tick/select"
    # the select_n primitive is not the select scope
    assert scope_of("jit(f)/while/body/closed_call/vmap()/select_n",
                    NAMES) is None
    assert scope_of("jit(f)/while/body/closed_call/user_write/vmap(jit("
                    "_where))/select_n", NAMES) == "user_write"
    assert scope_of("jit(f)/while", NAMES) is None


def test_scope_map_of_a_module():
    m = scope_map(HLO, NAMES)
    assert m["select_n.3"] == "gc_tick/select"
    assert m["select.4"] == "gc_tick/merge"
    assert m["gt.1"] == "gc_tick/guard"
    assert m["fusion.2"] == "user_write"
    assert m["while.2"] == "gc_tick"
    # made without op_name: the fusion and the first loop vote by what they
    # call, the sort and the reshape by what uses them; the copy (unused)
    # and the second loop (whose body names nothing) take the tick's own
    # scope, and so does that body
    assert m["fusion.7"] == m["while.9"] == m["add.9"] == "gc_tick/rewrite"
    assert m["reshape.6"] == "gc_tick/rewrite"
    assert m["sort.5"] == "gc_tick/merge"
    assert m["copy-start.2"] == m["while.10"] == m["add.10"] == "gc_tick"
    # the scan loop and its plumbing are under no scope
    for name in ("while.1", "constant.1", "a"):
        assert name not in m


def made_up():
    """Window 0-100 ms. A fleet step 0-40 (outer while, unscoped) holds a
    user write 1-5 and a tick loop 10-39: three iterations of select,
    rewrite and merge, the rewrite's nested loop running its op twice;
    guard evaluations at 10, 19, 28 and 37. Then an unscoped op 50-60."""
    device = [["%while.1 = (s32[64]) while(...)", 0, 40 * MS],
              ["%fusion.2 = s32[64] fusion(...)", 1 * MS, 4 * MS],
              ["%while.2 = (s32[64]) while(...)", 10 * MS, 29 * MS]]
    for i, t in enumerate((10, 19, 28)):
        t *= MS
        device += [["%gt.1 = pred[] compare(...)", t, MS],
                   ["%select_n.3 = s32[8] select(...)", t + 1 * MS, 2 * MS],
                   ["%fusion.7 = s32[64] fusion(...)", t + 3 * MS, 3 * MS],
                   ["%while.9 = (u32[]) while(...)", t + 6 * MS, 2 * MS],
                   ["%add.9 = u32[] add(...)", t + 6 * MS, MS // 2],
                   ["%add.9 = u32[] add(...)", t + 7 * MS, MS // 2],
                   ["%select.4 = s32[64] select(...)", t + 8 * MS, MS]]
    device += [["%gt.1 = pred[] compare(...)", 37 * MS, MS],
               ["%constant.1 = s32[] constant(0)", 50 * MS, 10 * MS]]
    host = [["window", 0, 100 * MS], ["dispatch", 0, MS]]
    return {"device": {"/device:TPU:0": device}, "host": host}


def test_split_sums_to_busy_and_counts_ticks():
    events = made_up()
    split = scope_split(events, scope_map(HLO, NAMES))
    busy = reduce_events(events)["busy_s"]
    assert busy == pytest.approx(0.05)
    assert sum(split["scope_s"].values()) == pytest.approx(busy)
    assert split["scope_s"] == pytest.approx({
        UNSCOPED: 0.007 + 0.010,        # scan loop's self time, constant
        "user_write": 0.004,
        # loop self time: 29 - 3 x (1 + 2 + 3 + 2 + 1) - 1 ms
        "gc_tick": 0.001,
        "gc_tick/guard": 0.004,
        "gc_tick/select": 0.006,
        # the fusion 3 ms, the nested loop's self time and its op 1 ms each,
        # per tick
        "gc_tick/rewrite": 0.009 + 0.003 + 0.003,
        "gc_tick/merge": 0.003})
    # the nested loop's op ran 6 times, the other body ops 3 times
    assert split["gc_ticks"] == 3
    line = scope_line(split)
    assert line.startswith("scopes: (none) 34.00%") and "3 GC ticks" in line


def test_no_tick_counts_zero():
    events, m = made_up(), scope_map(HLO, NAMES)
    events["device"]["/device:TPU:0"] = [
        e for e in events["device"]["/device:TPU:0"]
        if m.get(instruction(e[0])) not in TICK_BODY]
    assert scope_split(events, m)["gc_ticks"] == 0
    events["device"] = {}
    assert scope_split(events, {}) == {"scope_s": {}, "gc_ticks": 0}


def test_reduce_events_is_untouched_by_the_split():
    """The reduction every cell's traced run reports keeps its keys and
    values; the split reads the same events without changing them."""
    events = made_up()
    before = reduce_events(events)
    kept = copy.deepcopy(events)
    scope_split(events, scope_map(HLO, NAMES))
    assert events == kept
    assert reduce_events(events) == before
    assert set(before) == {"window_s", "busy_s", "devices", "op_s",
                           "layout_copy_s", "idle_s"}


def context(scope_s, ticks, reclaimed=0, steps=1000, volumes=47):
    return {"scope_s": scope_s, "gc_ticks": ticks, "steps": steps,
            "volumes": volumes, "before": {"reclaimed": 100},
            "after": {"reclaimed": 100 + reclaimed}}


def test_readers_on_a_context_without_gc():
    # a fill: the guard runs, no tick does
    ctx = context({UNSCOPED: 0.01, "user_write": 0.09, "gc_tick": 0.002,
                   "gc_tick/guard": 0.008}, 0)
    assert load_reader("user_write_us_per_step")(ctx) == \
        (pytest.approx(90.0), "us")
    assert load_reader("gc_tick_us_per_step")(ctx) == \
        (pytest.approx(10.0), "us")
    for name in PER_TICK:
        assert load_reader(name)(ctx) is None
    # a program without scopes: everything is unscoped, nothing to read
    ctx = context({UNSCOPED: 0.1}, 0)
    for name in PER_STEP + PER_TICK:
        assert load_reader(name)(ctx) is None
    assert load_reader("user_write_us_per_step")(
        context({"user_write": 0.1}, 0, steps=0)) is None


def test_readers_on_a_made_up_context():
    ctx = context({UNSCOPED: 0.01, "user_write": 0.08, "gc_tick": 0.05,
                   "gc_tick/guard": 0.01, "gc_tick/select": 0.1,
                   "gc_tick/rewrite": 0.3, "gc_tick/merge": 0.2}, 20,
                  reclaimed=47)
    want = {"user_write_us_per_step": (80.0, "us"),
            "gc_tick_us_per_step": (660.0, "us"),
            "gc_select_us_per_tick": (5000.0, "us"),
            "gc_rewrite_us_per_tick": (15000.0, "us"),
            "gc_merge_us_per_tick": (10000.0, "us"),
            "gc_tick_occupancy_pct": (5.0, "%")}
    for name, (value, unit) in want.items():
        assert load_reader(name)(ctx) == (pytest.approx(value), unit), name


def test_tick_slice_keeps_the_whole_first_tick_loop():
    from scope_split import tick_slice
    events = made_up()
    m = scope_map(HLO, NAMES)
    piece = tick_slice(events, m)
    ops = piece["device"]["/device:TPU:0"]
    # the window's first 20 ms, and the loop 10-39 whole
    assert [e for e in events["device"]["/device:TPU:0"]
            if e[1] + e[2] <= 39 * MS and e[0] != "%while.1 = (s32[64]) "
            "while(...)"] == ops
    assert scope_split(piece, piece["scopes"])["gc_ticks"] == 3
    assert set(piece["scopes"]) == {instruction(e[0]) for e in ops} - {
        "while.1"}


def test_small_cell_chunk_maps_every_scope():
    """The compiled chunk of a small fleet, as the harness builds it, has
    ops under all six scopes."""
    from harness import build_program, compile_programs
    from repro.core.jaxsim import SCOPES
    from small import small_cell
    prog = build_program(small_cell("sepbit_1g_x47.zipf_steady").config)
    _, chunk = compile_programs(prog, 8)
    m = scope_map(chunk.as_text(), SCOPES)
    assert set(m.values()) == SIX


def test_traced_window_at_small_size():
    """The tool's window on the CPU: set-up, traced chunks, the map; the
    CPU has no device trace, so the split is empty."""
    from scope_split import traced_window
    from small import small_cell
    got = traced_window(small_cell("sepbit_1g_x47.zipf_steady"), 2 ** 31 + 7,
                        2)
    assert got["steps"] == 2 * 256 and got["volumes"] == 2
    assert got["after"]["user_writes"] - got["before"]["user_writes"] == \
        2 * 256 * 2
    assert set(got["scopes"].values()) == SIX
    assert scope_split(got["events"], got["scopes"])["scope_s"] == {}


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_v5e_zipf_slice(recorded):
    """A slice of a ``sepbit_1g_x47.zipf_steady`` window traced on a v5e
    (``scope_split.py --keep``): the window's first 20 ms and its first GC
    tick loop whole, with the scope map of its ops (``scope_map`` of the
    same chunk compiled for a described v5e, whose instruction names are
    the trace's) and the split computed from them. The split reproduces
    the shares and the tick count stored with it, and agrees with a plain
    recount: each stretch of the timeline given to the innermost op over
    it, and the runs of the largest merge op."""
    import numpy as np
    split = scope_split(recorded, recorded["scopes"])
    assert split["gc_ticks"] == recorded["split"]["gc_ticks"] >= 1
    assert split["scope_s"] == pytest.approx(recorded["split"]["scope_s"],
                                             rel=1e-9)
    assert set(split["scope_s"]) == SIX | {UNSCOPED}
    r = reduce_events(recorded)
    assert sum(split["scope_s"].values()) == pytest.approx(r["busy_s"],
                                                           rel=1e-3)

    (_, lo, dur), = [e for e in recorded["host"] if e[0] == "window"]
    ops, = recorded["device"].values()
    names = sorted(split["scope_s"])
    cuts = [(max(s, lo), min(s + d, lo + dur)) for _, s, d in ops]
    edges = np.unique(cuts)
    owner = np.full(len(edges) - 1, -1)
    for (name, _, d), cut in sorted(zip(ops, cuts), key=lambda p: -p[0][2]):
        if cut[1] > cut[0]:
            a, b = np.searchsorted(edges, cut)
            owner[a:b] = names.index(
                recorded["scopes"].get(instruction(name), UNSCOPED))
    width = np.diff(edges)[owner >= 0]
    painted = np.bincount(owner[owner >= 0], weights=width,
                          minlength=len(names))
    for i, name in enumerate(names):
        assert split["scope_s"][name] == pytest.approx(painted[i] * 1e-9,
                                                       rel=1e-6)
    merge = [e for e in ops
             if recorded["scopes"].get(instruction(e[0])) == "gc_tick/merge"]
    largest = max(merge, key=lambda e: e[2])[0]
    assert split["gc_ticks"] == sum(e[0] == largest for e in ops)
