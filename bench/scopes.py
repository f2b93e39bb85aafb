"""Device time by named scope: which part of the fleet step a device op
belongs to.

The program wraps the parts of its fleet step in ``jax.named_scope``
(``repro.core.jaxsim.SCOPES``). A scope reaches the compiled program only as
the ``op_name`` of each instruction's metadata, so ``scope_map`` reads it
from the optimized HLO text of the compiled chunk (``Compiled.as_text()``):
the module whose instruction names (``%fusion.414 = ...``) the v5e trace
prints. ``scope_split`` then sums the traced window's device self time by
scope and counts GC tick iterations.

An instruction's scope is the scope names among the ``/``-separated
segments of its ``op_name``, joined by ``/``: ``.../gc_tick/while/body/
rewrite/vmap()/gather`` is ``gc_tick/rewrite``, and ``select_n`` is not
``select``. An instruction the compiler made without an ``op_name`` takes
the scope that most instructions of the computations it calls carry (a
fusion, a loop), else the scope most of its users take (a sort, a
reshape or a copy made for them). One that still has no scope takes that
of the loop, fusion or call whose computation holds it.
"""

from __future__ import annotations

import re
from collections import Counter, defaultdict

from trace_reduce import _clip, _self_times

UNSCOPED = "(none)"
# ops of the tick loop's body: each runs once per tick
TICK_BODY = ("gc_tick/select", "gc_tick/rewrite", "gc_tick/merge")

COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|branch_computations)="
    r"(\{[^}]*\}|%[\w.\-]+)")
REF = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str, names) -> str | None:
    """The scope of one ``op_name``, or None where it names none."""
    return "/".join(p for p in op_name.split("/") if p in names) or None


def scope_map(hlo_text: str, names) -> dict[str, str]:
    """``{instruction name: scope}`` of an optimized HLO module's text, for
    the scope names ``names``; instructions under no scope are left out."""
    own: dict[str, str | None] = {}             # scope of the op_name
    bare: set[str] = set()                      # instructions with no op_name
    where: dict[str, str] = {}                  # instruction -> computation
    calls: dict[str, list[str]] = {}            # fusion or loop -> callees
    caller: dict[str, str] = {}                 # computation -> instruction
    users: dict[str, list[str]] = defaultdict(list)
    body: dict[str, list[str]] = defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        op = OP_NAME.search(line)
        own[name] = scope_of(op.group(1), names) if op else None
        if not op:
            bare.add(name)
        where[name] = comp
        body[comp].append(name)
        called = set()
        for kind, refs in CALLED.findall(line):
            for c in REF.findall(refs):
                called.add(c)
                if kind != "to_apply":  # reducers are shared, never run alone
                    calls.setdefault(name, []).append(c)
                    caller.setdefault(c, name)
        for operand in REF.findall(line[m.end():]):
            if operand not in called:
                users[operand].append(name)

    def most(scopes):
        votes = Counter(s for s in scopes if s)
        return min(votes, key=lambda s: (-votes[s], s)) if votes else None

    resolved: dict[str, str | None] = {}

    def scope(name):
        if name not in resolved:
            resolved[name] = None            # a cycle resolves to no scope
            s = own[name]
            if s is None and name in bare:
                s = (most(own[i] for c in calls.get(name, ()) for i in body[c])
                     or most(scope(u) for u in users[name]))
            if s is None and where[name] in caller:
                s = scope(caller[where[name]])
            resolved[name] = s
        return resolved[name]

    return {n: s for n in own if (s := scope(n))}


def instruction(event_name: str) -> str:
    """The HLO instruction name of a device event (``%fusion.414 = ...``
    on a v5e)."""
    return event_name.lstrip("%").split(" = ", 1)[0]


def scope_split(events: dict, scopes: dict[str, str]) -> dict:
    """Within the ``window`` span of ``events`` (as ``trace_reduce.
    load_events`` gives them): ``scope_s``, device self time per scope,
    ``UNSCOPED`` for ops under none, averaged over the devices that ran
    any, so that it sums to ``reduce_events``'s ``busy_s``; and
    ``gc_ticks``, GC tick iterations: the count that most ops of the tick's
    body ran, 0 where none ran."""
    _, lo, dur = max((e for e in events["host"] if e[0] == "window"),
                     key=lambda e: e[2])
    ns: dict[str, float] = defaultdict(float)
    ticks, n = 0, 0
    for ops in events["device"].values():
        ops = _clip(ops, lo, lo + dur)
        if not ops:
            continue
        n += 1
        for name, t in _self_times(ops).items():
            ns[scopes.get(instruction(name), UNSCOPED)] += t
        runs = Counter(name for name, _, _ in ops
                       if scopes.get(instruction(name)) in TICK_BODY)
        if runs:
            counts = Counter(runs.values())
            ticks += min(counts, key=lambda c: (-counts[c], c))
    if not n:
        return {"scope_s": {}, "gc_ticks": 0}
    return {"scope_s": {k: v / n / 1e9 for k, v in ns.items()},
            "gc_ticks": round(ticks / n)}


def scope_line(split: dict) -> str:
    """``scopes:`` line: each scope's share of busy time, and the ticks."""
    busy = sum(split["scope_s"].values())
    shares = ", ".join(
        f"{k} {100 * v / busy:.2f}%" for k, v in
        sorted(split["scope_s"].items(), key=lambda kv: -kv[1])) or "none"
    return f"scopes: {shares}; {split['gc_ticks']} GC ticks"
