"""The comparison that decides ``correct``.

Two numbers, each held to its limit in ``bench/limits.json``:

* ``readback_bad``, over every volume of the fleet: LBAs whose latest
  acknowledged write cannot be read back (the LBA's location does not hold
  that LBA, valid, with the time of its last write), never-written LBAs that
  have a location, and valid slots beyond one per written LBA. It needs only
  the writes that were fed, not the reference, so it covers the whole fleet.
* ``counter_gap``, over the volumes replayed by the reference: the largest
  difference in the volume's counters (user writes, GC writes, segments
  reclaimed, free-pool overflows, and user and GC writes per class) against
  the reference, over the reference's user writes.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

LIMITS_FILE = Path(__file__).resolve().parent / "limits.json"
COUNTERS = ("user_writes", "gc_writes", "reclaimed", "overflow")
CLASS_COUNTERS = ("class_user", "class_gc")


def load_limits() -> dict:
    return json.loads(LIMITS_FILE.read_text())


def last_write_times(lbas: np.ndarray, n_lbas: int) -> np.ndarray:
    """Per LBA, the step of its last write in ``lbas`` (-1: never)."""
    last = np.full(n_lbas, -1, np.int64)
    np.maximum.at(last, np.asarray(lbas, np.int64), np.arange(len(lbas)))
    return last


def readback_bad(state: dict, lbas: np.ndarray, n_lbas: int) -> int:
    last = last_write_times(lbas, n_lbas)
    seg = np.asarray(state["loc_seg"], np.int64)
    off = np.asarray(state["loc_off"], np.int64)
    seg_lba = np.asarray(state["seg_lba"])
    seg_valid = np.asarray(state["seg_valid"], bool)
    seg_utime = np.asarray(state["seg_utime"])
    rows, slots = seg_lba.shape
    written = last >= 0
    bad = int(np.count_nonzero(~written & (seg >= 0)))
    lba = np.flatnonzero(written)
    s, o = seg[lba], off[lba]
    inside = (s >= 0) & (s < rows) & (o >= 0) & (o < slots)
    lba, s, o = lba[inside], s[inside], o[inside]
    good = (seg_lba[s, o] == lba) & seg_valid[s, o] \
        & (seg_utime[s, o] == last[lba])
    bad += int(np.count_nonzero(~inside)) + int(np.count_nonzero(~good))
    bad += abs(int(np.count_nonzero(seg_valid)) - int(np.count_nonzero(written)))
    return bad


def counter_gap(program: dict, reference: dict) -> float:
    diffs = [abs(int(program[k]) - int(reference[k])) for k in COUNTERS]
    for k in CLASS_COUNTERS:
        diffs.extend(np.abs(np.asarray(program[k], np.int64)
                            - np.asarray(reference[k], np.int64)).tolist())
    return max(diffs) / max(int(reference["user_writes"]), 1)


def compare(states: list[dict], traces: np.ndarray, n_lbas: int,
            references: dict[int, dict]) -> dict:
    """The numbers compared. ``states[v]`` is volume ``v``'s final state
    (the system's, or a control's), ``traces[:, v]`` every write fed to it,
    ``references[v]`` the reference's final state of the sampled volumes."""
    bad = sum(readback_bad(st, traces[:, v], n_lbas)
              for v, st in enumerate(states))
    gap = max(counter_gap(states[v], ref) for v, ref in references.items())
    return {"readback_bad": bad, "counter_gap": gap}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value": ..., "limit": ...}})."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
