"""Plain reference replay of one log-structured volume, in numpy.

It follows the deployment described by a configuration file under
``bench/configs/`` that names it (``"reference": "sepbit"``), one volume's
values at a time, and imports nothing of the system under test. One volume
is a fixed pool of ``n_segments`` physical segments of ``segment_size``
block slots each. Every block written by the user is appended to the open
segment of its class; its old copy, if any, is invalidated. Garbage
collection (GC) runs after each user write while the garbage proportion
(GP, invalid slots over occupied slots) exceeds the configured threshold,
at most ``max_gc_per_write`` victims per write, and stops early when no
sealed segment holds garbage.

Placement is SepBIT (FAST'22, Algorithm 1), six classes:

* user writes: class 0 when the block's lifespan ``v`` (time since its last
  user write) is below the estimate ``ell``, else class 1. A first write has
  no lifespan; it goes to class 0 while ``ell`` is unset (no estimate yet,
  so every user write is treated alike) and to class 1 afterwards;
* GC rewrites out of a class-0 victim: class 2; other victims: class 3, 4
  or 5 by age ``g`` (time since the block's last user write) against
  ``4 * ell`` and ``16 * ell``;
* ``ell`` is the mean lifespan (reclaim time minus creation time) of the
  last ``nc_window`` reclaimed class-0 segments.

Victims are chosen by Cost-Benefit, ``(1 - u) * age / (1 + u)`` with
``u`` the live fraction and ``age`` the time since the segment sealed,
evaluated exactly (an integer numerator over an integer denominator,
divided once), ties going to the lowest physical segment. Time is the count
of user writes so far: a user write at time ``t`` seals its segment at
``t``, and the GC that follows runs at ``t + 1``.

Allocation: each class starts with its own open segment (segments
``0 .. n_classes - 1``, created at time 0). A user write that fills its
segment seals it and opens the lowest free segment for that class, created
at that time. A GC pass first reserves the ``n_classes`` lowest free
segments, one per class in class order, before it releases the victim; a
class whose open segment fills during the pass continues in its reserved
segment. Running out of free segments is an error here (the deployment is
sized so that it does not happen).

:class:`Volume` keeps the same physical arrays a log-structured store keeps
(per-LBA location, per-slot LBA, user-write time and validity), so the
comparison in ``bench/check.py`` reads a reference volume and the system's
state alike. ``lose_every`` turns the reference into the control: every
``lose_every``-th user write is acknowledged (counted, time advances) but
never persisted.
"""

from __future__ import annotations

import math
from fractions import Fraction

import numpy as np

N_CLASSES = 6


class PoolExhausted(RuntimeError):
    """The volume ran out of free segments; the deployment is undersized."""


class Volume:
    def __init__(self, n_lbas: int, segment_size: int, n_segments: int,
                 gp_threshold: float, nc_window: int, max_gc_per_write: int,
                 lose_every: int = 0):
        if n_segments < 2 * N_CLASSES:
            raise ValueError("too few segments for one open per class")
        self.n_lbas, self.s, self.S = n_lbas, segment_size, n_segments
        gp = Fraction(str(gp_threshold))
        self.gp_num, self.gp_den = gp.numerator, gp.denominator
        self.nc_window = nc_window
        self.max_gc = max_gc_per_write
        self.lose_every = lose_every
        S, s = n_segments, segment_size
        self.seg_lba = np.zeros((S, s), np.int64)
        self.seg_utime = np.zeros((S, s), np.int64)
        self.seg_valid = np.zeros((S, s), bool)
        self.seg_n = np.zeros(S, np.int64)
        self.seg_nvalid = np.zeros(S, np.int64)
        self.seg_cls = np.zeros(S, np.int64)
        self.seg_state = np.zeros(S, np.int64)   # 0 free, 1 open, 2 sealed
        self.seg_ctime = np.zeros(S, np.int64)
        self.seg_stime = np.zeros(S, np.int64)
        self.open = list(range(N_CLASSES))
        self.seg_state[:N_CLASSES] = 1
        self.seg_cls[:N_CLASSES] = np.arange(N_CLASSES)
        self.loc_seg = [-1] * n_lbas
        self.loc_off = [0] * n_lbas
        self.last_uw = [-1] * n_lbas
        self.t = 0
        self.occupied = 0
        self.valid = 0
        self.user_writes = 0
        self.gc_writes = 0
        self.reclaimed = 0
        self.ell = math.inf
        self.ell_tot = 0
        self.nc = 0
        self.class_user = [0] * N_CLASSES
        self.class_gc = [0] * N_CLASSES

    # -- segments -------------------------------------------------------------
    def _free_segments(self, count: int) -> list[int]:
        return [int(i) for i in np.flatnonzero(self.seg_state == 0)[:count]]

    def _seal_and_open(self, cls: int, fresh: int | None) -> None:
        if fresh is None:
            raise PoolExhausted(f"no free segment at t={self.t}")
        old = self.open[cls]
        self.seg_state[old] = 2
        self.seg_stime[old] = self.t
        self.seg_state[fresh] = 1
        self.seg_cls[fresh] = cls
        self.seg_ctime[fresh] = self.t
        self.open[cls] = fresh

    # -- user writes ----------------------------------------------------------
    def write(self, lba: int) -> None:
        t = self.t
        self.user_writes += 1
        if self.lose_every and (t + 1) % self.lose_every == 0:
            self.t = t + 1          # acknowledged, never persisted
            self._collect()
            return
        old = self.loc_seg[lba]
        if old >= 0:
            self.seg_valid[old, self.loc_off[lba]] = False
            self.seg_nvalid[old] -= 1
            self.valid -= 1
            cls = 0 if t - self.last_uw[lba] < self.ell else 1
        else:
            cls = 0 if self.ell == math.inf else 1
        sid = self.open[cls]
        off = int(self.seg_n[sid])
        self.seg_lba[sid, off] = lba
        self.seg_utime[sid, off] = t
        self.seg_valid[sid, off] = True
        self.seg_n[sid] = off + 1
        self.seg_nvalid[sid] += 1
        self.loc_seg[lba] = sid
        self.loc_off[lba] = off
        self.last_uw[lba] = t
        self.occupied += 1
        self.valid += 1
        self.class_user[cls] += 1
        if off + 1 == self.s:
            free = self._free_segments(1)
            self._seal_and_open(cls, free[0] if free else None)
        self.t = t + 1
        self._collect()

    def replay(self, lbas) -> None:
        for lba in np.asarray(lbas).tolist():
            self.write(lba)

    # -- garbage collection ---------------------------------------------------
    def _over_threshold(self) -> bool:
        garbage = self.occupied - self.valid
        return garbage * self.gp_den > self.gp_num * self.occupied

    def _victim(self) -> int:
        n, nv = self.seg_n, self.seg_nvalid
        eligible = (self.seg_state == 2) & (nv < n)
        if not eligible.any():
            return -1
        age = np.maximum(self.t - self.seg_stime, 0)
        score = ((n - nv) * age).astype(np.float64) / np.maximum(n + nv, 1)
        return int(np.argmax(np.where(eligible, score, -np.inf)))

    def _collect(self) -> None:
        for _ in range(self.max_gc):
            if not self._over_threshold():
                return
            victim = self._victim()
            if victim < 0:
                return
            self._rewrite(victim)

    def _rewrite(self, victim: int) -> None:
        t, s = self.t, self.s
        vcls = int(self.seg_cls[victim])
        if vcls == 0:
            self.nc += 1
            self.ell_tot += t - int(self.seg_ctime[victim])
            if self.nc >= self.nc_window:
                self.ell = self.ell_tot / self.nc
                self.nc, self.ell_tot = 0, 0
        reserved = self._free_segments(N_CLASSES)
        n_v = int(self.seg_n[victim])
        live = np.flatnonzero(self.seg_valid[victim, :n_v])
        lbas = self.seg_lba[victim, live]
        utimes = self.seg_utime[victim, live]
        if vcls == 0:
            classes = np.full(len(live), 2)
        else:
            g = t - utimes
            classes = 3 + (g >= 4 * self.ell) + (g >= 16 * self.ell)
        for cls in range(N_CLASSES):
            pick = classes == cls
            for lba, ut in zip(lbas[pick].tolist(), utimes[pick].tolist()):
                sid = self.open[cls]
                off = int(self.seg_n[sid])
                self.seg_lba[sid, off] = lba
                self.seg_utime[sid, off] = ut
                self.seg_valid[sid, off] = True
                self.seg_n[sid] = off + 1
                self.seg_nvalid[sid] += 1
                self.loc_seg[lba] = sid
                self.loc_off[lba] = off
                if off + 1 == s:
                    self._seal_and_open(
                        cls, reserved[cls] if cls < len(reserved) else None)
            self.class_gc[cls] += int(pick.sum())
        k = len(live)
        self.seg_state[victim] = 0
        self.seg_valid[victim] = False
        self.seg_n[victim] = 0
        self.seg_nvalid[victim] = 0
        self.occupied += k - n_v
        self.gc_writes += k
        self.reclaimed += 1

    # -- the comparison's view -------------------------------------------------
    def as_state(self) -> dict:
        """The volume in the layout ``bench/check.py`` compares."""
        return {
            "loc_seg": np.asarray(self.loc_seg, np.int64),
            "loc_off": np.asarray(self.loc_off, np.int64),
            "seg_lba": self.seg_lba, "seg_utime": self.seg_utime,
            "seg_valid": self.seg_valid,
            "user_writes": self.user_writes, "gc_writes": self.gc_writes,
            "reclaimed": self.reclaimed, "overflow": 0,
            "class_user": np.asarray(self.class_user, np.int64),
            "class_gc": np.asarray(self.class_gc, np.int64),
        }


def replay_volume(config: dict, lbas, lose_every: int = 0) -> dict:
    """Replay ``lbas`` on a fresh volume of ``config``; the final state."""
    if (config["scheme"], config["selector"]) != ("sepbit", "cost_benefit"):
        raise ValueError(f"this reference replays SepBIT under Cost-Benefit, "
                         f"not {config['scheme']} under {config['selector']}")
    vol = Volume(config["n_lbas"], config["segment_size"],
                 config["n_segments"], config["gp_threshold"],
                 config["nc_window"], config["max_gc_per_write"],
                 lose_every=lose_every)
    vol.replay(lbas)
    return vol.as_state()
