"""Finds a piece of the benchmark by its name: ``bench/<kind>/<name>.py``.

Traffic phases (``phases``), annotation streams (``annotations``), plain
references (``references``) and per-layer metric readers (``metrics``)
each sit in a file of their own, so a later cell that needs a new one adds
a file and edits none.
"""

from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@functools.cache
def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    if not NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        have = sorted(p.stem for p in (BENCH / kind).glob("*.py"))
        raise KeyError(f"no {kind} {name!r}; have {have}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
