"""Named scopes on the tick engine's fleet path (`jaxsim.SCOPES`): each one
reaches the compiled fleet program as op metadata, and nothing else in the
program changes with them."""

import contextlib
import functools
import re

import jax
import numpy as np
import pytest

from repro.core import jaxsim
from repro.core.tracegen import make_fleet

CFG = jaxsim.JaxSimConfig(n_lbas=128, segment_size=16, scheme="sepbit")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# what the compiled text holds besides instructions: op metadata and the
# stack-frame tables it points into
METADATA = re.compile(r", metadata=\{[^}]*\}")
TABLES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*?\n",
    re.M)


def compiled_fleet(masked: bool) -> str:
    """Optimized HLO text of a two-volume fleet replay."""
    traces = make_fleet("mixed", 2, CFG.n_lbas, 2 * CFG.n_lbas, seed=5)
    if not masked:
        traces = [t[:min(map(len, traces))] for t in traces]
    padded = jaxsim.pad_fleet(traces)
    nxts = np.full_like(padded, -1)
    pols = jaxsim.broadcast_policies(CFG, len(traces))
    body = jax.jit(functools.partial(jaxsim.fleet_body, CFG, masked))
    return body.lower(padded, nxts, pols).compile().as_text()


def scopes_in(text: str) -> set[str]:
    return {"/".join(p for p in op.split("/") if p in jaxsim.SCOPES)
            for op in OP_NAME.findall(text)} - {""}


@pytest.mark.parametrize("masked", [False, True])
def test_fleet_program_carries_every_scope(masked):
    """Every name of `SCOPES` is a whole segment of some op's name, the
    tick's parts only inside the tick."""
    got = scopes_in(compiled_fleet(masked))
    assert got == {"user_write", "gc_tick", "gc_tick/guard",
                   "gc_tick/select", "gc_tick/rewrite", "gc_tick/merge"}


@pytest.mark.parametrize("masked", [False, True])
def test_scopes_leave_the_compiled_program_unchanged(masked, monkeypatch):
    """Without the scopes the compiled instructions are the same, once the
    metadata that carries them is stripped."""
    def strip(text):
        return METADATA.sub("", TABLES.sub("", text))

    scoped = compiled_fleet(masked)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled_fleet(masked)
    assert scopes_in(bare) == set()
    assert "metadata=" not in strip(scoped)
    assert strip(scoped) == strip(bare)
