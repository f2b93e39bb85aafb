"""Read/write manifests over the state pytree, from jaxpr var identity.

A state leaf an entry point does not touch appears in the jaxpr as the
*same* ``Var`` object in ``outvars`` as in ``invars`` (an identity
pass-through survives tracing untouched). So, per state key:

* **write** — the out slot is not the very invar that carried the key in
  (a new producer, or a literal, replaced the value);
* **read** — the invar feeds any equation, or is aliased into a *different*
  output slot (returning another scheme's table as your class output is a
  read of that table).

Sub-jaxprs never capture state invars behind the analysis' back: ``cond``
branches, ``scan`` bodies and ``jit`` callees all receive their operands
through the enclosing equation's ``invars``.
"""

from __future__ import annotations

import dataclasses

from .walker import is_literal


@dataclasses.dataclass(frozen=True)
class Manifest:
    """Per-entry-point state-key footprint (sorted, deterministic)."""

    reads: tuple[str, ...]
    writes: tuple[str, ...]

    def as_dict(self):
        return {"reads": list(self.reads), "writes": list(self.writes)}


def state_manifest(rec) -> Manifest:
    """Manifest for one :class:`~.tracing.TraceRecord` with state slots."""
    jaxpr = rec.jaxpr
    used = set()
    for eqn in jaxpr.eqns:
        used.update(a for a in eqn.invars if not is_literal(a))

    invar_of = {k: jaxpr.invars[i] for k, i in rec.state_in.items()}
    reads, writes = set(), set()
    for key, var in invar_of.items():
        if var in used:
            reads.add(key)
    for key, j in rec.state_out.items():
        out_atom = jaxpr.outvars[j]
        if key not in invar_of or out_atom is not invar_of[key]:
            writes.add(key)
    # an invar aliased into someone else's output slot is a read of it
    own_slot = {k: rec.state_out.get(k) for k in invar_of}
    for j, out_atom in enumerate(jaxpr.outvars):
        for key, var in invar_of.items():
            if out_atom is var and j != own_slot[key]:
                reads.add(key)
    return Manifest(reads=tuple(sorted(reads)), writes=tuple(sorted(writes)))
