"""Jaxpr traversal shared by the analysis passes.

Everything downstream of tracing works on ``jax.core`` jaxprs: equations,
``Var``/``Literal`` atoms, and the sub-jaxprs that structured primitives
(``cond`` branches, ``scan``/``while`` bodies, ``jit``'s inner function,
``pallas_call``'s kernel body) carry in their params. This module is the
one place that knows how to find those sub-jaxprs and how to classify an
equation's effects, so the lints stay jaxpr-version-agnostic.
"""

from __future__ import annotations

from jax import core as jax_core
from jax.extend.core import ClosedJaxpr, Literal


def is_literal(atom) -> bool:
    return isinstance(atom, Literal)


def subjaxprs(eqn):
    """Every sub-jaxpr an equation carries, normalized to raw ``Jaxpr``.

    ``ClosedJaxpr`` params (jit/cond/scan/...) are paired with their consts;
    raw ``Jaxpr`` params (``pallas_call``) get ``None`` consts — their
    constvars' values are unknown to the analysis.
    Yields ``(jaxpr, consts_or_None)``.
    """
    for sub in jax_core.jaxprs_in_params(eqn.params):
        if isinstance(sub, ClosedJaxpr):
            yield sub.jaxpr, sub.consts
        else:
            yield sub, None


def iter_eqns(jaxpr):
    """All equations, recursing into every sub-jaxpr (pre-order)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub, _ in subjaxprs(eqn):
            yield from iter_eqns(sub)


# -- collective classification -------------------------------------------------
# Cross-device communication primitives: anything that moves data between
# shards of a mesh axis. The fleet engine must never emit one over the
# "fleet" axis — volumes are independent logs (lint SA502).

_COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "psum2", "psum_invariant", "pmax", "pmin", "pgather",
    "all_gather", "all_to_all", "ppermute", "pbroadcast", "reduce_scatter",
})


def collective_axes(eqn) -> tuple:
    """Mesh axis names a collective equation communicates over; ``()`` for
    non-collective equations."""
    if eqn.primitive.name not in _COLLECTIVE_PRIMITIVES:
        return ()
    axes = eqn.params.get("axes", None)
    if axes is None:
        axes = eqn.params.get("axis_name", ())
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


# -- effect classification -----------------------------------------------------
# Scheme bodies must be pure *to the host*: no callbacks, no infeed/outfeed.
# jax-internal state effects (the ReadEffect/WriteEffect that Pallas kernel
# bodies carry on their ref get/swap equations) are the mechanism of the
# kernel DSL itself, not an escape hatch, so they do not count.

_IMPURE_PRIMITIVE_FRAGMENTS = ("callback", "infeed", "outfeed", "outside_call")
_IMPURE_EFFECT_FRAGMENTS = ("callback", "debug", "print", "io_effect", "host")


def impurity_of(eqn) -> str | None:
    """A human-readable reason this equation breaks the purity contract,
    or None if it is pure (to the host)."""
    name = eqn.primitive.name
    for frag in _IMPURE_PRIMITIVE_FRAGMENTS:
        if frag in name:
            return f"primitive {name!r}"
    for eff in eqn.effects:
        eff_name = type(eff).__name__.lower()
        for frag in _IMPURE_EFFECT_FRAGMENTS:
            if frag in eff_name:
                return f"effect {type(eff).__name__} on primitive {name!r}"
    return None
