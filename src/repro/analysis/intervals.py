"""Best-effort interval abstract interpretation over jaxprs.

Drives two lints: **totality** (SA301 — is a scheme's class output provably
inside ``[0, n_classes)``?) and the **float index carry** detector (SA201 —
can an integer index round-trip through a float dtype whose mantissa cannot
represent it exactly?).

The domain is a single ``(lo, hi)`` pair of floats per value (infinities for
unknown), covering *every element* of an array value. The transfer rules are
deliberately conservative: any primitive without a rule maps to unbounded,
and opaque sub-jaxpr bodies (``scan``/``while``/``pallas_call``) are walked
with an unknown environment — their equations still reach the lint visitor,
but contribute nothing to bounds. ``jit`` and ``cond`` are the two
structured primitives interpreted *precisely*: jnp-level helpers such as
``jnp.clip`` / ``jnp.where`` / ``%`` lower to jit-wrapped sub-jaxprs, so
recursing into jit with the caller's operand intervals is what makes
literal clamp bounds visible at all.
"""

from __future__ import annotations

import math

import numpy as np

from .walker import is_literal, subjaxprs

INF = math.inf
UNKNOWN = (-INF, INF)
BOOL = (0.0, 1.0)

# Largest integer a float dtype represents exactly (2**mantissa_bits).
FLOAT_EXACT_INT = {
    "bfloat16": 2.0 ** 8,
    "float16": 2.0 ** 11,
    "float32": 2.0 ** 24,
    "float64": 2.0 ** 53,
}


def const_interval(x):
    """Interval of a concrete constant (array or scalar)."""
    try:
        arr = np.asarray(x)
        if arr.size == 0 or arr.dtype.kind not in "biufc":
            return UNKNOWN
        if arr.dtype.kind == "c":
            return UNKNOWN
        return (float(arr.min()), float(arr.max()))
    except (TypeError, ValueError, OverflowError):
        return UNKNOWN


def union(a, b):
    return (min(a[0], b[0]), max(a[1], b[1]))


def _mul_bound(a, b):
    # 0 * inf is the only ill-defined product; resolve it to 0 (sound for
    # the "n repetitions of x" uses below, where n == 0 means an empty sum).
    if a == 0 or b == 0:
        return 0.0
    return a * b


def _arith(name, ins, eqn):
    if not ins:
        return None
    a = ins[0]
    b = ins[1] if len(ins) > 1 else None
    if name == "add":
        return [(a[0] + b[0], a[1] + b[1])]
    if name == "sub":
        return [(a[0] - b[1], a[1] - b[0])]
    if name == "mul":
        cands = [_mul_bound(x, y) for x in a for y in b]
        return [(min(cands), max(cands))]
    if name in ("max",):
        return [(max(a[0], b[0]), max(a[1], b[1]))]
    if name in ("min",):
        return [(min(a[0], b[0]), min(a[1], b[1]))]
    if name in ("div", "floor_divide"):
        # precise only for a known-positive divisor; else unbounded
        if b[0] > 0:
            lo = min(a[0] / b[0], a[0] / b[1])
            hi = max(a[1] / b[0], a[1] / b[1])
            if name == "floor_divide":
                lo, hi = math.floor(lo), math.floor(hi)
            return [(lo, hi)]
        return [UNKNOWN]
    if name == "rem":
        m = max(abs(b[0]), abs(b[1]))
        if math.isfinite(m):
            return [(-m, m)]
        return [UNKNOWN]
    if name == "neg":
        return [(-a[1], -a[0])]
    if name == "abs":
        lo = 0.0 if a[0] <= 0 <= a[1] else min(abs(a[0]), abs(a[1]))
        return [(lo, max(abs(a[0]), abs(a[1])))]
    if name == "sign":
        return [(-1.0, 1.0)]
    if name == "floor":
        return [(math.floor(a[0]) if math.isfinite(a[0]) else a[0],
                 math.floor(a[1]) if math.isfinite(a[1]) else a[1])]
    if name in ("ceil", "round", "round_nearest_even"):
        return [(math.floor(a[0]) if math.isfinite(a[0]) else a[0],
                 math.ceil(a[1]) if math.isfinite(a[1]) else a[1])]
    return None


_PASS_THROUGH = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "copy",
    "stop_gradient", "transpose", "rev", "slice", "dynamic_slice",
    "reduce_max", "reduce_min", "reduce_and", "reduce_or", "real",
    "convert_element_type_pass",  # placeholder, handled explicitly
})

_COMPARISONS = frozenset({"lt", "le", "gt", "ge", "eq", "ne", "is_finite"})

_BOUNDED_UNARY = {
    "tanh": (-1.0, 1.0), "logistic": (0.0, 1.0), "erf": (-1.0, 1.0),
    "sin": (-1.0, 1.0), "cos": (-1.0, 1.0),
}


class IntervalAnalysis:
    """One pass over a closed jaxpr, computing output intervals and calling
    ``visitor(eqn, in_intervals)`` on every equation (including those inside
    opaque sub-jaxprs, where the intervals degrade to unknown)."""

    def __init__(self, visitor=None):
        self.visitor = visitor

    def run(self, closed_jaxpr, in_intervals):
        return self._jaxpr(closed_jaxpr.jaxpr,
                           [const_interval(c) for c in closed_jaxpr.consts],
                           list(in_intervals))

    # -- core walk -------------------------------------------------------------

    def _atom(self, atom, env):
        if is_literal(atom):
            return const_interval(atom.val)
        return env.get(atom, UNKNOWN)

    def _jaxpr(self, jaxpr, const_ivs, in_ivs):
        env = {}
        for var, iv in zip(jaxpr.constvars, const_ivs):
            env[var] = iv
        for var, iv in zip(jaxpr.invars, in_ivs):
            env[var] = iv
        for eqn in jaxpr.eqns:
            ins = [self._atom(a, env) for a in eqn.invars]
            if self.visitor is not None:
                self.visitor(eqn, ins)
            outs = self._eqn(eqn, ins)
            for var, iv in zip(eqn.outvars, outs):
                env[var] = iv
        return [self._atom(v, env) for v in jaxpr.outvars]

    def _opaque(self, eqn):
        # walk sub-jaxpr bodies with an unknown environment so the visitor
        # still sees their equations; outputs contribute no bounds
        for sub, consts in subjaxprs(eqn):
            const_ivs = ([const_interval(c) for c in consts]
                         if consts is not None
                         else [UNKNOWN] * len(sub.constvars))
            self._jaxpr(sub, const_ivs, [UNKNOWN] * len(sub.invars))
        return [UNKNOWN] * len(eqn.outvars)

    # -- transfer rules --------------------------------------------------------

    def _eqn(self, eqn, ins):
        name = eqn.primitive.name
        n_out = len(eqn.outvars)

        if name in ("jit", "closed_call", "core_call", "custom_jvp_call",
                    "custom_vjp_call", "custom_vjp_call_jaxpr"):
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if inner is not None and hasattr(inner, "jaxpr"):
                return self._jaxpr(
                    inner.jaxpr, [const_interval(c) for c in inner.consts],
                    list(ins))
            return self._opaque(eqn)

        if name == "cond":
            outs = None
            for br in eqn.params["branches"]:
                got = self._jaxpr(br.jaxpr,
                                  [const_interval(c) for c in br.consts],
                                  list(ins[1:]))
                outs = got if outs is None else [union(a, b)
                                                for a, b in zip(outs, got)]
            return outs if outs is not None else [UNKNOWN] * n_out

        arith = _arith(name, ins, eqn)
        if arith is not None:
            return arith

        if name in _COMPARISONS:
            return [BOOL]
        if name in ("and", "or", "xor", "not"):
            dtype = getattr(eqn.outvars[0].aval, "dtype", None)
            return [BOOL if dtype == np.bool_ else UNKNOWN]
        if name in _PASS_THROUGH:
            return [ins[0] if ins else UNKNOWN] * n_out
        if name == "select_n":
            out = ins[1]
            for case in ins[2:]:
                out = union(out, case)
            return [out]
        if name == "clamp":                       # clamp(min, operand, max)
            lo_b, x, hi_b = ins
            t = (max(x[0], lo_b[0]), max(x[1], lo_b[1]))
            return [(min(t[0], hi_b[0]), min(t[1], hi_b[1]))]
        if name == "convert_element_type":
            dtype = eqn.params.get("new_dtype")
            if dtype == np.bool_:
                return [BOOL]
            src = getattr(eqn.invars[0].aval, "dtype", None)
            iv = ins[0]
            if (dtype is not None and np.issubdtype(dtype, np.integer)
                    and src is not None and np.issubdtype(src, np.floating)):
                iv = (math.floor(iv[0]) if math.isfinite(iv[0]) else iv[0],
                      math.floor(iv[1]) if math.isfinite(iv[1]) else iv[1])
            return [iv]
        if name == "iota":
            dim = eqn.params["dimension"]
            return [(0.0, max(eqn.params["shape"][dim] - 1, 0))]
        if name in ("argmax", "argmin"):
            axes = eqn.params.get("axes", ())
            shape = eqn.invars[0].aval.shape
            n = 1
            for ax in axes:
                n *= shape[ax]
            return [(0.0, max(n - 1, 0))]
        if name == "reduce_sum":
            in_size = int(np.prod(eqn.invars[0].aval.shape or (1,)))
            out_size = int(np.prod(eqn.outvars[0].aval.shape or (1,)))
            n = in_size // max(out_size, 1)
            lo, hi = ins[0]
            return [(min(_mul_bound(n, lo), 0.0), max(_mul_bound(n, hi), 0.0))]
        if name == "clz":
            bits = np.dtype(eqn.invars[0].aval.dtype).itemsize * 8
            return [(0.0, float(bits))]
        if name == "population_count":
            bits = np.dtype(eqn.invars[0].aval.dtype).itemsize * 8
            return [(0.0, float(bits))]
        if name == "concatenate" or name == "pad":
            out = ins[0]
            for x in ins[1:]:
                out = union(out, x)
            return [out]
        if name == "dynamic_update_slice":
            return [union(ins[0], ins[1])]
        if name.startswith("scatter"):
            # scatter/scatter-add/...: untouched positions keep the operand's
            # value; touched ones get (a function of) the updates. Folding in
            # operand+updates covers add; plain set is union(operand, updates).
            upd = ins[-1]
            out = union(ins[0], upd)
            if "add" in name:
                out = union(out, (ins[0][0] + min(upd[0], 0.0),
                                  ins[0][1] + max(upd[1], 0.0)))
            return [out]
        if name == "gather":
            # out-of-bounds fill values depend on the gather mode; stay sound
            return [UNKNOWN]
        if name == "sort":
            return list(ins[:n_out]) if len(ins) >= n_out else [UNKNOWN] * n_out
        if name in _BOUNDED_UNARY:
            return [_BOUNDED_UNARY[name]]
        if name == "exp":
            lo = 0.0 if not math.isfinite(ins[0][0]) else math.exp(min(ins[0][0], 700))
            hi = INF if ins[0][1] > 700 else math.exp(ins[0][1])
            return [(lo, hi)]
        if name == "sqrt":
            return [(0.0, INF)]
        if name == "integer_pow":
            y = eqn.params["y"]
            lo, hi = ins[0]
            if not (math.isfinite(lo) and math.isfinite(hi)):
                if y % 2 == 0 or y <= 0:
                    return [UNKNOWN]
                return [ins[0]]
            cands = [lo ** y, hi ** y] + ([0.0] if lo <= 0 <= hi else [])
            return [(min(cands), max(cands))]

        return self._opaque(eqn)
