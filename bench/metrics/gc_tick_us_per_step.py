"""Device self time of the GC tick (scope ``gc_tick``: all of
``jaxsim.fleet_gc_tick``, its guard, select, rewrite and merge included) per
fleet step, in the traced window. Nothing to read where the program has no
such scope."""


def read(ctx):
    tick = [t for k, t in ctx["scope_s"].items()
            if k == "gc_tick" or k.startswith("gc_tick/")]
    if not tick or ctx["steps"] == 0:
        return None
    return 1e6 * sum(tick) / ctx["steps"], "us"
