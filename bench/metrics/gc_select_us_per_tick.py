"""Device self time of victim selection (``jaxsim._select_victims_fleet``),
scope ``gc_tick/select``, per GC tick iteration in the traced window.
Nothing to read where no tick ran."""


def read(ctx):
    if ctx["gc_ticks"] == 0:
        return None
    t = ctx["scope_s"].get("gc_tick/select", 0.0)
    return 1e6 * t / ctx["gc_ticks"], "us"
