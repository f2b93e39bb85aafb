"""Device self time of the rewrite (the vmapped ``jaxsim._gc_once``),
scope ``gc_tick/rewrite``, per GC tick iteration in the traced window.
Nothing to read where no tick ran."""


def read(ctx):
    if ctx["gc_ticks"] == 0:
        return None
    t = ctx["scope_s"].get("gc_tick/rewrite", 0.0)
    return 1e6 * t / ctx["gc_ticks"], "us"
