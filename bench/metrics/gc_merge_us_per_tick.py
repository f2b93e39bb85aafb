"""Device self time of the merge (the masked select of every state leaf),
scope ``gc_tick/merge``, per GC tick iteration in the traced window.
Nothing to read where no tick ran."""


def read(ctx):
    if ctx["gc_ticks"] == 0:
        return None
    t = ctx["scope_s"].get("gc_tick/merge", 0.0)
    return 1e6 * t / ctx["gc_ticks"], "us"
