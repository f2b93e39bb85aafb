"""Share of the volume slots in the traced window's GC ticks that
reclaimed a segment: segments reclaimed over ticks times volumes. Every
tick carries the whole fleet; the rest of the slots were masked no-ops.
Nothing to read where no tick ran."""


def read(ctx):
    if ctx["gc_ticks"] == 0:
        return None
    reclaimed = ctx["after"]["reclaimed"] - ctx["before"]["reclaimed"]
    return 100.0 * reclaimed / (ctx["gc_ticks"] * ctx["volumes"]), "%"
