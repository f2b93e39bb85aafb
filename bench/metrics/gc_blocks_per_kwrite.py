"""Blocks rewritten by GC per 1,000 user writes over the window, from the
fleet's state counters. Nothing to read where the window reclaimed no
segment."""


def read(ctx):
    b, a = ctx["before"], ctx["after"]
    user = a["user_writes"] - b["user_writes"]
    if a["reclaimed"] == b["reclaimed"] or user <= 0:
        return None
    return 1000.0 * (a["gc_writes"] - b["gc_writes"]) / user, "blocks/kwrite"
