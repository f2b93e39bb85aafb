"""Device time in XLA layout copies (``copy``/``transpose`` operations)
over device busy time, in the traced window."""


def read(ctx):
    tr = ctx["trace"]
    if tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["layout_copy_s"] / tr["busy_s"], "%"
