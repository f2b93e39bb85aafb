"""Device busy time in the traced window per fleet step replayed in it."""


def read(ctx):
    tr = ctx["trace"]
    if tr["devices"] == 0 or ctx["steps"] == 0:
        return None
    return 1e6 * tr["busy_s"] / ctx["steps"], "us"
