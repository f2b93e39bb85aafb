"""Device self time of the user write (scope ``user_write``: the vmapped
``jaxsim._user_write``) per fleet step, in the traced window. Nothing to
read where the program has no such scope."""


def read(ctx):
    t = ctx["scope_s"].get("user_write")
    if t is None or ctx["steps"] == 0:
        return None
    return 1e6 * t / ctx["steps"], "us"
