"""Device busy time per round in the traced window (ms): the union of
device op intervals over the rounds completed in the window."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["rounds"] == 0:
        return None
    return 1e3 * tr["busy_s"] / ctx["rounds"]
