"""Share of the traced window in which no op ran on the device (%):
100 x (1 - union of device op intervals / window), from the trace."""


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None else tr["idle_share"]
