"""Rounds completed in the timed window over the window's whole length
(the call that straddles the end counts, with its time); host clock."""


def read(ctx):
    return ctx["rounds"] / ctx["window_s"]
