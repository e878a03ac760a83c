"""Seconds in the allocation solver during set-up: the program's
``solver/two_step`` span totals, collected while the deployment is built
(the solve ends in host values, so the span holds the whole solve)."""


def read(ctx):
    rec = ctx["spans"].get("solver/two_step")
    return None if rec is None else rec["total_s"]
