"""Seconds of the warm-up calls (the first run_block calls, which compile
or load the round programs from the cache); host clock."""


def read(ctx):
    return ctx["warmup_s"]
