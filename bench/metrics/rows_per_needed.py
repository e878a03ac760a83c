"""Rows the round program read over the rows the window's rounds needed:
the program's ``round/rows`` counter over the traced window, over the
needed rows of the kind's work count (``ctx["rows"]``,
``bench/workcount.py``)."""


def read(ctx):
    rec = ctx["window_counters"].get("round/rows")
    return None if rec is None or not ctx.get("rows") \
        else rec["total"] / ctx["rows"]
