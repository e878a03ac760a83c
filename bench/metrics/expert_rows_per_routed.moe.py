"""Rows the held experts' grouped matmuls computed per routed assignment:
the program's ``moe/expert_rows`` counter (each expert's rows in whole
kernel tiles) over its ``moe/routed`` counter, over the traced window."""


def read(ctx):
    rows = ctx["window_counters"].get("moe/expert_rows")
    routed = ctx["window_counters"].get("moe/routed")
    if rows is None or routed is None or not routed["total"]:
        return None
    return rows["total"] / routed["total"]
