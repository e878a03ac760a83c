"""Bytes a hierarchical round hands to the device (GB per round): the
program's ``hier/h2d_bytes`` counter over the traced window (shard
blocks and return masks), over the rounds the window completed."""


def read(ctx):
    rec = ctx["window_counters"].get("hier/h2d_bytes")
    if rec is None or not ctx["rounds"]:
        return None
    return rec["total"] / 1e9 / ctx["rounds"]
