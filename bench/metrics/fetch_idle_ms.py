"""Device idle time per call while the block driver fetches the
per-round outputs (ms): the idle time of the traced window under the
program's ``block/fetch`` span (the wait for the scan, then the one
packed copy), split by overlap, over the window's calls."""


def read(ctx):
    split = (ctx["trace"] or {}).get("idle_by_span") or {}
    if "block/fetch" not in split or not ctx["blocks_s"]:
        return None
    return 1e3 * split["block/fetch"] / len(ctx["blocks_s"])
