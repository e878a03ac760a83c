"""Device idle time per call while the block driver prepares the next
call's inputs (ms): the idle time of the traced window under the
program's ``block/prepare`` span (host delay draws, packing and the one
upload, before dispatch), split by overlap, over the window's calls."""


def read(ctx):
    split = (ctx["trace"] or {}).get("idle_by_span") or {}
    if "block/prepare" not in split or not ctx["blocks_s"]:
        return None
    return 1e3 * split["block/prepare"] / len(ctx["blocks_s"])
