"""The whole window's share of the chip's bf16 peak (%): the FLOPs the
window's rounds needed (``bench/workcount.py``) over the traced window
times the peak."""
import workcount


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    if tr is None or peak is None or ctx["rounds"] == 0:
        return None
    flops, _ = workcount.round_work(ctx["rows"], ctx["q"], ctx["c"])
    return workcount.mfu(flops, tr["window_s"], peak)
