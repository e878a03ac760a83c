"""Share of the rounds' device time the chip would need at its peak (%):
100 x max(F / peak FLOP/s, B / peak bytes/s) over the device busy time
in the traced window; F and B from the rows the window's rounds needed
(``bench/workcount.py``), never from padded tensors."""
import workcount


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    if tr is None or peak is None or ctx["rounds"] == 0 or tr["busy_s"] <= 0:
        return None
    flops, nbytes = workcount.round_work(ctx["rows"], ctx["q"], ctx["c"])
    return workcount.roofline_share(flops, nbytes, tr["busy_s"], peak)
