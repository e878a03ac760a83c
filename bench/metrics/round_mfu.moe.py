"""`round_mfu` of the language-model cells, which report `rounds_per_s`:
the forward and backward FLOPs the window's rounds needed (the loaded
prefixes of the clients back by t*, ``bench/kinds/lm_deadline.py``) over
the traced window times the chip's bf16 peak (%)."""
import workcount


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    if tr is None or peak is None or "flops_needed" not in ctx:
        return None
    return workcount.mfu(ctx["flops_needed"], tr["window_s"], peak)
