"""Share of the routed-expert matmuls' device time the chip would need at
its peak (%).  Work from the program's ``moe/routed`` counter (token-expert
assignments to held experts over the traced window): 2 D F FLOPs for each
of the three expert products per assignment, three times over for the
forward and backward passes; bytes, as often: the held experts' weights
once per MoE layer and round, and each routed row in and out (f32).  Time:
the device seconds of the grouped-matmul ops (``ragged-dot`` or
``ragged_dot`` in their name, metadata ops excepted) in the trace; None
when none ran."""
import workcount


def _expert_ops_s(op_s: dict) -> float:
    ops = ((k.rsplit("/", 1)[-1], v) for k, v in op_s.items())
    return sum(v for op, v in ops
               if ("ragged-dot" in op or "ragged_dot" in op)
               and "metadata" not in op)


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    rec = ctx["window_counters"].get("moe/routed")
    if tr is None or peak is None or rec is None or "d_model" not in ctx:
        return None
    secs = _expert_ops_s(tr["op_s"])
    if secs <= 0:
        return None
    routed = float(rec["total"])
    D, F = ctx["d_model"], ctx["d_expert"]
    flops = 3.0 * 3 * 2.0 * D * F * routed
    nbytes = 3.0 * (ctx["rounds"] * ctx["moe_layers"] * ctx["expert_bytes"]
                    + routed * 2 * D * 4)
    return workcount.roofline_share(flops, nbytes, secs, peak)
