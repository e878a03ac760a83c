"""Tokens the round program computed over the tokens the window's rounds
needed: the program's ``round/tokens`` counter over the traced window (n
clients x S positions a round), over the loaded prefixes of the clients
back by t* (``tokens_needed``, ``bench/kinds/lm_deadline.py``)."""


def read(ctx):
    rec = ctx["window_counters"].get("round/tokens")
    if rec is None or not ctx.get("tokens_needed"):
        return None
    return rec["total"] / ctx["tokens_needed"]
