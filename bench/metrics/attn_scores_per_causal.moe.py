"""Query-key scores the model round's attention forward computed per
score of the causal triangle: the program's ``attn/scores`` counter over
its ``attn/causal_scores`` counter, over the traced window (1 would be
the triangle alone; the whole square reads 2S/(S+1))."""


def read(ctx):
    scores = ctx["window_counters"].get("attn/scores")
    causal = ctx["window_counters"].get("attn/causal_scores")
    if scores is None or causal is None or not causal["total"]:
        return None
    return scores["total"] / causal["total"]
