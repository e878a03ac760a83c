"""Seconds the program takes to set up the model's iterate during set-up:
the program's ``model/init`` span, collected while the deployment is
built (making the weights from the seed, or checking the ones the caller
hands it, and AdamW's zero state; the span ends in a sync on the
iterate)."""


def read(ctx):
    rec = ctx["spans"].get("model/init")
    return None if rec is None else rec["total_s"]
