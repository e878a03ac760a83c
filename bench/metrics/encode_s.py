"""Seconds in the parity encode during set-up: the program's
``encode/parity`` span totals, collected while the deployment is built
(the span covers the encode and the parity aggregate of every edge
aggregator and, with spans on, ends in a sync on the parity set)."""


def read(ctx):
    rec = ctx["spans"].get("encode/parity")
    return None if rec is None else rec["total_s"]
