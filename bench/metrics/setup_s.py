"""Process start to the first timed call: JAX start-up, inputs made from
the seed, the deployment built (allocation solve, parity encode) and the
warm-up calls (compilation or cache loads); host clock."""


def read(ctx):
    return ctx["setup_s"]
