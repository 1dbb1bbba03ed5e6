"""setup_s: process start to the first timed round, in seconds (host clock).

Covers start-up, device init, weights and state from the seed, the batch
pool, the step's compile (or its load from the compile cache), and the first
rounds the check follows."""


def read(ctx):
    return ctx["setup_s"]
