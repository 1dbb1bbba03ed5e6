"""tokens_per_s: tokens of every round completed in the window, over the
window's seconds (host clock; the window ends in ``block_until_ready``)."""


def read(ctx):
    return ctx["tokens"] / ctx["window_s"]
