"""peak_hbm_gib: the fullest chip's peak device memory after the window, in
GiB (2**30 bytes): ``memory_stats()`` ``peak_bytes_in_use`` (the arrays)
plus ``peak_bytes_reserved`` (what the runtime holds for the compiled
programs' temporaries, which ``peak_bytes_in_use`` leaves out)."""


def read(ctx):
    if ctx["peak_bytes"] is None:
        return None
    return ctx["peak_bytes"] / 2 ** 30
