"""Device busy milliseconds per Krylov iteration: busy time in the traced
window over the iterations of the solves in it."""


def read(ctx):
    tr, it = ctx["trace"], ctx["counters"].get("iterations")
    if tr is None or not it or not sum(it):
        return None
    return tr["busy_s"] * 1e3 / sum(it)
