"""Mean Krylov iterations per solve in the traced window
(``SolveInfo.iterations`` of each solve)."""


def read(ctx):
    it = ctx["counters"].get("iterations")
    if not it or not any(it):
        return None
    return sum(it) / len(it)
