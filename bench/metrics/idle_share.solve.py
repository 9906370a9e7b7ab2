"""Share of the traced window in which the device ran no op, in percent."""


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]
