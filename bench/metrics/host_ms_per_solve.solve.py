"""Host milliseconds per solve of the traced window outside the wait for
the device: the mean over the window's ``sla.solve`` records
(``repro.sla.solve_records``) of the root span's duration less its
``solve.wait`` span."""

from records import window_records


def read(ctx):
    recs = window_records(ctx)
    if recs is None:
        return None
    host = [r["seconds"] - r["incl_s"].get("solve.wait", 0.0) for r in recs]
    return 1e3 * sum(host) / len(host)
