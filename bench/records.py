"""The program's own per-solve records, for the readers of the metrics
that read them (``repro.sla.solve_records``).

The ``solve`` loop makes exactly one ``sla.solve_with_info`` call per step
and none after the window, so the window's solves are the newest
``counters["solves"]`` records whose root span is ``sla.solve``; a set-up
call such as ``get_plan`` leaves a record of another name.
"""
from __future__ import annotations


def window_records(ctx):
    """The window's solve records, or None: in a run whose trace holds no
    device op (the counts describe the traced window on the chip), where
    the program keeps no records, where fewer records than solves remain
    (the ``--control`` runs make none), or where a record ran under a JAX
    trace (its times are then trace-time times)."""
    n = ctx["counters"].get("solves")
    if ctx["trace"] is None or not n:
        return None
    try:
        from repro import sla
    except ImportError:
        return None
    read = getattr(sla, "solve_records", None)
    if read is None:
        return None
    recs = [r for r in read() if r.get("name") == "sla.solve"]
    if len(recs) < n:
        return None
    recs = recs[-n:]
    if any(r.get("traced") for r in recs):
        return None
    return recs


def mean_counter(ctx, key: str):
    """Mean of one counter over the window's solve records."""
    recs = window_records(ctx)
    if recs is None:
        return None
    return sum(r["counters"].get(key, 0) for r in recs) / len(recs)
