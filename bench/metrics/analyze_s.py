"""Host seconds of the cell's first plan analysis in set-up
(``repro.sla.get_plan``), on the host clock."""


def read(ctx):
    return ctx["setup"].get("analyze_s")
