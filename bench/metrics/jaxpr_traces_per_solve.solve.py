"""Jaxprs JAX traced per solve of the traced window: the mean of
``counters["jax_traces"]`` over the window's ``sla.solve`` records
(``repro.sla.solve_records``); eager ops each trace one."""

from records import mean_counter


def read(ctx):
    return mean_counter(ctx, "jax_traces")
