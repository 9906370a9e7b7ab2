"""Programs JAX lowered to MLIR per solve of the traced window: the mean
of ``counters["jax_lowerings"]`` over the window's ``sla.solve`` records
(``repro.sla.solve_records``).  A warm solve that lowers nothing reads 0."""

from records import mean_counter


def read(ctx):
    return mean_counter(ctx, "jax_lowerings")
