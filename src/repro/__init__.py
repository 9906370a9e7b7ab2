"""repro — JAX/TPU reproduction of torch-sla: differentiable sparse linear
algebra with adjoint solvers and sparse tensor parallelism.  One plan per
sparsity pattern serves direct, iterative, multigrid, nonlinear, eigen and
adjoint solves, on one chip or sharded over a mesh.

The supported public surface is :mod:`repro.sla`::

    from repro import sla
    x = sla.solve(A, b)

Everything else (``repro.core``, ``repro.kernels``, ``repro.launch``) is
internal and may change between releases.
"""

__version__ = "1.0.0"

__all__ = ["sla"]


def __getattr__(name):
    """Lazy re-export (PEP 562): ``import repro`` stays free of jax import
    cost until the public API is actually touched."""
    if name == "sla":
        from importlib import import_module
        return import_module("repro.sla")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
