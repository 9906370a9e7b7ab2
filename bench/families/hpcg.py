"""Program side of the HPCG configurations.

The operator is HPCG's ``GenerateProblem`` on one process's cubic local
grid, built by the program's own public constructor
(``repro.data.hpcg.hpcg_problem``) in the 3-D stencil layout.  It depends
on no seed: the traffic draws the right-hand sides.
"""
from __future__ import annotations


class System:
    def __init__(self, cfg: dict, seed: int, rehearse: bool):
        from repro.data.hpcg import hpcg_problem
        dims = (cfg["nx"], cfg["ny"], cfg["nz"])
        if len(set(dims)) != 1:
            raise ValueError(f"the HPCG family runs a cubic grid, got {dims}")
        self.ng = int(cfg["rehearse"]["ng"] if rehearse else cfg["nx"])
        self.n = self.ng ** 3
        self.A = hpcg_problem(self.ng, self.ng, self.ng)

    def ref_data(self) -> dict:
        """What the plain reference is given: the grid."""
        return {"dims": (self.ng, self.ng, self.ng)}

    def release(self) -> None:
        self.A = None
