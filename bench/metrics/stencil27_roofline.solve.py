"""Share of its memory roofline that the 27-point stencil kernel reaches,
over the levels of the 3-D hierarchy.

Events: the device ops whose names start with ``stencil27_l<k>``, the
launch of level k of the program's HPCG V-cycle (``pallas_call(name=)``;
the CG's own SpMV is level 0).  Least bytes per call, from the kernel's
arithmetic: it reads the 27 coefficient planes and x and writes y once,
29 · n_l float32 words, with n_l = (ng / 2^k)^3 at level k and ng =
``ctx["grid"][0]``, the cube's side.  The least time is those bytes over
the peak HBM bandwidth; the share is that least time over the kernel's
device time.  Reads nothing where no such op ran.
"""

LEVELS = 4
WORDS = 29


def bytes_per_call(level: int, ng: int, itemsize: int = 4) -> int:
    return WORDS * (ng >> level) ** 3 * itemsize


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx.get("grid") is None:
        return None
    ng = ctx["grid"][0]
    secs, nbytes = 0.0, 0
    for level in range(LEVELS):
        s, calls = ctx["kernel_time"](f"stencil27_l{level}")
        secs += s
        nbytes += calls * bytes_per_call(level, ng)
    if secs <= 0 or not nbytes:
        return None
    return 100.0 * nbytes / ctx["peak"]()["hbm_bytes_per_s"] / secs
