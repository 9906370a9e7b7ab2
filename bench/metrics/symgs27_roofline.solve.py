"""Share of its memory roofline that the 8-colour symmetric Gauss–Seidel
kernel reaches, over the levels of the 3-D hierarchy.

Events: the device ops whose names start with ``symgs27_l<k>``, the
launches of level k of the program's HPCG V-cycle (``pallas_call(name=)``).
Least bytes, from the sweep's arithmetic: a whole sweep reads the 27
coefficient planes, x and b and writes x, 30 · n_l float32 words, with
n_l = (ng / 2^k)^3 at level k and ng = ``ctx["grid"][0]``, the cube's
side.  The program launches each sweep as two kernels (the four colours
of one z parity apiece; a symmetric step is a forward and a backward
sweep, four launches), so a call is half a sweep: 15 · n_l words.  The
least time is those bytes over the peak HBM bandwidth; the share is that
least time over the kernel's device time.  Reads nothing where no such
op ran.
"""

LEVELS = 4
WORDS_PER_SWEEP = 30
LAUNCHES_PER_SWEEP = 2


def bytes_per_call(level: int, ng: int, itemsize: int = 4) -> int:
    return (WORDS_PER_SWEEP * (ng >> level) ** 3 * itemsize
            // LAUNCHES_PER_SWEEP)


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx.get("grid") is None:
        return None
    ng = ctx["grid"][0]
    secs, nbytes = 0.0, 0
    for level in range(LEVELS):
        s, calls = ctx["kernel_time"](f"symgs27_l{level}")
        secs += s
        nbytes += calls * bytes_per_call(level, ng)
    if secs <= 0 or not nbytes:
        return None
    return 100.0 * nbytes / ctx["peak"]()["hbm_bytes_per_s"] / secs
