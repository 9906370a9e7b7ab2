"""Share of its memory roofline that the stencil kernel reaches.

Events: the device ops whose name starts with ``stencil5_pallas``
(the program's ``jit(stencil5_pallas)`` kernel).  Bytes per call: the
kernel must read the five coefficient planes and x and write y once, 7 n
float32 words (n = nx * ny); the least time is those bytes over the peak
HBM bandwidth.  The share is that least time over the kernel's device time.
"""

PREFIX = "stencil5_pallas"


def bytes_per_call(nx: int, ny: int, itemsize: int = 4) -> int:
    return 7 * nx * ny * itemsize


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx.get("grid") is None:
        return None
    secs, calls = ctx["kernel_time"](PREFIX)
    if not calls or secs <= 0:
        return None
    nx, ny = ctx["grid"]
    least = calls * bytes_per_call(nx, ny) / ctx["peak"]()["hbm_bytes_per_s"]
    return 100.0 * least / secs
