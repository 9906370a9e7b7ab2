"""Share of their memory roofline that the fused CG step kernels reach.

Events: the device ops named after the kernels (``fused_cg_halfstep``,
``fused_cg_update``, ``fused_cg_direction``; each launch runs under a
``jax.named_scope`` of its kernel's name).  Bytes per call, from each
kernel's arithmetic: the half-step reads x, r, p, A p and writes x, r (6 n
float32 words); the direction pass reads z, w, p, s and writes p, s (6 n);
the update reads x, r, p, s, dinv and writes x, r, z (8 n); n = nx * ny.
The least time is those bytes over the peak HBM bandwidth; the share is
that least time over the kernels' device time.
"""

WORDS = {"fused_cg_halfstep": 6, "fused_cg_direction": 6,
         "fused_cg_update": 8}


def bytes_per_call(kernel: str, n: int, itemsize: int = 4) -> int:
    return WORDS[kernel] * n * itemsize


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx.get("grid") is None:
        return None
    nx, ny = ctx["grid"]
    secs, nbytes = 0.0, 0
    for kernel in WORDS:
        s, calls = ctx["kernel_time"](kernel)
        secs += s
        nbytes += calls * bytes_per_call(kernel, nx * ny)
    if secs <= 0 or not nbytes:
        return None
    return 100.0 * nbytes / ctx["peak"]()["hbm_bytes_per_s"] / secs
