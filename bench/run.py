#!/usr/bin/env python3
"""Chip benchmark harness: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by the names in ``BENCHMARK.json``:
the configuration file it names (``family`` picks ``bench/families/
<family>.py``, the program side, and ``<family>_ref.py``, the plain
reference), ``bench/traffic/<traffic>.json`` (its ``kind`` picks the loop
in ``bench/loops/<kind>.py``), ``bench/limits/<cell>.json`` (the limits of
the correctness comparison) and ``bench/metrics/<metric>.py`` (one reader
per per-layer metric).  A new cell is new files and a new entry.

A run: set-up (data from the seed, the plan's analysis, compilation or the
persistent cache, warm-up), then a window of ``--seconds`` on the host
clock over whole steps, each ending in a wait for its result, then the
peak device memory, then the plain reference's comparison of a sample of
the window's answers.  ``--trace 0`` prints the cell's end-to-end metrics;
``--trace 1`` traces the window and prints its per-layer metrics.  The last
stdout line is one JSON object; the numbers compared with their limits are
the last stderr lines and the result's last key.

Float32 with x64 off and matmul precision as the configuration states.
The run refuses (exit 1, no result) where JAX finds no TPU or fewer chips
than the cell asks for; ``--rehearse`` runs tiny sizes on any platform.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from common import ROOT, bench_module, load_module, log  # noqa: E402


class Refused(Exception):
    pass


def _entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"BENCHMARK.json has no {what} {name!r}")


def _read_json(path):
    with open(path) as fh:
        return json.load(fh)


def cell_spec(bench: dict, workload: str) -> dict:
    """Everything BENCHMARK.json and the cell's own files say of a cell."""
    cell = _entry(bench["workloads"], workload, "workload")
    conf = _entry(bench["configs"], cell["config"], "config")
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return {
        "cell": cell,
        "config": _read_json(os.path.join(ROOT, conf["file"])),
        "traffic": _read_json(os.path.join(BENCH, "traffic",
                                           cell["traffic"] + ".json")),
        "limits": _read_json(os.path.join(BENCH, "limits", workload + ".json")),
        "end_to_end": e2e,
        "per_layer": layer,
    }


def setup_jax(cfg: dict, rehearse: bool):
    import jax
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    if rehearse:
        return jax
    # a fixed directory inside the checkout (the path is part of the key)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def check_devices(jax, chips: int, rehearse: bool):
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not rehearse:
        raise Refused(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found "
                      f"{len(devices)}")
    return devices


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run(args) -> dict:
    bench = _read_json(os.path.join(ROOT, "BENCHMARK.json"))
    spec = cell_spec(bench, args.workload)
    cfg, traffic = spec["config"], spec["traffic"]
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no program under {src}")
    sys.path.insert(0, src)
    jax = setup_jax(cfg, args.rehearse)
    devices = check_devices(jax, int(spec["cell"]["chips"]), args.rehearse)
    dev = devices[0]

    fam = cfg["family"]
    family = bench_module("families", fam)
    ref = bench_module("families", fam + "_ref")
    loop_mod = bench_module("loops", traffic["kind"])
    with jax.profiler.TraceAnnotation("bench.setup"):
        system = family.System(cfg, args.seed, args.rehearse)
        loop = loop_mod.Loop(system, traffic, args.seed, ref, args.control)
        setup = loop.setup()
    setup_s = time.perf_counter() - T_START
    log(f"[setup] {setup_s:.3f} s")

    tdir = None
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    steps, durations = 0, []
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = t1 = time.perf_counter()
        while True:
            loop.step(steps)
            steps += 1
            t_prev, t1 = t1, time.perf_counter()
            durations.append(t1 - t_prev)
            if t1 - t0 >= args.seconds:
                break
    window_s = t1 - t0
    tr = events = None
    if tdir is not None:
        jax.profiler.stop_trace()
        tmod = load_module(os.path.join(BENCH, "trace.py"), "bench_trace")
        path = tmod.find_xplane(tdir)
        events = tmod.load(path)
        shutil.rmtree(tdir, ignore_errors=True)
        if events["devices"]:
            tr = tmod.reduce(events)
    durations.sort()
    log(f"[window] {steps} steps in {window_s:.3f} s; step seconds min "
        f"{durations[0]:.4f} median {durations[len(durations) // 2]:.4f} "
        f"max {durations[-1]:.4f}")

    mem = memory_peak(devices)
    e2e = loop.end_to_end(window_s, steps)
    e2e["setup_s"] = setup_s
    counters = loop.counters()
    grid = getattr(system, "ng", None)
    loop.release()
    gc.collect()

    t_chk = time.perf_counter()
    checks = loop.check(spec["limits"])
    log(f"[check] reference comparison took {time.perf_counter() - t_chk:.3f} s")
    correct = all(v <= lim for v, lim in checks.values())

    if args.trace:
        from peaks import peak
        ctx = {"trace": tr, "counters": counters, "setup": setup,
               "memory_peak_bytes": mem,
               "grid": (grid, grid) if grid else None,
               "peak": lambda: peak(dev.device_kind),
               "kernel_time": lambda pat: tmod.kernel_time(events, pat)}
        metrics = {}
        for m in spec["per_layer"]:
            value = bench_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {}
        for m in spec["end_to_end"]:
            if m["name"] not in e2e:
                raise Refused(f"the loop reports no {m['name']}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": steps,
           "failed": int(checks["failed"][0]), "metrics": metrics,
           "device": device}
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["check"] = {k: {"value": v, "limit": lim}
                    for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} {v!r} limit {lim!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform (CPU rehearsal)")
    ap.add_argument("--control", action="store_true",
                    help="the plain reference in bfloat16 in the program's "
                         "place (to show the comparison fails it)")
    args = ap.parse_args(argv)
    try:
        out = run(args)
    except (Refused, FileNotFoundError) as e:
        log(f"bench: refused: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
