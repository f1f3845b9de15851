"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); both are found by name. Set-up makes
the weights and the inputs from the seed and runs the first rounds, the
first of which compiles; the window then runs rounds for ``--seconds``.
With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result holds the
per-layer metrics, each read by ``bench/metrics/<metric>.py`` from the
run's ``ctx`` (the reduced trace under ``"trace"``, the trace file itself
under ``"trace_path"`` until the readers are done), with the device's
busy time and a breakdown.

Set-up ends in a full garbage collection, after which every object it
made is frozen out of later collections: the window's collections then
walk only what the rounds make. After the window the program's state is
freed and the plain reference repeats the set-up rounds on the
benchmark's own batches; ``bench/correct.py`` compares them. The
numbers compared, each beside its limit, end the standard error and the
result line. The last line of standard output is the result, one JSON
object. JAX must find a TPU and as many chips as the cell asks for;
otherwise the run exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import spec  # noqa: E402

sys.path.insert(0, os.path.join(spec.ROOT, "src"))

TRACE_DIR = os.path.join(spec.ROOT, "bench_out", "trace")


def enable_cache():
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    unless the environment names one), for every program however small:
    set-up then finds each of them there after a cell's first run."""
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class FullCollections:
    """Seconds of each full (generation 2) garbage collection while
    registered in ``gc.callbacks``."""

    def __init__(self):
        self.pauses, self._start = [], None

    def __call__(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.pauses.append(time.perf_counter() - self._start)
            self._start = None


def percentile(values, q):
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def trace_metrics(workload, ctx):
    out = {}
    for m in spec.per_layer_metrics(workload["name"]):
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def e2e_metrics(workload, ctx):
    times = ctx["times"]
    span = times[-1][1] - times[0][0]
    values = {
        "tokens_per_s": sum(ctx["tokens"]) / span,
        "round_ms_p90": percentile([(b - a) * 1e3 for a, b in times], 90),
        "setup_s": ctx["setup_s"],
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec.end_to_end_metrics(workload["name"])}


def run(workload, cfg, traffic, limits, seed, seconds, trace,
        require_chip=True):
    """One run of a cell; returns the result dict, or None where JAX finds
    no TPU or too few chips."""
    enable_cache()
    import jax
    from bench import correct, drive, flops, model
    devices = jax.devices()
    platform = devices[0].platform
    chips = workload["chips"]
    if require_chip and platform != "tpu":
        print(f"bench/run.py: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench/run.py: the cell asks for {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return None
    devices = devices[:chips]
    kind = devices[0].device_kind
    peaks = flops.peaks(kind) if require_chip else None

    cell = drive.build(cfg, traffic, seed, devices)
    reads = drive.setup_rounds(cell)
    compiles0 = drive.compile_count(cell)
    gc.collect()
    gc.freeze()
    collections = FullCollections()
    gc.callbacks.append(collections)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # device operations and the benchmark's host spans; no Python
        # function calls, which would swell the trace a hundredfold
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    t_window = time.perf_counter()
    setup_s = t_window - T_PROCESS
    times, tokens, losses = drive.window(cell, seconds)
    if trace:
        jax.profiler.stop_trace()
    gc.callbacks.remove(collections)
    frozen = gc.get_freeze_count()
    gc.unfreeze()
    compiles = drive.compile_count(cell) - compiles0
    peak = drive.peak_bytes(devices)
    fed, feed = cell.fed, cell.bench_feed
    prog_losses = list(cell.losses)
    weights = feed.sizes if traffic["weighted"] else None
    drive.free(cell)
    del cell

    try:
        ref_losses, ref_norms, _, ref_epochs = model.reference_rounds(
            cfg, traffic, seed, feed, drive.SETUP_ROUNDS, devices=devices,
            weights=weights)
        values = correct.numbers(prog_losses, reads, ref_losses, ref_norms)
        values["feed_gap"] = correct.feed_gap(fed, feed, ref_epochs)
    except model.NotCovered as e:
        print(f"bench/run.py: the reference does not cover {e}",
              file=sys.stderr)
        values = {n: math.inf for n in correct.NUMBERS}
    ok, rows = correct.judge(values, limits)
    failed = sum(not math.isfinite(x) for x in losses)
    ms = sorted(((b - a) * 1e3, i) for i, (a, b) in enumerate(times))
    print(f"rounds {len(ms)}: median {ms[len(ms) // 2][0]:.3f} ms, "
          f"slowest {[(i, round(t, 3)) for t, i in ms[-5:]]}",
          file=sys.stderr)
    print(f"gc: {frozen} objects frozen after set-up; full collections in "
          f"the window {len(collections.pauses)}, "
          f"{[round(p * 1e3, 3) for p in collections.pauses[:10]]} ms",
          file=sys.stderr)

    ctx = {"times": times, "tokens": tokens, "setup_s": setup_s,
           "peak_bytes": peak, "compiles": compiles, "cfg": cfg,
           "traffic": traffic, "chips": chips, "peaks": peaks,
           "trace": None, "trace_path": None}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": ok, "attempted": len(times), "failed": failed}
    if trace:
        from bench import trace as trace_mod
        import glob
        ctx["trace_path"] = glob.glob(os.path.join(
            TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))[0]
        ctx["trace"] = trace_mod.load(ctx["trace_path"])
        lo, hi = trace_mod.window(ctx["trace"])
        device["busy_s"] = trace_mod.busy_seconds(ctx["trace"])
        device["window_s"] = hi - lo
        result["metrics"] = trace_metrics(workload, ctx)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        result["device"] = device
        result["breakdown"] = trace_mod.breakdown(ctx["trace"])
    else:
        result["metrics"] = e2e_metrics(workload, ctx)
        result["device"] = device
    result["checked"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        print(f"checked {n} {v!r} limit {lim!r}", file=sys.stderr)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    workload = spec.workload(args.workload)
    result = run(workload, spec.config(workload["config"]),
                 spec.traffic(workload["traffic"]),
                 spec.limits(workload["name"]), args.seed, args.seconds,
                 bool(args.trace))
    if result is None:
        return 1
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
