"""Record the program's own round under the profiler, for ``test_scopes.py``.

Usage, on a machine with one TPU chip:

    python bench/tests/record_round_trace.py <out.xplane.pb.gz>

The small configuration of ``tiny.py`` is built as the benchmark builds a
one-chip cell (``drive.build``: ``CoLearner`` with the fused engine),
once with the flat int8 wire and once with the exact one, and each runs
its set-up rounds. Then, under the profiler, ``ROUNDS`` rounds of the
int8 cell and ``ROUNDS`` of the exact one run, each inside the
benchmark's ``bench.round`` span as in the timed window. The trace holds
the program's host spans (``colearn.*``), its named scopes in each device
operation's op name, and the Pallas kernel under its name. It is written
gzip-compressed: most of it is the two round programs' HLO. The script
prints what ``bench.scopes`` reads of it.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

ROUNDS = 3
SEED = 2 ** 31 + 11


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("out")
    args = ap.parse_args(argv)

    import jax
    from bench import drive, scopes, trace
    from bench.tests import tiny

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("record_round_trace.py: JAX found no TPU", file=sys.stderr)
        return 1
    cells = [drive.build(tiny.config(), tiny.traffic(codec=codec), SEED,
                         devs[:1]) for codec in ("fused", "exact")]
    for cell in cells:
        drive.setup_rounds(cell)

    tmp = tempfile.mkdtemp(prefix="record_round_trace_",
                           dir=os.path.dirname(os.path.abspath(args.out)))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # no Python calls: a small trace
    jax.profiler.start_trace(tmp, profiler_options=options)
    for cell in cells:
        for _ in range(ROUNDS):
            with jax.profiler.TraceAnnotation("bench.round"):
                cell.run_round()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    sc = scopes.load(path)
    with open(path, "rb") as f, gzip.open(args.out, "wb") as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(tmp)
    print(f"wrote {args.out}: {os.path.getsize(args.out)} bytes")

    kernels = sorted({(e.name, t) for d in sc.trace.devices
                      for e, t in zip(sc.trace.devices[d], sc.tf_ops[d])
                      if "tpu_custom_call" in e.text})
    skews = scopes.clock_skew(sc)
    print(json.dumps({
        "coverage": scopes.coverage(sc),
        "kernels": kernels,
        "scope_ms": {s: scopes.scope_ms(sc, s) for s in scopes.SCOPES},
        "busy_ms": 1e3 * trace.busy_seconds(sc.trace) / scopes.rounds(sc),
        "host_ms": scopes.host_ms_per_round(sc),
        "skew_ms": [1e3 * min(skews), 1e3 * max(skews)] if skews else None,
        "shift_ms": 1e3 * sc.shift_s,
        "program_spans": [s.name for s in sc.program[:12]],
        "breakdown": scopes.breakdown(sc),
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
