"""The program's own names in a profiler trace: its host spans and the
named scopes of its device operations.

The program opens a host span (``jax.profiler.TraceAnnotation``) around
each phase of a round -- ``colearn.round`` around ``colearn.membership``,
``colearn.input``, ``colearn.dispatch``, ``colearn.fetch``,
``colearn.finish`` and ``colearn.publish`` -- and compiles the round's
device work under three named scopes: ``local_step`` (the local epochs'
loop and everything in it), ``eq2_wire`` (the Eq. 2 aggregate: codec,
relayouts, kernel or collective) and ``eq4_rel`` (the Eq. 4 metric and
the gated divergence).

``load(path)`` reads the trace with ``bench.trace.load``, so every reader
of that module sees what it always saw, and adds two things:

* the program's host spans, in ``Scoped.program``, on the host clock the
  benchmark's own spans use;
* each device operation's op name (``tf_op``: the scope path of the
  compiled instruction, such as ``jit(fn)/local_step/while``), which
  ``jax.profiler.ProfileData`` does not give. It comes from xprof's
  ``trace_viewer`` conversion of a copy of the file, made in a temporary
  directory (the conversion writes cache files beside what it reads).
  Both sources carry each operation's HLO text, which names the
  instruction, and an operation takes the op name that the conversion
  gives its text on that device (``op_names``). The conversion keeps at
  most a million events, the latest, so a long window on four chips loses
  its first rounds there; every round runs the same programs, so the
  texts of the rounds kept name those lost. Where one text carries
  several names (two executables that share an instruction's text), its
  operations are matched from the latest back, and those the conversion
  dropped stay unnamed.

Clocks: a ``colearn.fetch`` span cannot end before the device has finished
the program it waits for. ``clock_skew`` measures, for each fetch, its end
less the end of that program on the device. Where the median of those lies
within ``SKEW_TOLERANCE_S`` the spans are used as recorded; otherwise
``load`` shifts the program's spans back by the median (``Scoped.shift_s``),
so that a gap is named by the phase the host was in. On a TPU v5e the
device's clock in the trace runs behind the host's by more than that (the
device starts a program before the host has enqueued it, by the host's own
runtime events), so the program's spans are shifted there.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import os
import shutil
import statistics
import tempfile

from bench import trace

#: prefix of the program's host spans
PROGRAM = "colearn."
#: the program's named scopes of device work
SCOPES = ("local_step", "eq2_wire", "eq4_rel")
#: a median skew within this is left as recorded
SKEW_TOLERANCE_S = 0.5e-3


@dataclasses.dataclass
class Scoped:
    trace: trace.Trace   # as bench.trace.load reads it
    program: list        # the program's host spans (trace.Event), in order
    tf_ops: dict         # device id -> op name of each trace.devices[dev]
    shift_s: float = 0.0  # what load subtracted from the program's spans


def _host_spans(path, prefix):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append(trace.Event(
                        e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9))
    out.sort(key=lambda e: e.start)
    return out


def _viewer_events(path):
    """xprof's trace_viewer events of a copy of ``path``."""
    from xprof.convert import raw_to_tool_data
    tmp = tempfile.mkdtemp(prefix="scopes_")
    try:
        copy = os.path.join(tmp, os.path.basename(path))
        shutil.copyfile(path, copy)
        data, _ = raw_to_tool_data.xspace_to_tool_data(
            [copy], "trace_viewer", {})
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return json.loads(data)["traceEvents"]


def viewer_ops(path):
    """device id -> {HLO text: [op name, ...] in time order}, from the
    ``XLA Ops`` line of each device in the trace_viewer conversion."""
    events = _viewer_events(path)
    devices, lines = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        name = e.get("args", {}).get("name", "")
        if e["name"] == "process_name" and name.startswith("/device:TPU:"):
            devices[e["pid"]] = int(name.rsplit(":", 1)[1])
        elif e["name"] == "thread_name" and name == "XLA Ops":
            lines.setdefault(e["pid"], set()).add(e["tid"])
    ops = [e for e in events if e.get("ph") == "X"
           and e.get("tid") in lines.get(e.get("pid"), ())
           and e["pid"] in devices]
    ops.sort(key=lambda e: e["ts"])
    out = {}
    for e in ops:
        args = e.get("args", {})
        per_dev = out.setdefault(devices[e["pid"]], {})
        per_dev.setdefault(args.get("long_name", e["name"]), []).append(
            args.get("tf_op", ""))
    return out


def op_names(evs, names):
    """The op name of each of one device's operations ``evs`` (in time
    order), from ``names``: HLO text -> the conversion's op names of that
    text, in time order. A text with one name names all its operations; a
    text with several pairs its latest operations with its names, in
    order, and leaves the earlier ones, which the conversion dropped,
    unnamed ("")."""
    count = collections.Counter(e.text for e in evs)
    seen = collections.Counter()
    out = []
    for e in evs:
        v = names.get(e.text, [])
        i = seen[e.text] - (count[e.text] - len(v))
        seen[e.text] += 1
        if len(set(v)) == 1:
            out.append(v[0])
        else:
            out.append(v[i] if 0 <= i < len(v) else "")
    return out


def load(path):
    """The trace at ``path`` with the program's spans and op names, the
    spans aligned to the device's clock (``align``)."""
    base = trace.load(path)
    names = viewer_ops(path)
    tf_ops = {}
    for dev, evs in base.devices.items():
        tf_ops[dev] = op_names(evs, names.get(dev, {}))
    return align(Scoped(base, _host_spans(path, PROGRAM), tf_ops))


def align(sc):
    """Shift the program's spans back by the median ``clock_skew`` where
    that lies beyond ``SKEW_TOLERANCE_S``; leave them otherwise."""
    skews = clock_skew(sc)
    if skews and abs(statistics.median(skews)) > SKEW_TOLERANCE_S:
        shift(sc, statistics.median(skews))
    return sc


def shift(sc, seconds):
    """Move the program's spans ``seconds`` earlier."""
    sc.program = [dataclasses.replace(e, start=e.start - seconds,
                                      end=e.end - seconds)
                  for e in sc.program]
    sc.shift_s += seconds


def coverage(sc):
    """device id -> (operations given an op name, operations)."""
    return {d: (sum(bool(t) for t in tfs), len(tfs))
            for d, tfs in sc.tf_ops.items()}


def in_scope(tf_op, scope):
    """Whether ``scope`` is a component of the op name's path."""
    return scope in tf_op.rsplit(":", 1)[0].split("/")


def scope_seconds(sc, scope, dev):
    """Device seconds inside the window under operations whose op name
    has ``scope`` as a component, as a union. The trace gives a loop op
    (``while``) no op name; the operations inside it carry the scope, and
    their union is the loop's busy time."""
    lo, hi = trace.window(sc.trace)
    evs = sc.trace.devices.get(dev, [])
    return trace.measure(trace.clip(trace.union(
        (e.start, e.end) for e, t in zip(evs, sc.tf_ops.get(dev, []))
        if in_scope(t, scope)), lo, hi))


def rounds(sc):
    """The benchmark's rounds in the trace."""
    return sum(s.name == "bench.round" for s in sc.trace.spans)


def scope_ms(sc, scope):
    """Device milliseconds a round under ``scope``, averaged over the
    chips; None where no operation carries it."""
    devs = sorted(sc.trace.devices)
    n = rounds(sc)
    if not devs or not n:
        return None
    secs = sum(scope_seconds(sc, scope, d) for d in devs) / len(devs)
    return 1e3 * secs / n if secs > 0 else None


def unscoped_ops(sc, dev, top=10):
    """Device seconds inside the window that no scoped operation covers,
    by the name of the operation running then, most first: the time the
    program's scopes leave unnamed. An operation without an op name that
    runs inside a scoped one (a loop op, say) adds nothing."""
    lo, hi = trace.window(sc.trace)
    evs, tfs = sc.trace.devices.get(dev, []), sc.tf_ops.get(dev, [])
    scoped = trace.union((e.start, e.end) for e, t in zip(evs, tfs)
                         if any(in_scope(t, s) for s in SCOPES))
    starts = [a for a, _ in scoped]
    per_op = collections.Counter()
    for e, t in zip(evs, tfs):
        if any(in_scope(t, s) for s in SCOPES):
            continue
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        covered = 0.0
        k = max(bisect.bisect_right(starts, a) - 1, 0)
        while k < len(scoped) and scoped[k][0] < b:
            covered += max(0.0, min(b, scoped[k][1]) - max(a, scoped[k][0]))
            k += 1
        if b - a > covered:
            per_op[e.name] += b - a - covered
    return per_op.most_common(top)


def host_ms_per_round(sc):
    """Mean over the window's ``colearn.round`` spans of the round less
    the ``colearn.fetch`` spans inside it, in milliseconds: the host's own
    work a round. None where the window holds no program round."""
    lo, hi = trace.window(sc.trace)
    fetches = [s for s in sc.program if s.name == "colearn.fetch"]
    out = []
    for r in sc.program:
        # the window is on the host's clock as recorded
        if (r.name != "colearn.round"
                or not lo <= r.start + sc.shift_s <= hi):
            continue
        waited = sum(f.end - f.start for f in fetches
                     if r.start <= f.start and f.end <= r.end)
        out.append(r.end - r.start - waited)
    return 1e3 * sum(out) / len(out) if out else None


def clock_skew(sc):
    """Seconds by which each ``colearn.fetch`` ends after the end of the
    device program it waited for: the last program run (on the first
    device) that starts before the fetch ends. The fetch's own transfer
    after the program lies in it too, so it bounds the clocks' offset
    from above."""
    runs = sorted(sc.trace.modules.get(min(sc.trace.devices, default=0), []),
                  key=lambda e: e.start)
    starts = [e.start for e in runs]
    out = []
    for f in sc.program:
        k = bisect.bisect_left(starts, f.end)
        if f.name == "colearn.fetch" and k:
            out.append(f.end - runs[k - 1].end)
    return out


def breakdown(sc, top=10):
    """``bench.trace.breakdown`` with each idle gap named by the innermost
    span over it, the program's and the benchmark's together, the
    benchmark's moved by the same ``shift_s`` as the program's."""
    bench = [dataclasses.replace(e, start=e.start - sc.shift_s,
                                 end=e.end - sc.shift_s)
             for e in sc.trace.spans]
    spans = sorted(bench + sc.program, key=lambda e: e.start)
    return trace.breakdown(dataclasses.replace(sc.trace, spans=spans), top)


def from_ctx(ctx):
    """The scoped trace of a traced run whose ``ctx`` names the trace file
    (``trace_path``), read once; None where it names none."""
    if ctx.get("trace_path") is None:
        return None
    if "scoped" not in ctx:
        ctx["scoped"] = load(ctx["trace_path"])
    return ctx["scoped"]
