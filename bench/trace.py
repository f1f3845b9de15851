"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

``load`` reads the file with ``jax.profiler.ProfileData`` into plain
events: for each device plane (``/device:TPU:<n>``) the operations of its
``XLA Ops`` line and the asynchronous spans of its ``Async XLA Ops`` line,
and the benchmark's own host spans (names starting ``bench.``) from the
host plane. Everything below works on those lists, in seconds on the one
clock of the trace, and is what the tests check.

Device and host clocks in a TPU trace agree to within about 2 ms, so a
host span is matched to a device gap by the largest overlap, never by a
single point.
"""
from __future__ import annotations

import collections
import dataclasses
import re

#: an XLA loop: the local steps' scans lower to these
LOOP = re.compile(r"while(\.\d+)?$")
#: operations that move data between chips, by their short name
COLLECTIVE = re.compile(r"all-reduce|collective-permute|all-gather|"
                        r"reduce-scatter|all-to-all")
#: the same by the opcode in the HLO text (``%psum.7 = f32[..] all-reduce(``):
#: a ``shard_map`` psum keeps the name ``psum``
COLLECTIVE_OPCODE = re.compile(r"\s(?:all-reduce|collective-permute|"
                               r"all-gather|reduce-scatter|all-to-all)"
                               r"(?:-start|-done)?\(")


@dataclasses.dataclass
class Event:
    name: str           # short name: the HLO op's name, or the span's
    start: float        # seconds
    end: float
    text: str = ""      # the event's whole name (HLO text on device lines)


@dataclasses.dataclass
class Trace:
    devices: dict       # device id -> list of Event (XLA Ops, in order)
    asyncs: dict        # device id -> list of Event (Async XLA Ops)
    modules: dict       # device id -> list of Event (XLA Modules)
    spans: list         # host spans of the benchmark (Event)


def short_name(text):
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, asyncs, modules, spans = {}, {}, {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                target = {"XLA Ops": devices, "Async XLA Ops": asyncs,
                          "XLA Modules": modules}.get(line.name)
                if target is None:
                    continue
                evs = [Event(short_name(e.name), e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9, e.name)
                       for e in line.events]
                evs.sort(key=lambda e: e.start)
                target[dev] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Event(e.name, e.start_ns * 1e-9,
                                           (e.start_ns + e.duration_ns)
                                           * 1e-9))
    spans.sort(key=lambda e: e.start)
    return Trace(devices, asyncs, modules, spans)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def union(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a, b):
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    b = union(b)
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def window(trace):
    """(start, end) of the measured window: the first to the last
    ``bench.round`` span."""
    rounds = [s for s in trace.spans if s.name == "bench.round"]
    if not rounds:
        raise ValueError("the trace holds no bench.round span")
    return rounds[0].start, rounds[-1].end


def busy(trace, dev, lo, hi):
    """Union of the device's operations inside [lo, hi]."""
    cache = trace.__dict__.setdefault("_busy", {})
    if dev not in cache:
        cache[dev] = union((e.start, e.end)
                           for e in trace.devices.get(dev, []))
    return clip(cache[dev], lo, hi)


def busy_seconds(trace):
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = window(trace)
    devs = sorted(trace.devices)
    if not devs:
        return 0.0
    return sum(measure(busy(trace, d, lo, hi)) for d in devs) / len(devs)


def idle_gaps(trace, dev):
    """Idle intervals of one device inside the window."""
    lo, hi = window(trace)
    return subtract([(lo, hi)], busy(trace, dev, lo, hi))


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def attribute(gap, spans):
    """The innermost benchmark span that covers at least half as much of
    the gap as the span covering most of it: clocks that disagree by a
    millisecond or two then still name the span the host was in."""
    cover = [(_overlap(gap[0], gap[1], s.start, s.end), s) for s in spans]
    best = max((ov for ov, _ in cover), default=0.0)
    if best <= 0:
        return "none"
    near = [s for ov, s in cover if ov >= 0.5 * best]
    return min(near, key=lambda s: s.end - s.start).name


def breakdown(trace, top=10):
    """``device_ops``: the operations that took most device time, summed by
    name over the window and averaged over devices; ``idle_gaps``: the
    longest idle gaps, each named by the host span open over it."""
    lo, hi = window(trace)
    devs = sorted(trace.devices)
    per_op = collections.Counter()
    for d in devs:
        for e in trace.devices[d]:
            ov = _overlap(e.start, e.end, lo, hi)
            if ov > 0:
                per_op[e.name] += ov / len(devs)
    gaps = []
    for d in devs:
        for g in idle_gaps(trace, d):
            gaps.append((g[1] - g[0], attribute(g, trace.spans)))
    gaps.sort(reverse=True)
    return {"device_ops": [[n, s] for n, s in per_op.most_common(top)],
            "idle_gaps": [[n, s] for s, n in gaps[:top]]}


def main_module(trace):
    """Name of the module (jitted program) with the most device time."""
    tot = collections.Counter()
    for evs in trace.modules.values():
        for e in evs:
            tot[e.name] += e.end - e.start
    return tot.most_common(1)[0][0] if tot else None


def round_gaps(trace):
    """Device idle seconds between consecutive runs of the main module,
    one list a device."""
    name = main_module(trace)
    lo, hi = window(trace)
    out = {}
    for d, evs in trace.modules.items():
        runs = [e for e in evs if e.name == name and lo <= e.start <= hi]
        gaps = []
        for a, b in zip(runs, runs[1:]):
            gaps.append(measure(subtract([(a.end, b.start)],
                                         busy(trace, d, a.end, b.start))))
        out[d] = gaps
    return out


def op_seconds(trace, pattern, dev):
    """Device seconds of the operations whose whole text matches
    ``pattern``, inside the window."""
    lo, hi = window(trace)
    rx = re.compile(pattern)
    return measure(clip(union((e.start, e.end)
                              for e in trace.devices.get(dev, [])
                              if rx.search(e.text or e.name)), lo, hi))


def loop_seconds(trace, dev):
    """Device seconds inside the window under the program's loops: the
    union of its ``while`` operations (a nested loop lies inside the one
    around it)."""
    lo, hi = window(trace)
    return measure(clip(union((e.start, e.end)
                              for e in trace.devices.get(dev, [])
                              if LOOP.match(e.name)), lo, hi))


def is_collective(e):
    """Whether the operation moves data between chips."""
    return bool(COLLECTIVE.search(e.name) or COLLECTIVE_OPCODE.search(e.text))


def collective_seconds(trace, dev):
    """(all, exposed) seconds of collectives on one device: their
    operations and asynchronous spans, and the part of those during which
    no other operation runs there."""
    lo, hi = window(trace)
    coll, other = [], []
    for e in trace.devices.get(dev, []):
        (coll if is_collective(e) else other).append((e.start, e.end))
    for e in trace.asyncs.get(dev, []):
        if is_collective(e):
            coll.append((e.start, e.end))
    coll = clip(union(coll), lo, hi)
    return measure(coll), measure(subtract(coll, other))


def ms_per_round(trace, pattern):
    """Device milliseconds a round of the operations matching ``pattern``,
    averaged over the chips; None where none ran."""
    rounds = sum(s.name == "bench.round" for s in trace.spans)
    devs = sorted(trace.devices)
    if not rounds or not devs:
        return None
    secs = sum(op_seconds(trace, pattern, d) for d in devs) / len(devs)
    return 1e3 * secs / rounds if secs > 0 else None
