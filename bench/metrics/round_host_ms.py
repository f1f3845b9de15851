"""The host's own work a round: over the window's rounds, the program's
``colearn.round`` span less the ``colearn.fetch`` spans in it, in
milliseconds. Read from the program's own record of its rounds
(``repro.analysis.spans.recent()``), each matched to the window by its
``perf_counter`` times; None where the program keeps no such record or
ran no round of its own in the window."""


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:          # a program without host spans
        return None
    lo, hi = ctx["times"][0][0], ctx["times"][-1][1]
    own = [r.host_ms["colearn.round"] - r.host_ms.get("colearn.fetch", 0.0)
           for r in spans.recent() if lo <= r.start and r.end <= hi]
    return sum(own) / len(own) if own else None
