"""Share of the routed choices (token x k, over the expert layers, the
steps and the participants) that land on an expert held on this chip, in
percent, over the window's rounds. Read from the program's own counters
of each round (``repro.analysis.spans.recent()``: ``moe.held_picks`` and
``moe.picks``, summed on the device and fetched with the round), each
round matched to the window by its ``perf_counter`` times; None where the
program keeps no such counters."""

HELD, ALL = "moe.held_picks", "moe.picks"


def window_counts(ctx):
    """(held choices, all choices) of the window's rounds, or None."""
    try:
        from repro.analysis import spans
    except ImportError:          # a program without host spans
        return None
    lo, hi = ctx["times"][0][0], ctx["times"][-1][1]
    own = [getattr(r, "counters", {}) for r in spans.recent()
           if lo <= r.start and r.end <= hi]
    held = sum(c.get(HELD, 0) for c in own)
    picks = sum(c.get(ALL, 0) for c in own)
    return (held, picks) if picks else None


def read(ctx):
    got = window_counts(ctx)
    return None if got is None else 100.0 * got[0] / got[1]
