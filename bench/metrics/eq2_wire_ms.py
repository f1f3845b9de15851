"""Device time a round under the program's ``eq2_wire`` scope: the whole
Eq. 2 pass -- the wire kernel or the exact mean, with its relayouts and
pads, or the pod's collective (``bench.scopes``), averaged over the
chips. Reads the trace file a traced run names in ``ctx["trace_path"]``;
None where it names none or no operation carries the scope."""


def read(ctx):
    from bench import scopes
    sc = scopes.from_ctx(ctx)
    return None if sc is None else scopes.scope_ms(sc, "eq2_wire")
