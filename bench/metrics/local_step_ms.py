"""Device time a round under the program's ``local_step`` scope: the
local epochs' loop and everything inside it (``bench.scopes``), averaged
over the chips. Reads the trace file a traced run names in
``ctx["trace_path"]``; None where it names none or no operation carries
the scope."""


def read(ctx):
    from bench import scopes
    sc = scopes.from_ctx(ctx)
    return None if sc is None else scopes.scope_ms(sc, "local_step")
