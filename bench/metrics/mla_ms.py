"""Device time a round under the program's ``mla`` scope: the latent
attention mixer -- its projections, the expanded chunked attention and
the output projection -- forward, recomputed forward and backward
(``bench.scopes``), averaged over the chips. Reads the trace file a
traced run names in ``ctx["trace_path"]``; None where it names none or no
operation carries the scope."""


def read(ctx):
    from bench import scopes
    sc = scopes.from_ctx(ctx)
    return None if sc is None else scopes.scope_ms(sc, "mla")
