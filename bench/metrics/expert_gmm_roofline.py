"""Share of its roofline that the expert layer's grouped products reach:
the least time for their work, max(FLOPs at the bf16 peak, bytes at the
HBM bandwidth), over their device time under the program's ``moe_gmm``
scope (``bench.scopes``), in the window, in percent.

The work is counted from the routed choices that landed on held experts
in the window (the program's counter, ``held_pick_share.window_counts``),
summed over the expert layers, steps and participants: each such row
passes three grouped products (up and gate, d by f; down, f by d), each
run forward and twice backward (the rows' gradient, and the weights'
gradient contracted over the rows): 3 x 3 x 2 x rows x d x f FLOPs. Each
of those nine products reads or writes its rows at d and at f, and each
reads or writes the held experts' d x f weights once a layer and a
participant-step, in f32. The forward that the layer's remat runs again
is in the time, not in the count."""
from bench.metrics.held_pick_share import window_counts

BYTES = 4


def work(cfg, held_rows, layer_steps):
    """(FLOPs, bytes) of the grouped products over ``held_rows`` routed
    rows and ``layer_steps`` runs of an expert layer by one participant."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    flops = 9 * 2 * held_rows * d * f
    elems = 9 * (held_rows * (d + f) + layer_steps * held * d * f)
    return flops, BYTES * elems


def read(ctx):
    from bench import scopes
    sc = scopes.from_ctx(ctx)
    got = window_counts(ctx)
    if sc is None or got is None or ctx["peaks"] is None:
        return None
    ms = scopes.scope_ms(sc, "moe_gmm")
    if ms is None:
        return None
    cfg, t = ctx["cfg"], ctx["traffic"]
    rounds = scopes.rounds(sc)
    steps = sum(ctx["tokens"]) / (t["batch_size"] * t["seq_len"])
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    flops, nbytes = work(cfg, got[0], steps * layers)
    need_s = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                 nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need_s / (ms * 1e-3 * rounds)
