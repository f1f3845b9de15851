"""Token-choice top-k MoE: a capacity-dropping layer that holds every
expert, and a dropless layer that holds a chip's share of them.

Routing (f32): ``cfg.router_score`` "softmax" takes the top-k of the
softmax probabilities, with a Switch-style aux loss E·Σ f_e·P_e.
"sigmoid" is DeepSeek-V3's noaux_tc router: scores ``sigmoid(x @ W)``
(the product at HIGHEST, as DeepSeek-V3's gate computes in fp32), the
top-k chosen on the scores plus a correction bias ``router_bias`` (a leaf
that only selects, so it takes no gradient), the weights taken from the
uncorrected scores, and the sequence-wise balance loss α·Σ_i f_i·P_i of
each sequence. Either way the chosen weights are normalised over the k
and scaled by ``cfg.routed_scale``. Shared experts are one SwiGLU of width
``n_shared_experts · moe_d_ff``, run on every token.

Capacity dispatch (``cfg.experts_held == 0``, every expert held):
  * Dispatch avoids the classic (tokens, experts, capacity) one-hot einsum —
    at 32k-seq prefill that tensor is O(10^13). Instead tokens are argsorted
    by expert id, ranked within their expert by position arithmetic, and
    scattered into a static (E, capacity, D) buffer (`mode='drop'` handles
    over-capacity tokens = the standard "token dropping" semantics).
  * Expert weights carry a leading E dim sharded over the `model` mesh axis
    (expert parallelism); the scatter/gather pair is where XLA inserts the
    all-to-all — visible in the dry-run collective table.

Held share (``cfg.experts_held = H > 0``, one chip's share under expert
parallelism): the layer holds experts [0, H) of the n_experts the router
scores over. Every (token, choice) pair is sorted by expert, the held
ones first, and the held ones run through one grouped product over the H
experts (``grouped_matmul``) with no capacity, so no token is dropped. A
choice of an expert held elsewhere adds nothing here (its chip adds it).
The layer counts, for the loss's metrics, the routed choices that land
on held experts (``HELD_PICKS``) and all routed choices (``PICKS``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.core.engine import MOE, MOE_GMM
from repro.kernels import ops as kops
from repro.models.layers import trunc_normal
from repro.sharding.constrain import constrain

#: the held share's counters: routed choices of a held expert, and all
#: routed choices (token x k), summed over layers and steps
HELD_PICKS = "moe.held_picks"
PICKS = "moe.picks"


def _unbatched(fn, axis_size, in_batched, *args):
    """``vmap`` of ``fn`` as a loop over the batch: the grouped products
    take no batch dimension."""
    args = [a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
            for a, b in zip(args, in_batched)]
    return jnp.stack([fn(*(a[i] for a in args))
                      for i in range(axis_size)]), True


_CONTRACT_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
#: megablox row tile; the rows are padded to a multiple of it
TILE_M = 512


def _tile(d):
    return 512 if d % 512 == 0 else 128 if d % 128 == 0 else d


@functools.lru_cache(maxsize=None)
def _products(impl):
    """(gmm, gmm_t, tgmm) for ``impl``: the megablox kernels ("pallas",
    or "interpret" for the CPU) or ``jax.lax.ragged_dot`` ("ref"), each
    taking no batch dimension and looping over one under ``vmap``.

    gmm(lhs (m,k), rhs (g,k,n), sizes (g,)) -> (m,n): each group's rows
    times its matrix; gmm_t the same with rhs (g,n,k) transposed;
    tgmm(lhs (m,k), dout (m,n), sizes) -> (g,k,n): each group's lhs rows,
    transposed, times its dout rows. Rows past the groups are not
    computed and are not to be read."""
    if impl == "ref":
        def gmm(lhs, rhs, sizes, transpose):
            if transpose:
                rhs = jnp.swapaxes(rhs, 1, 2)
            return jax.lax.ragged_dot(lhs, rhs, sizes,
                                      preferred_element_type=jnp.float32)

        def tgmm(lhs, dout, sizes):
            return jax.lax.ragged_dot_general(
                lhs, dout, sizes, _CONTRACT_ROWS,
                preferred_element_type=jnp.float32)
    else:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as mgmm
        from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as mtgmm
        interpret = impl == "interpret"

        def pad(x):
            return jnp.pad(x, ((0, -x.shape[0] % TILE_M), (0, 0)))

        def gmm(lhs, rhs, sizes, transpose):
            k, n = lhs.shape[1], rhs.shape[1 if transpose else 2]
            out = mgmm(pad(lhs), rhs, sizes, jnp.float32,
                       (TILE_M, _tile(k), _tile(n)),
                       transpose_rhs=transpose, interpret=interpret)
            return out[:lhs.shape[0]]

        def tgmm(lhs, dout, sizes):
            k, n = lhs.shape[1], dout.shape[1]
            return mtgmm(pad(lhs).T, pad(dout), sizes, jnp.float32,
                         (TILE_M, _tile(k), _tile(n)), interpret=interpret)
    gmm_v = custom_vmap(lambda lhs, rhs, sizes: gmm(lhs, rhs, sizes, False))
    gmm_v.def_vmap(functools.partial(_unbatched, gmm_v))
    gmm_t = custom_vmap(lambda lhs, rhs, sizes: gmm(lhs, rhs, sizes, True))
    gmm_t.def_vmap(functools.partial(_unbatched, gmm_t))
    tgmm_v = custom_vmap(tgmm)
    tgmm_v.def_vmap(functools.partial(_unbatched, tgmm_v))
    return gmm_v, gmm_t, tgmm_v


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs, rhs, sizes, impl):
    """Rows of ``lhs`` (m,k), sorted in groups of ``sizes`` (g,), times
    their group's ``rhs`` (g,k,n): (m,n) in f32; rows past the groups are
    not to be read. Under ``vmap`` it runs one product a batch row; its
    backward pass runs two more grouped products."""
    return _products(impl)[0](lhs, rhs, sizes)


def _gmm_fwd(lhs, rhs, sizes, impl):
    return _products(impl)[0](lhs, rhs, sizes), (lhs, rhs, sizes)


def _gmm_bwd(impl, res, g):
    lhs, rhs, sizes = res
    _, gmm_t, tgmm = _products(impl)
    return (gmm_t(g, rhs, sizes).astype(lhs.dtype),
            tgmm(lhs, g, sizes).astype(rhs.dtype), None)


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def moe_init(key, cfg, dtype, stack=()):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    held = cfg.experts_held or E
    ks = jax.random.split(key, 7)
    p = {
        "router": trunc_normal(ks[0], (*stack, d, E), d ** -0.5, jnp.float32),
        "wi": trunc_normal(ks[1], (*stack, held, d, f), d ** -0.5, dtype),
        "wg": trunc_normal(ks[2], (*stack, held, d, f), d ** -0.5, dtype),
        "wo": trunc_normal(ks[3], (*stack, held, f, d), f ** -0.5, dtype),
    }
    if cfg.router_score == "sigmoid":
        p["router_bias"] = jnp.zeros((*stack, E), jnp.float32)
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared"] = {
            "wi": trunc_normal(ks[4], (*stack, d, fs), d ** -0.5, dtype),
            "wg": trunc_normal(ks[5], (*stack, d, fs), d ** -0.5, dtype),
            "wo": trunc_normal(ks[6], (*stack, fs, d), fs ** -0.5, dtype),
        }
    return p


def capacity(n_tokens, cfg):
    c = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts)
    return max(4, -(-c // 4) * 4)    # round up to a multiple of 4, >= 4


def n_groups(T, E):
    """Dispatch groups: largest power of two <= 64 such that every group
    still holds >= 4·E tokens (so per-group capacity stays meaningful)."""
    g = 1
    while g < 64 and T % (2 * g) == 0 and T // (2 * g) >= 4 * E:
        g *= 2
    return g


def moe_apply(p, x, cfg):
    """x: (B,S,D) -> (y, aux_loss, counters): the held share when
    ``cfg.experts_held``, else every expert behind a capacity buffer (no
    counters)."""
    with jax.named_scope(MOE):
        if cfg.experts_held:
            return _held_apply(p, x, cfg)
        y, aux = _capacity_apply(p, x, cfg)
        return y, aux, {}


def route(p, xt, cfg, batch):
    """The router over every expert, for the tokens ``xt`` (T,D) of
    ``batch`` sequences: the chosen expert ids (T,k), their weights (T,k)
    and the balance loss."""
    E, k = cfg.n_experts, cfg.top_k
    T = xt.shape[0]
    if cfg.router_score == "sigmoid":
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"],
                            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)                         # (T,E)
        _, top_i = jax.lax.top_k(scores + p["router_bias"], k)  # bias selects
        top_p = jnp.take_along_axis(scores, top_i, axis=-1)
        # sequence-wise balance: f_i = E/(k·S)·(picks of i in a sequence),
        # P_i = the sequence's mean of the scores normalised over all E
        picks = jax.nn.one_hot(top_i, E, dtype=jnp.float32).sum(1)
        f = picks.reshape(batch, -1, E).sum(1) * (E * batch / (k * T))
        P = (scores / scores.sum(-1, keepdims=True)).reshape(
            batch, -1, E).mean(1)
        aux = cfg.router_aux_coef * jnp.mean(jnp.sum(f * P, axis=-1))
    else:
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
        probs = jax.nn.softmax(logits, axis=-1)                 # (T,E)
        top_p, top_i = jax.lax.top_k(probs, k)                  # (T,k)
        # Switch aux load-balance loss
        f_e = jnp.zeros(E, jnp.float32).at[top_i.reshape(-1)].add(1.0) / (
            T * k)
        aux = cfg.router_aux_coef * E * jnp.sum(f_e * probs.mean(0))
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scale != 1.0:
        top_p = top_p * cfg.routed_scale
    return top_i, top_p, aux


def _swiglu(x, wi, wg, wo, ein):
    h = ein("td,df->tf", x, wi)
    g = ein("td,df->tf", x, wg)
    return ein("tf,fd->td", jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
               * h, wo)


def _with_shared(p, xt, y):
    """y (B,S,D) plus the shared experts (always-on, DeepSeek-style) of
    the tokens xt (T,D)."""
    if "shared" in p:
        s = p["shared"]
        y = y + _swiglu(xt, s["wi"], s["wg"], s["wo"],
                        jnp.einsum).reshape(y.shape)
    return y.astype(xt.dtype)


def _held_apply(p, x, cfg):
    """x: (B,S,D) -> (y, aux_loss, counters): the part experts [0,
    experts_held) give, with no token dropped, plus the shared experts."""
    B, S, D = x.shape
    T, k, H = B * S, cfg.top_k, cfg.experts_held
    xt = x.reshape(T, D)
    top_i, top_p, aux = route(p, xt, cfg, B)
    # every (token, choice) sorted by expert, experts held elsewhere last
    # (past the H groups, where the grouped product leaves nothing to
    # read). Their rows gather, and scatter back to, a spare zero row T,
    # and their products are zeroed before they are weighted, so no
    # gradient reads what was left there.
    eid = jnp.minimum(top_i.reshape(T * k), H)
    order = jnp.argsort(eid, stable=True)
    sizes = jnp.bincount(eid, length=H + 1)[:H].astype(jnp.int32)
    held = eid[order] < H
    tok = jnp.where(held, order // k, T)
    rows = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)])[tok]
    impl = kops.resolve_impl(None)
    with jax.named_scope(MOE_GMM):
        h = grouped_matmul(rows, p["wi"], sizes, impl)
        g = grouped_matmul(rows, p["wg"], sizes, impl)
        h = jax.nn.silu(g) * h
        out = grouped_matmul(h.astype(xt.dtype), p["wo"], sizes, impl)
    out = jnp.where(held[:, None], out, 0.0)
    w = top_p.reshape(T * k)[order][:, None]
    y = jnp.zeros((T + 1, D), out.dtype).at[tok].add(out * w)[:T]
    counters = {HELD_PICKS: jnp.sum(held, dtype=jnp.int32),
                PICKS: jnp.asarray(T * k, jnp.int32)}
    return _with_shared(p, xt, y.reshape(B, S, D)), aux, counters


def _capacity_apply(p, x, cfg):
    """x: (B,S,D) -> (y, aux_loss), every expert held.

    Grouped sort-based dispatch (§Perf cycle 2): tokens are split into G
    data-parallel groups; sort/rank/scatter happen *within* a group, so with
    the G dim pinned to `data` and the E dim to `model` every scatter is
    shard-local and the only cross-shard movement is the (G,E)-blocked
    token buffer — which GSPMD lowers as all-to-all/all-gather instead of
    the pathological full-buffer all-reduce the global scatter produced
    (measured 78 GiB -> ~9 GiB link bytes per DeepSeek MoE layer at 32k
    prefill). Per-(group,expert) capacity is the standard TPU "grouped"
    token-dropping semantic (Switch/GShard style).
    """
    B, S, D = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(T, D)

    top_i, top_p, aux = route(p, xt, cfg, B)

    # ---- grouped sort-based dispatch ---------------------------------------
    G = n_groups(T, E)
    Tg = T // G
    cap = capacity(Tg, cfg)
    xg = constrain(xt.reshape(G, Tg, D), ("dp", None, None))
    ge = top_i.reshape(G, Tg * k)                               # expert ids
    gp = top_p.reshape(G, Tg * k)

    def dispatch_one(eids):
        order = jnp.argsort(eids)
        se = eids[order]
        start = jnp.searchsorted(se, jnp.arange(E))
        rank = jnp.arange(Tg * k) - start[se]
        keep = rank < cap
        dest = jnp.where(keep, se * cap + rank, E * cap)
        return order, dest, keep

    order, dest, keep = jax.vmap(dispatch_one)(ge)
    st = order // k                                             # token in group
    src = jnp.take_along_axis(
        xg, st[..., None], axis=1)                              # (G,Tg*k,D)
    buf = jax.vmap(lambda d, s: jnp.zeros((E * cap, D), xt.dtype)
                   .at[d].set(s, mode="drop"))(dest, src)
    buf = constrain(buf.reshape(G, E, cap, D),
                    ("dp", "model", None, None))

    # ---- expert compute (block-local: G on data, E on model) ----------------
    h = jnp.einsum("gecd,edf->gecf", buf, p["wi"])
    g_ = jnp.einsum("gecd,edf->gecf", buf, p["wg"])
    h = jax.nn.silu(g_.astype(jnp.float32)).astype(buf.dtype) * h
    h = constrain(h, ("dp", "model", None, None))
    out = jnp.einsum("gecf,efd->gecd", h, p["wo"]).reshape(G, E * cap, D)
    out = constrain(out, ("dp", None, None))      # gather experts per group

    # ---- combine (group-local gather + weighted scatter-add) ----------------
    back = jnp.take_along_axis(out, jnp.minimum(dest, E * cap - 1)[..., None],
                               axis=1)
    sp = jnp.take_along_axis(gp, order, axis=1)
    w = jnp.where(keep, sp, 0.0).astype(back.dtype)[..., None]
    y = jax.vmap(lambda t, bw: jnp.zeros((Tg, D), back.dtype)
                 .at[t].add(bw))(st, back * w * keep[..., None])
    y = y.reshape(B, S, D)

    return _with_shared(p, xt, y), aux
