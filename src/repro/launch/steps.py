"""Step builders + ShapeDtypeStruct input specs for every (arch × shape).

``long_500k`` policy (DESIGN.md §4): SSM/hybrid run natively; DeepSeek's MLA
latent cache is ~0.6 GB at 524k so it also runs natively (the latent *is*
the compression); pure full-attention dense/vlm/audio archs switch to the
first-class sliding-window variant (window 4096).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape
from repro.models import transformer as tr
from repro.optim.optimizers import apply_updates, get_optimizer

LONG_WINDOW = 4096
# families whose long-context decode needs the SWA carve-in
SWA_AT_500K = {"dense", "vlm", "audio"}


def config_for_shape(cfg, shape: InputShape):
    """Apply per-shape config adjustments (the SWA carve-in)."""
    if shape.name == "long_500k" and cfg.family in SWA_AT_500K:
        return cfg.with_(window=LONG_WINDOW)
    return cfg


def params_shapes(cfg, dtype=jnp.bfloat16):
    """Abstract (ShapeDtypeStruct) params — no allocation."""
    return jax.eval_shape(
        lambda k: tr.init_params(k, cfg, dtype),
        jax.ShapeDtypeStruct((2,), jnp.uint32))


def cache_shapes(cfg, batch, seq_len, dtype=jnp.bfloat16):
    return jax.eval_shape(
        functools.partial(tr.init_cache, cfg, batch, seq_len, dtype))


def input_specs(cfg, shape: InputShape, participants: int = 0,
                dtype=jnp.bfloat16):
    """ShapeDtypeStruct stand-ins for the step's data inputs.

    train/prefill -> batch dict; decode -> (cache, token, pos).
    participants > 0 stacks a leading K dim (co-learning variant).
    """
    B, S = shape.global_batch, shape.seq_len
    lead = (participants,) if participants else ()
    if participants:
        assert B % participants == 0
        B = B // participants

    if shape.kind in ("train", "prefill"):
        S_tok = S - (cfg.prefix_len if cfg.input_mode == "tokens+prefix" else 0)
        batch = {"tokens": jax.ShapeDtypeStruct((*lead, B, S_tok), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((*lead, B, S), jnp.int32)}
        if cfg.input_mode == "tokens+prefix":
            batch["prefix"] = jax.ShapeDtypeStruct(
                (*lead, B, cfg.prefix_len, cfg.d_model), dtype)
        return batch

    cache = cache_shapes(cfg, B, S, dtype)
    if participants:
        cache = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct((participants, *v.shape), v.dtype),
            cache)
    token = jax.ShapeDtypeStruct((*lead, B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return {"cache": cache, "token": token, "pos": pos}


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------
def make_train_step(cfg, optimizer="sgd", lr=0.01, lowering="scan",
                    impl="ref", remat=True, microbatch=1):
    """Paper-faithful local step: SGD on the LM loss.

    (params, batch) -> (params, loss). microbatch>1 scans over gradient-
    accumulation slices of the global batch (numerically identical SGD step,
    M× lower activation memory — the production memory knob)."""
    opt = get_optimizer(optimizer)

    def grad_of(params, b):
        return jax.value_and_grad(
            lambda p: tr.loss_fn(p, cfg, b, lowering, impl, remat),
            has_aux=True)(params)

    def train_step(params, batch):
        if microbatch > 1:
            mb = jax.tree.map(
                lambda t: t.reshape(microbatch, t.shape[0] // microbatch,
                                    *t.shape[1:]), batch)

            def acc(g, b):
                (loss, _), gi = grad_of(params, b)
                return jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), g, gi), loss

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            grads, losses = jax.lax.scan(acc, g0, mb)
            grads = jax.tree.map(lambda g: g / microbatch, grads)
            loss = losses.mean()
        else:
            (loss, _), grads = grad_of(params, batch)
        upd, _ = opt.update(grads, opt.init(params), params, lr)
        return apply_updates(params, upd), loss

    return train_step


def make_colearn_train_step(cfg, **kw):
    """One local step for every participant: vmapped over the leading K dim,
    pinned to the `pod` mesh axis so gradient reductions stay intra-pod."""
    from repro.sharding.constrain import batch_axes
    step = make_train_step(cfg, **kw)
    vstep = jax.vmap(step, spmd_axis_name="pod")

    def wrapped(params, batch):
        # the vmap consumes the pod axis; in-model "dp" hints must not
        with batch_axes(("data",)):
            return vstep(params, batch)
    return wrapped


def make_average_step():
    """Eq. 2 over the leading participant dim (all-reduce over `pod`)."""
    from repro.core.averaging import average_pjit
    return average_pjit


def make_fused_round_step(cfg, ccfg, *, optimizer="sgd", lowering="scan",
                          impl="ref", remat=True, mesh=None,
                          param_specs=None, codec=None, aggregator=None,
                          schedule=None, round_index=0,
                          expose_schedule_args=False, masked=False,
                          live=False, compress=None, compress_block=256,
                          compress_impl=None, codec_bits=8,
                          error_feedback=False):
    """Pod-path fused round: the whole communication round as one program.

    Shares ``repro.core.engine`` with the simulation path, but pins the
    participant vmap to the ``pod`` mesh axis (``spmd_axis_name``) and — when
    ``mesh``/``param_specs`` are given — Eq. 2 to an explicit shard_map psum
    over that axis instead of an inferred all-reduce.

    codec / aggregator / schedule take ``repro.core.api`` strategy objects
    or registry names (schedule=None resolves ``ccfg.schedule``). Under
    ``FullAverage`` (the default) the codec keeps its pod fast path:
    ``FlatFusedInt8`` runs each pod's int8 roundtrip locally and ONE psum
    over the ``pod`` axis aggregates the dequantized block payloads of one
    contiguous buffer, instead of L per-leaf collectives; ``LeafwiseInt8``
    keeps the per-leaf reference roundtrip in front of the shard_map
    average. ``compress=None|"leafwise"|"fused"`` remains the legacy
    spelling of the codec choice (mutually exclusive with codec=).

    The schedule rides into the engine as traced data (``lr_fn`` +
    parameter pack, see ``repro.core.engine``). By default this step
    closes the pack for ``round_index`` plus the static ``T0 * max_rounds``
    budget over the returned fn as baked constants — the compact
    signature below, right for compile-oriented callers (the dry-run) and
    for constant-η schedules, but a schedule whose parameters move per
    round (warmup, policy-aware budget) would be frozen at ``round_index``.
    A driver stepping many rounds should instead pass
    ``expose_schedule_args=True`` and feed
    ``schedule.device_round_params(i)`` + the budget per round: the same
    ONE compiled executable serves every round (do NOT rebuild this step
    per round — each build returns a fresh ``jax.jit`` with an empty
    cache, i.e. a full recompile).

    Returns round_fn(stacked_params, opt_state, batches, global_epoch0)
    for weight-free aggregators (Eq. 2), or round_fn(..., agg_weights) when
    the aggregator mixes with a per-round (K, K) matrix (partial
    participation / gossip — build it with ``aggregator.mixing_matrix``).
    With ``expose_schedule_args=True`` the signature grows to
    round_fn(stacked_params, opt_state, batches, global_epoch0, sched,
    total_epochs[, agg_weights]) with ``sched``/``total_epochs`` traced.
    ``batches`` is the (T_i, K, n_batches, ...) stacked-epoch batch dict.

    ``masked=True`` (ragged shards — unequal per-pod batch counts): the
    returned round_fn takes a traced (K, n_batches) bool ``batch_mask``
    right after ``batches`` (``ParticipantData.batch_mask``; masked epoch
    steps are identity carries, see ``repro.core.engine``).

    ``live=True`` (elastic membership): the returned round_fn additionally
    takes a traced (K,) float ``live_row`` right after ``batch_mask`` (or
    right after ``batches`` when not masked). Dead pods identity-carry
    through the local epochs AND the aggregation, and the aggregate fn is
    built ``dynamic`` so the per-round mixing matrix renormalizes over the
    live set (``Membership.live_mask()`` feeds both the row and
    ``aggregator.mixing_matrix(..., live=...)``). Membership changes ride
    in as data — the compiled executable is reused across churn.

    ``compress_impl`` picks the quantizing codecs' kernels: None is the
    compiled Pallas kernel on a TPU backend and the jnp reference elsewhere.

    ``codec_bits``/``error_feedback`` parameterize the quantizing codecs
    (registry-name or legacy ``compress=`` spellings): payload bit width
    in {8, 4, 1} and error-feedback residual memory. An error-feedback
    codec is STATEFUL, and so is a stateful aggregator (``"d2"``'s
    variance-reduction correction) — the returned round_fn then takes the
    (K,)-leading round-state pytree right after ``opt_state``
    (``aggregator.init_round_state(codec, stacked)`` builds the zero
    state; the pod paths keep each pod's rows resident on that pod) and
    its aux dict grows ``{"residual": new_state}``.
    """
    from repro.core import api, engine as engine_mod
    from repro.optim.optimizers import get_optimizer as _get_opt
    from repro.sharding.constrain import batch_axes

    def loss_fn(params, batch):
        return tr.loss_fn(params, cfg, batch, lowering, impl, remat)

    if compress is not None:
        if codec is not None:
            raise ValueError("pass codec= or the legacy compress=, not both")
        if compress not in ("leafwise", "fused"):
            raise ValueError(f"unknown compress {compress!r}")
        codec = compress
    codec = api.get_codec(codec, block=compress_block, impl=compress_impl,
                          bits=codec_bits, error_feedback=error_feedback)
    aggregator = api.get_aggregator(aggregator)
    # the round is stateful when either side carries per-participant
    # memory: the codec's EF residual and/or the aggregator's state
    # (D² correction) — one slot, one plumbing
    stateful = (getattr(codec, "stateful", False)
                or getattr(aggregator, "stateful", False))
    schedule = api.get_schedule(schedule, ccfg)
    aggregate_fn = aggregator.make_aggregate_fn(
        codec, mesh=mesh, param_specs=param_specs, dynamic=live)

    fused = engine_mod.make_fused_round(
        loss_fn, _get_opt(optimizer), lr_fn=api.traced_body(schedule),
        spmd_axis_name="pod", aggregate_fn=aggregate_fn, masked=masked,
        live=live, stateful=stateful, donate=False)

    # the engine's vmap consumes the pod axis; in-model "dp" hints must
    # then resolve to data only (same contract as the colearn step)
    if expose_schedule_args:
        def round_fn(stacked_params, opt_state, *rest):
            """round_fn(params, opt[, residual], batches[, batch_mask]
            [, live_row], ge0, sched, total_epochs[, agg_weights]) — the
            bracketed args appear per the step's error_feedback=/masked=/
            live= flags and the aggregator's uses_weights."""
            with batch_axes(("data",)):
                return fused(stacked_params, opt_state, *rest)
        return round_fn

    sched = schedule.device_round_params(round_index)
    total = jnp.int32(max(ccfg.T0 * ccfg.max_rounds, 1))
    # (residual?, batches, batch_mask?, live_row?, ge0) lead the varargs;
    # agg_weights trails. The baked sched/total pair splices in between —
    # one wrapper covers every stateful × masked × live × uses_weights
    # combination.
    n_lead = 2 + int(stateful) + int(masked) + int(live)

    def round_fn(stacked_params, opt_state, *rest):
        """round_fn(params, opt[, residual], batches[, batch_mask]
        [, live_row], ge0[, agg_weights]) — bracketed args per
        error_feedback=/masked=/live=/uses_weights."""
        lead, tail = rest[:n_lead], rest[n_lead:]
        with batch_axes(("data",)):
            return fused(stacked_params, opt_state,
                         *lead, sched, total, *tail)
    return round_fn


def make_prefill_step(cfg, lowering="scan", impl="ref"):
    def prefill_step(params, batch):
        return tr.prefill(params, cfg, batch, lowering, impl)
    return prefill_step


def make_serve_step(cfg, lowering="scan"):
    def serve_step(params, cache, token, pos):
        return tr.decode_step(params, cfg, cache, token, pos, lowering)
    return serve_step
