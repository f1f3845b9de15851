"""Fused round engine — one XLA executable per communication round.

The reference implementation of Algorithm 1 (``CoLearner.run_round`` with
the python engine) drives the T_i local epochs from a host loop: one jit
dispatch + one blocking ``device_get`` per epoch, plus a host-side Eq. 4
``relative_change`` over the parameter leaves. Since the paper's protocol
spends nearly all wall-clock inside those local epochs, that dispatch
overhead sits directly on the hottest path.

``make_fused_round`` instead compiles the *whole* round into a single
donated jit:

    lax.scan over the T_i local epochs          (the Eq. 3-family schedule
        |                                        computed *traced* inside
        |  each epoch: vmap over K participants, the scan via ``lr_fn`` —
        |  inner lax.scan over that epoch's      default ``schedule.
        v  batches                               switch_lr``)
    Eq. 2 averaging / mixing (``aggregate_fn``)
    Eq. 4 relative_change, on-device            (``relative_change_traced``)

so a round costs one dispatch and exactly one host sync (the aux fetch at
the end). The schedule is pure *data* to the executable: ``lr_fn(sched, j,
T_i, ge, total)`` receives the per-round parameter pack ``sched`` (η_i,
decay, kind — built by ``api.LRSchedule.round_params``), the round length
``T_i``, the global-epoch offset and the run's epoch budget all as traced
arguments. Swapping between built-in schedules, a warmup ramping η^i per
round, a policy-aware budget update, or an ILE doubling of T_i therefore
reuse the compiled executables; only a changed *batch shape* recompiles
(the single-shot path bakes T_i from the staged-batch shape, i.e.
O(log T_max) compiles per run).

Staging T_i epochs of batches on device costs memory linear in T_i, and
the ILE rule doubles T_i. For large rounds ``CoLearner`` therefore caps
the staged window at the engine's ``chunk`` epochs and strings together
``make_fused_epochs`` executables (same in-scan schedule; j0/T_i/ge0/sched/
total passed traced so chunks never recompile as T_i grows) followed by
one ``make_fused_finalize`` executable (aggregation + Eq. 4 + opt reset).
The round is then ceil(T_i/chunk)+1 dispatches — still zero host syncs
until the final aux fetch.

``gated=True`` builds the divergence-triggered variants (Kamp et al.,
1807.03210, via ``api.DivergenceTrigger``): the executable additionally
takes the last *synced* shared model and a traced δ, computes the local-
model divergence on-device, and selects — still inside the one program —
between the aggregated state (sync) and the untouched local state (skip);
the sync decision comes back with the aux fetch so the host can bill the
wire only on synced rounds.

``masked=True`` builds the ragged-shard variants (heterogeneous data,
``repro.data`` scenario subsystem): the executable takes a traced
(K, n_batches) bool validity mask (``ParticipantData.batch_mask``) right
after the staged batches, and a masked batch slot is an identity carry —
params/opt pass through untouched and the slot is excluded from the epoch
loss mean — so participants with unequal shard sizes train on exactly
their own data inside one shape-stable executable (compile count stays
flat across mask values; asserted by ``round_latency.py --check-retrace``).

``live=True`` builds the elastic-membership variants (``repro.core.
membership``): the executable takes a traced ``(K,)`` float 0/1 *liveness
row* right after the batch mask (or right after the batches when
unmasked). A dead participant slot is an identity carry through the WHOLE
round — the per-step commit gate is ``batch_mask & live`` so it trains
nothing, its loss is excluded from the epoch mean, and after aggregation
``select_live`` restores its own params/opt (it neither uploads nor
downloads; the aggregators renormalize the mixing matrix over the live
set host-side, so the mean never sees the dead rows either). The
shared-model slot is the FIRST LIVE row (``argmax`` of the traced row,
still on-device), not slot 0. Membership changes are pure traced data:
crash, rejoin, and flaky-slot rounds all reuse ONE compiled program
(asserted by ``round_latency.py --check-retrace`` scenario 4).

Backend API — shared by the simulation and pod paths:

  * simulation (single host, K vmapped participants): the defaults.
  * pod (K = pods on a multi-pod mesh): pass ``spmd_axis_name="pod"`` so
    the participant vmap is pinned to the ``pod`` mesh axis, and an
    aggregate fn built against the mesh (``api.Aggregator.
    make_aggregate_fn(codec, mesh=...)``) so the cross-pod traffic is the
    aggregator's actual wire pattern
    (``launch/steps.make_fused_round_step`` wires this for the dry-run).

``CoLearner(round_engine=FusedEngine(chunk)|PythonEngine())`` selects
between this engine and the reference loop; both produce the same
``RoundLog``/state transitions and are asserted equivalent to <=1e-5 in
``tests/test_engine.py``. The aggregation step is supplied as
``aggregate_fn(stacked, weights)`` by a ``repro.core.api`` aggregator
(codec roundtrip + participant mixing; ``weights`` is the traced per-round
mixing matrix, None for statically-uniform Eq. 2).

The end-of-round Eq. 2 step has its own fast path:
``make_fused_compressed_average`` (owned by ``api.FlatFusedInt8`` as its
fused mean) replaces the leafwise int8 roundtrip + separate mean with the
flat-buffer wire codec (``core.flatbuf``) and one fused
quantize->average->dequantize kernel (``kernels.comm``) over one
contiguous buffer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import averaging, flatbuf
from repro.core.schedule import (divergence_traced, relative_change_traced,
                                 switch_lr)
from repro.kernels import ops as kops
from repro.optim.optimizers import apply_updates


def stage(value, dtype=None):
    """Explicitly stage a host value (python scalar / numpy array) onto
    device — the one kind of H2D ``analysis.guards.no_transfer`` allows.
    Device arrays pass through untouched, so staging is idempotent."""
    if isinstance(value, jax.Array):
        return value
    return jax.device_put(np.asarray(value, dtype))


def stack_epoch_batches(per_epoch):
    """Stack a list of per-epoch (K, n_batches, ...) pytrees along a new
    leading epoch axis — the shape the fused epoch scan consumes.

    Host (numpy) leaves are stacked host-side and staged with ONE
    explicit ``jax.device_put`` per leaf — the round's designated staging
    transfer, legal under ``analysis.guards.no_transfer()``. A
    device-resident stack (``jnp.stack`` over numpy inputs) would instead
    issue an *implicit* transfer per epoch per leaf. Device-resident
    inputs stack on device untouched."""
    def stack(*xs):
        if all(isinstance(x, np.ndarray) for x in xs):
            return jax.device_put(np.stack(xs))
        return jnp.stack(xs)
    return jax.tree.map(stack, *per_epoch)


def select_live(live_row, new, old):
    """Per-slot identity carry over a stacked (K, ...) pytree pair: keep
    ``new`` on live rows, ``old`` on dead ones. ``live_row`` is the traced
    0/1 float (K,) liveness row."""
    alive = live_row.astype(bool)

    def sel(n, o):
        return jnp.where(alive.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
    return jax.tree.map(sel, new, old)


def first_live(live_row):
    """Traced index of the first live slot — the shared-model row under
    elastic membership (slot 0 may be dead)."""
    return jnp.argmax(live_row)


def unstack_first_live(stacked, live_row):
    """Unstack the first LIVE participant's model (traced dynamic index)."""
    idx = first_live(live_row)
    return jax.tree.map(lambda t: t[idx], stacked)


def make_epoch_fn(loss_fn, opt, spmd_axis_name=None, masked=False,
                  live=False):
    """One local epoch for all K participants (vmapped).

    Returns epoch_fn(stacked_params, opt_state, batches, lr) ->
    (stacked_params, opt_state, per-participant mean loss). This is THE
    local-epoch body: the python reference loop jits it directly and the
    fused engine scans over it, so the SGD semantics cannot diverge.

    ``masked=True`` is the ragged-shard variant: epoch_fn takes a trailing
    ``mask`` argument, a (K, n_batches) bool marking which batch slots hold
    a shard's real data (``ParticipantData.batch_mask``). A masked-out step
    is an identity carry — params and opt state pass through untouched and
    the slot's loss is excluded from the epoch mean — so shards with fewer
    batches than ``n_batches`` train on exactly their own data with no
    min-clamp. The mask is plain traced data: it never changes the compiled
    program, only which steps commit.

    ``live=True`` is the elastic-membership variant: epoch_fn takes a
    trailing traced (K,) 0/1 float ``live_row`` (after ``mask`` when both
    are on). A dead participant's commit gate is forced off for every step
    — identity carry on params/opt — and its epoch loss is 0 with a zero
    denominator weight, so it contributes nothing anywhere. Liveness is
    traced data, exactly like the batch mask: membership changes never
    recompile.
    """
    def participant_body(params, ostate, pbatches, lr, pmask, palive):
        alive = None if palive is None else palive.astype(bool)

        def step(carry, xs):
            params, ostate = carry
            if masked:
                batch, valid = xs
            else:
                batch = xs
            (loss, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            upd, new_ostate = opt.update(grads, ostate, params, lr)
            new_params = apply_updates(params, upd)
            # identity carry on padding slots / dead participants: nothing
            # trains, nothing counts — compute runs unconditionally so the
            # executable is shape-stable, the select commits only real steps
            gate = None
            if masked:
                gate = valid
            if alive is not None:
                gate = alive if gate is None else (gate & alive)
            if gate is not None:
                keep = lambda new, old: jnp.where(gate, new, old)  # noqa: E731
                new_params = jax.tree.map(keep, new_params, params)
                new_ostate = jax.tree.map(keep, new_ostate, ostate)
                loss = jnp.where(gate, loss, 0.0)
            return (new_params, new_ostate), loss
        xs = (pbatches, pmask) if masked else pbatches
        (params, ostate), losses = jax.lax.scan(step, (params, ostate), xs)
        if masked or live:
            denom = pmask.sum() if masked else losses.size
            if live:
                denom = denom * palive
            mean = losses.sum() / jnp.maximum(denom, 1)
        else:
            mean = losses.mean()
        return params, ostate, mean

    # explicit signature per variant so vmap's positional in_axes line up
    if masked and live:
        def one_participant(params, ostate, pbatches, lr, pmask, palive):
            return participant_body(params, ostate, pbatches, lr, pmask,
                                    palive)
    elif masked:
        def one_participant(params, ostate, pbatches, lr, pmask):
            return participant_body(params, ostate, pbatches, lr, pmask, None)
    elif live:
        def one_participant(params, ostate, pbatches, lr, palive):
            return participant_body(params, ostate, pbatches, lr, None,
                                    palive)
    else:
        def one_participant(params, ostate, pbatches, lr):
            return participant_body(params, ostate, pbatches, lr, None, None)

    vmap_kw = {"spmd_axis_name": spmd_axis_name} if spmd_axis_name else {}
    in_axes = ((0, 0, 0, None) + ((0,) if masked else ())
               + ((0,) if live else ()))
    return jax.vmap(one_participant, in_axes=in_axes, **vmap_kw)


def _make_epoch_scan(epoch_fn, lr_fn, masked=False, live=False):
    """scan_epochs(params, opt, batches, j0, T_i, ge0, sched, total[, mask]
    [, live_row]): run the leading-dim epochs of ``batches`` with the
    schedule computed traced in-scan via ``lr_fn(sched, j, T_i, ge, total)``.

    j0 (round-local offset of the first staged epoch), T_i (the round's
    cycle denominator), ge0 (global epoch at round start), ``sched`` (the
    per-round schedule parameter pack) and ``total`` (the run's epoch
    budget) may all be traced, so one chunk executable is reused unchanged
    as T_i doubles, as the budget updates, and across built-in schedule
    swaps. ``masked=True``: a trailing (K, n_batches) bool ``mask``
    (ragged shards, also traced — see ``make_epoch_fn``) is applied every
    epoch. ``live=True``: a trailing traced (K,) liveness row is applied
    every epoch (elastic membership — dead rows are identity carries).
    """
    def scan_epochs(stacked_params, opt_state, batches, j0, T_i,
                    global_epoch0, sched, total, mask=None, live_row=None):
        n = jax.tree.leaves(batches)[0].shape[0]
        extra = (((mask,) if masked else ())
                 + ((live_row,) if live else ()))

        def body(carry, xs):
            params, ostate = carry
            j, ebatches = xs
            lr = lr_fn(sched, j, T_i, global_epoch0 + j, total)
            params, ostate, loss = epoch_fn(params, ostate, ebatches, lr,
                                            *extra)
            return (params, ostate), (loss, lr)

        return jax.lax.scan(body, (stacked_params, opt_state),
                            (j0 + jnp.arange(n), batches))
    return scan_epochs


def make_fused_compressed_average(*, block=256, impl=None, bits=8,
                                  mesh=None, axis="pod", weighted=False,
                                  stateful=False):
    """Eq. 2 fast path: quantized wire emulation + averaging as ONE pass.

    Returns an ``average_fn`` (stacked tree -> stacked tree, every slot
    holding the mean) that replaces the leafwise pair ``compress_fn=
    make_compress_fn(...)`` + ``average_pjit``: the stacked params are
    flattened through the flat-buffer wire codec (``repro.core.flatbuf``)
    into one contiguous ``(K, N_pad)`` f32 buffer and a single
    ``quant_avg_dequant`` kernel (``repro.kernels.comm``) quantizes,
    averages, and dequantizes it blockwise — collapsing ~2 pallas launches
    + a pad/reshape per leaf + a separate whole-tree mean into one pass,
    with every leaf (however small) on the wire format.

    simulation path (``mesh=None``): the kernel sees all K rows at once.

    pod path (``mesh`` given): a ``shard_map`` over ``axis`` — each pod
    int8-roundtrips only its local row (its upload, exactly what the wire
    carries) and a single psum over the inter-pod axis aggregates the
    dequantized block payloads; only that one fused collective crosses the
    pod boundary, with ``flatbuf.wire_bytes`` giving the exact encoded
    size a production transport would move.

    ``weighted=True`` builds the example-count-weighted Eq. 2 variant
    (FedAvg's generalization for unequal shards): the returned fn takes a
    trailing traced length-K weight row (a normalized mixing-matrix row)
    and computes the weighted mean of the per-row dequantized payloads over
    the same single flat buffer — sim path via the quantize/dequantize
    kernels + one einsum, pod path still ONE psum of the weight-scaled
    local payload. Uniform weights reproduce the unweighted kernel's mean
    up to f32 summation order; the unweighted path itself is untouched
    (bit-compatible Eq. 2).

    ``bits`` ∈ {8, 4, 1} selects the wire precision (one code path —
    ``kernels.quantize.unpack_codes`` is the identity at 8 bits, so the
    int8 payloads stay bit-compatible). ``stateful=True`` builds the
    error-feedback variants: the returned fn takes the ``(K, N_pad)`` f32
    residual buffer as its LAST argument and returns ``(mean_tree,
    new_residual)`` — sim path via the fused ``quant_avg_dequant_ef``
    kernel (uniform) or the quantize/dequantize pair (weighted), pod path
    still ONE psum with each pod's residual staying resident on that pod.

    The layout is recomputed per trace from static shapes only (free); the
    same tree structure always yields the same wire layout. ``impl=None``
    runs the kernels on a TPU backend and the reference elsewhere.
    """
    impl = kops.resolve_impl(impl)
    if mesh is None:
        if stateful:
            if weighted:
                def average_w_ef(stacked, wrow, residual):
                    layout = flatbuf.make_layout(stacked, block=block)
                    buf = flatbuf.flatten(stacked, layout)
                    y = buf + residual
                    q, scale, shape = kops.quantize_blockwise(
                        y, block=block, bits=bits, impl=impl)
                    dq = kops.dequantize_blockwise(q, scale, shape,
                                                   bits=bits, impl=impl)
                    mean = jnp.einsum("k,kn->n", wrow.astype(jnp.float32),
                                      dq)
                    return flatbuf.unflatten_mean(mean, layout), y - dq
                return average_w_ef

            def average_ef(stacked, residual):
                layout = flatbuf.make_layout(stacked, block=block)
                buf = flatbuf.flatten(stacked, layout)
                mean, new_res = kops.quant_avg_dequant_ef(
                    buf, residual, block=block, bits=bits, impl=impl)
                return flatbuf.unflatten_mean(mean, layout), new_res
            return average_ef

        if weighted:
            def average_w(stacked, wrow):
                layout = flatbuf.make_layout(stacked, block=block)
                buf = flatbuf.flatten(stacked, layout)
                q, scale, shape = kops.quantize_blockwise(buf, block=block,
                                                          bits=bits,
                                                          impl=impl)
                dq = kops.dequantize_blockwise(q, scale, shape, bits=bits,
                                               impl=impl)
                mean = jnp.einsum("k,kn->n", wrow.astype(jnp.float32), dq)
                return flatbuf.unflatten_mean(mean, layout)
            return average_w

        def average(stacked):
            layout = flatbuf.make_layout(stacked, block=block)
            buf = flatbuf.flatten(stacked, layout)
            mean = kops.quant_avg_dequant(buf, block=block, bits=bits,
                                          impl=impl)
            return flatbuf.unflatten_mean(mean, layout)
        return average

    from repro.kernels.quantize import unpack_codes
    K = mesh.shape[axis]

    def _local_dequant(q, scale):
        # unpack_codes is the identity at bits=8, so this is the exact
        # expression the pre-bits pod path computed (bit-compatible)
        qq = unpack_codes(q, bits)
        return qq.astype(jnp.int32).astype(jnp.float32) * scale[:, None]

    if stateful:
        if weighted:
            def average_w_ef(stacked, wrow, residual):
                layout = flatbuf.make_layout(stacked, block=block)
                buf = flatbuf.flatten(stacked, layout)

                def local_avg(lbuf, w, lres):          # (1, N_pad) per pod
                    y = lbuf + lres
                    q, scale, _ = kops.quantize_blockwise(
                        y, block=block, bits=bits, impl=impl)
                    dq = _local_dequant(q, scale).reshape(
                        1, -1)[:, :layout.n_pad]
                    k = jax.lax.axis_index(axis)
                    s = jax.lax.psum(w[k].astype(jnp.float32) * dq, axis)
                    return s, y - dq

                avg, new_res = jax.shard_map(
                    local_avg, mesh=mesh,
                    in_specs=(P(axis, None), P(), P(axis, None)),
                    out_specs=(P(axis, None), P(axis, None)),
                    check_vma=False)(buf, wrow, residual)
                return flatbuf.unflatten(avg, layout), new_res
            return average_w_ef

        def average_ef(stacked, residual):
            layout = flatbuf.make_layout(stacked, block=block)
            buf = flatbuf.flatten(stacked, layout)

            def local_avg(lbuf, lres):                 # (1, N_pad) per pod
                y = lbuf + lres
                q, scale, _ = kops.quantize_blockwise(
                    y, block=block, bits=bits, impl=impl)
                dq = _local_dequant(q, scale).reshape(
                    1, -1)[:, :layout.n_pad]
                mean = jax.lax.psum(dq, axis) / K
                return mean, y - dq

            avg, new_res = jax.shard_map(
                local_avg, mesh=mesh,
                in_specs=(P(axis, None), P(axis, None)),
                out_specs=(P(axis, None), P(axis, None)),
                check_vma=False)(buf, residual)
            return flatbuf.unflatten(avg, layout), new_res
        return average_ef

    if weighted:
        def average_w(stacked, wrow):
            layout = flatbuf.make_layout(stacked, block=block)
            buf = flatbuf.flatten(stacked, layout)     # (K, N_pad) over pod

            def local_avg(lbuf, w):                    # (1, N_pad) per pod
                q, scale, _ = kops.quantize_blockwise(lbuf, block=block,
                                                      bits=bits, impl=impl)
                dq = _local_dequant(q, scale)
                k = jax.lax.axis_index(axis)
                s = jax.lax.psum(w[k].astype(jnp.float32) * dq, axis)
                return s.reshape(1, -1)[:, :layout.n_pad]

            avg = jax.shard_map(local_avg, mesh=mesh,
                                in_specs=(P(axis, None), P()),
                                out_specs=P(axis, None),
                                check_vma=False)(buf, wrow)
            return flatbuf.unflatten(avg, layout)
        return average_w

    def average(stacked):
        layout = flatbuf.make_layout(stacked, block=block)
        buf = flatbuf.flatten(stacked, layout)         # (K, N_pad) over pod

        def local_avg(lbuf):                           # (1, N_pad) per pod
            q, scale, _ = kops.quantize_blockwise(lbuf, block=block,
                                                  bits=bits, impl=impl)
            dq = _local_dequant(q, scale)
            mean = jax.lax.psum(dq, axis) / K
            return mean.reshape(1, -1)[:, :layout.n_pad]

        avg = jax.shard_map(local_avg, mesh=mesh,
                            in_specs=(P(axis, None),),
                            out_specs=P(axis, None),
                            check_vma=False)(buf)
        return flatbuf.unflatten(avg, layout)
    return average


def as_aggregate_fn(aggregate_fn=None, compress_fn=None, average_fn=None):
    """Normalize the aggregation surface to ``aggregate(stacked, weights)``.

    New callers (``repro.core.api`` aggregators) pass ``aggregate_fn``
    directly — ``weights`` is the traced per-round mixing matrix (or None).
    Legacy callers keep the PR-2 pair: an optional stacked->stacked
    ``compress_fn`` upload transform followed by a one-argument
    ``average_fn`` (default ``averaging.average_pjit``); the pair is
    wrapped, ignoring weights. Passing both surfaces is an error.
    """
    if aggregate_fn is not None:
        if compress_fn is not None or average_fn is not None:
            raise ValueError(
                "pass aggregate_fn OR compress_fn/average_fn, not both")
        return aggregate_fn
    if average_fn is None:
        average_fn = averaging.average_pjit

    def aggregate(stacked, weights=None):
        del weights                     # legacy pair: statically uniform
        uploaded = compress_fn(stacked) if compress_fn is not None else stacked
        return average_fn(uploaded)
    return aggregate


def _make_finalize(opt, aggregate_fn, live=False, stateful=False):
    """Aggregation (Eq. 2 / mixing) + Eq. 4 metric + per-participant opt
    reset; ``agg_weights`` is the aggregator's traced mixing matrix.

    ``live=True`` (elastic membership): finalize takes ``(params,
    opt_state, old_avg, live_row, agg_weights)`` — after aggregating, dead
    rows are restored to their own params/opt (identity carry: a dead
    participant neither uploads nor downloads) and ``new_avg`` is read
    from the first LIVE row (the mixing matrix gives every live row the
    same mixed model for averaging schemes; gossip rows differ but the
    shared-model reference is by convention the first live row).

    ``stateful=True`` (error-feedback codec and/or stateful aggregator —
    the D² correction rides the same slot): the round state enters right
    after ``opt_state`` (right after ``params`` on the opt-free static
    variant, since the paper discards the local opt state there), the
    aggregate is ``aggregate_fn(params, agg_weights, residual) -> (mixed,
    new_residual)``, dead rows additionally FREEZE their state rows
    (they neither uploaded nor mixed), and the new state is appended to
    the outputs. Everything here is generic over the state PYTREE — the
    codec residual, the D² correction tree, or a dict of both.
    """
    if live:
        if stateful:
            def finalize_live_ef(params, opt_state, residual, old_avg,
                                 live_row, agg_weights=None):
                averaged, new_res = aggregate_fn(params, agg_weights,
                                                 residual)
                new_avg = unstack_first_live(averaged, live_row)
                rel = relative_change_traced(new_avg, old_avg)
                fresh_opt = jax.vmap(opt.init)(averaged)
                averaged = select_live(live_row, averaged, params)
                fresh_opt = select_live(live_row, fresh_opt, opt_state)
                new_res = select_live(live_row, new_res, residual)
                return averaged, fresh_opt, rel, new_avg, new_res
            return finalize_live_ef

        def finalize_live(params, opt_state, old_avg, live_row,
                          agg_weights=None):
            averaged = aggregate_fn(params, agg_weights)
            new_avg = unstack_first_live(averaged, live_row)
            rel = relative_change_traced(new_avg, old_avg)
            fresh_opt = jax.vmap(opt.init)(averaged)
            averaged = select_live(live_row, averaged, params)
            fresh_opt = select_live(live_row, fresh_opt, opt_state)
            return averaged, fresh_opt, rel, new_avg
        return finalize_live

    if stateful:
        def finalize_ef(params, residual, old_avg, agg_weights=None):
            averaged, new_res = aggregate_fn(params, agg_weights, residual)
            new_avg = averaging.unstack_participant(averaged, 0)
            rel = relative_change_traced(new_avg, old_avg)
            fresh_opt = jax.vmap(opt.init)(averaged)
            return averaged, fresh_opt, rel, new_avg, new_res
        return finalize_ef

    def finalize(params, old_avg, agg_weights=None):
        averaged = aggregate_fn(params, agg_weights)
        new_avg = averaging.unstack_participant(averaged, 0)
        rel = relative_change_traced(new_avg, old_avg)
        # paper: local opt state is discarded; restart from the shared model
        fresh_opt = jax.vmap(opt.init)(averaged)
        return averaged, fresh_opt, rel, new_avg
    return finalize


def _default_gate(div, delta):
    """The default on-device sync gate (api.SyncPolicy.traced_should_sync)."""
    return div > delta


def _make_gated_finalize(opt, aggregate_fn, gate_fn=None, live=False,
                         stateful=False):
    """Divergence-gated aggregation: compute the Kamp divergence of the
    locals from the last synced model, then branch — on-device, via a
    ``lax.cond`` on the traced ``do_sync`` from ``gate_fn(div, delta)``
    (the policy's ``traced_should_sync``, default ``div > delta``) —
    between the synced state (aggregated params, fresh opt, advanced
    reference) and the untouched local state (params/opt as trained,
    reference unchanged). The cond means a quiet round skips the
    aggregation COMPUTE (codec roundtrip, mean, opt re-init) too, not
    just the wire accounting; ``rel`` is the Eq. 4 metric on synced
    rounds and the divergence on quiet ones.

    ``live=True`` (elastic membership): gfinalize takes the traced
    ``live_row`` after ``delta``; the divergence is measured over live
    rows only, and in the sync branch dead rows keep their own params/opt
    (identity carry) while ``new_avg`` comes from the first LIVE row.

    ``stateful=True`` (error-feedback codec and/or stateful aggregator):
    gfinalize takes the round state right after ``opt_state``, the
    aggregate is ``aggregate_fn(params, agg_weights, residual) -> (mixed,
    new_residual)``, a quiet round carries the state UNCHANGED through
    the skip branch (nothing was quantized or mixed, so no memory moves),
    dead rows freeze theirs, and the new state is appended LAST to the
    outputs."""
    if gate_fn is None:
        gate_fn = _default_gate

    if live:
        if stateful:
            def gfinalize_live_ef(params, opt_state, residual, sync_ref,
                                  delta, live_row, agg_weights=None):
                div = divergence_traced(params, sync_ref, live_row)
                do_sync = gate_fn(div, delta)

                def sync_branch(operands):
                    params, opt_state, residual = operands
                    averaged, new_res = aggregate_fn(params, agg_weights,
                                                     residual)
                    new_avg = unstack_first_live(averaged, live_row)
                    rel = relative_change_traced(new_avg, sync_ref)
                    fresh_opt = jax.vmap(opt.init)(averaged)
                    averaged = select_live(live_row, averaged, params)
                    fresh_opt = select_live(live_row, fresh_opt, opt_state)
                    new_res = select_live(live_row, new_res, residual)
                    return averaged, fresh_opt, rel, new_avg, new_res

                def skip_branch(operands):
                    params, opt_state, residual = operands
                    return params, opt_state, div, sync_ref, residual

                out_p, out_o, rel, new_ref, out_res = jax.lax.cond(
                    do_sync, sync_branch, skip_branch,
                    (params, opt_state, residual))
                return out_p, out_o, rel, div, do_sync, new_ref, out_res
            return gfinalize_live_ef

        def gfinalize_live(params, opt_state, sync_ref, delta, live_row,
                           agg_weights=None):
            div = divergence_traced(params, sync_ref, live_row)
            do_sync = gate_fn(div, delta)

            def sync_branch(operands):
                params, opt_state = operands
                averaged = aggregate_fn(params, agg_weights)
                new_avg = unstack_first_live(averaged, live_row)
                rel = relative_change_traced(new_avg, sync_ref)
                fresh_opt = jax.vmap(opt.init)(averaged)
                averaged = select_live(live_row, averaged, params)
                fresh_opt = select_live(live_row, fresh_opt, opt_state)
                return averaged, fresh_opt, rel, new_avg

            def skip_branch(operands):
                params, opt_state = operands
                return params, opt_state, div, sync_ref

            out_p, out_o, rel, new_ref = jax.lax.cond(
                do_sync, sync_branch, skip_branch, (params, opt_state))
            return out_p, out_o, rel, div, do_sync, new_ref
        return gfinalize_live

    if stateful:
        def gfinalize_ef(params, opt_state, residual, sync_ref, delta,
                         agg_weights=None):
            div = divergence_traced(params, sync_ref)
            do_sync = gate_fn(div, delta)

            def sync_branch(operands):
                params, opt_state, residual = operands
                averaged, new_res = aggregate_fn(params, agg_weights,
                                                 residual)
                new_avg = averaging.unstack_participant(averaged, 0)
                rel = relative_change_traced(new_avg, sync_ref)
                fresh_opt = jax.vmap(opt.init)(averaged)
                return averaged, fresh_opt, rel, new_avg, new_res

            def skip_branch(operands):
                params, opt_state, residual = operands
                return params, opt_state, div, sync_ref, residual

            out_p, out_o, rel, new_ref, out_res = jax.lax.cond(
                do_sync, sync_branch, skip_branch,
                (params, opt_state, residual))
            return out_p, out_o, rel, div, do_sync, new_ref, out_res
        return gfinalize_ef

    def gfinalize(params, opt_state, sync_ref, delta, agg_weights=None):
        div = divergence_traced(params, sync_ref)
        do_sync = gate_fn(div, delta)

        def sync_branch(operands):
            params, opt_state = operands
            averaged = aggregate_fn(params, agg_weights)
            new_avg = averaging.unstack_participant(averaged, 0)
            rel = relative_change_traced(new_avg, sync_ref)
            fresh_opt = jax.vmap(opt.init)(averaged)
            return averaged, fresh_opt, rel, new_avg

        def skip_branch(operands):
            params, opt_state = operands
            return params, opt_state, div, sync_ref

        out_p, out_o, rel, new_ref = jax.lax.cond(
            do_sync, sync_branch, skip_branch, (params, opt_state))
        return out_p, out_o, rel, div, do_sync, new_ref
    return gfinalize


def _bind_mask_live(body, masked, live, stateful=False):
    """Adapt a ``body(params, opt, residual, batches, mask, live_row,
    *rest)`` to the public signature for the (masked, live, stateful)
    combination: the codec residual appears right after ``opt_state`` when
    ``stateful`` (bound to None otherwise), and enabled mask/live features
    appear as positional args right after ``batches`` (mask first, then
    live_row); disabled ones are bound to None."""
    if masked and live:
        bound = body
    elif masked:
        def bound(stacked_params, opt_state, residual, batches, mask,
                  *rest, **kw):
            return body(stacked_params, opt_state, residual, batches, mask,
                        None, *rest, **kw)
    elif live:
        def bound(stacked_params, opt_state, residual, batches, live_row,
                  *rest, **kw):
            return body(stacked_params, opt_state, residual, batches, None,
                        live_row, *rest, **kw)
    else:
        def bound(stacked_params, opt_state, residual, batches, *rest, **kw):
            return body(stacked_params, opt_state, residual, batches, None,
                        None, *rest, **kw)
    if stateful:
        return bound

    def fn(stacked_params, opt_state, batches, *rest, **kw):
        return bound(stacked_params, opt_state, None, batches, *rest, **kw)
    return fn


def make_fused_round(loss_fn, opt, *, lr_fn=None, compress_fn=None,
                     spmd_axis_name=None, average_fn=None, aggregate_fn=None,
                     gated=False, gate_fn=None, masked=False, live=False,
                     stateful=False, donate=True):
    """Build the single-executable round: epoch scan + aggregation + Eq. 4.

    loss_fn(params, batch) -> (loss, aux) for ONE participant.
    opt: optimizer triple (init/update) from ``repro.optim.optimizers``.
    lr_fn(sched, j, T_i, ge, total): the traced schedule (default
        ``schedule.switch_lr``, the lax.switch combinator every built-in
        ``api.LRSchedule`` shares).
    spmd_axis_name: e.g. "pod" to pin the participant vmap to a mesh axis.
    aggregate_fn(stacked, weights): the round-strategy aggregation (codec
        roundtrip + mixing, see ``repro.core.api``), traced into the same
        executable. Legacy alternative: ``compress_fn`` (optional stacked->
        stacked upload transform) + ``average_fn`` (one-arg Eq. 2 over
        stacked params, default ``averaging.average_pjit``).

    Returns round_fn(stacked_params, opt_state, batches, global_epoch0,
    sched, total, agg_weights=None) -> (aggregated_params, fresh_opt_state,
    aux) with aux = {losses (T,K), lrs (T,), rel (scalar), new_avg
    (unstacked slot-0 model)}. ``batches`` is a (T_i, K, n_batches, ...)
    pytree; ``global_epoch0``/``sched``/``total`` are traced (an int32
    offset, the schedule parameter pack, the int32 epoch budget) so
    neither an ELR step, a per-round η^i, a budget update, nor a built-in
    schedule swap ever retriggers compilation. ``agg_weights`` is the
    aggregator's traced (K, K) mixing matrix (None for statically-known
    schemes like Eq. 2). stacked_params and opt_state are donated.

    ``gated=True`` (divergence-triggered sync, ``api.DivergenceTrigger``):
    round_fn additionally takes ``(sync_ref, delta)`` after ``total`` —
    the last synced shared model and the traced threshold — and aux grows
    {div, synced}; on a quiet round (div <= delta) the returned state is
    the *local* post-epoch params/opt and ``new_avg`` stays ``sync_ref``.

    ``masked=True`` (ragged shards): round_fn takes a (K, n_batches) bool
    ``batch_mask`` right after ``batches`` — traced, so shard-size changes
    between runs never recompile — and the epoch scan applies the
    identity-carry masking of ``make_epoch_fn(masked=True)``.

    ``live=True`` (elastic membership): round_fn takes a traced (K,) 0/1
    float ``live_row`` right after ``batches`` (after ``batch_mask`` when
    both are on). Dead rows are identity carries end-to-end — no training,
    no upload, no download (own params/opt restored after aggregation) —
    the entry/exit shared model is read from the first LIVE row, and in
    the gated variant the divergence is live-masked. Membership changes
    are traced data: crash/rejoin/flaky rounds never recompile.

    ``stateful=True`` (error-feedback codec): round_fn takes the traced
    per-participant residual pytree right after ``opt_state`` —
    ``aggregate_fn`` must be the 3-arg stateful form ``(stacked, weights,
    residual) -> (mixed, new_residual)`` — the residual is donated with
    params/opt, and aux grows ``{"residual": new_residual}``. Dead rows
    freeze their residual; a gated quiet round carries it unchanged.
    """
    if lr_fn is None:
        lr_fn = switch_lr
    scan_epochs = _make_epoch_scan(
        make_epoch_fn(loss_fn, opt, spmd_axis_name, masked=masked,
                      live=live), lr_fn, masked=masked, live=live)
    agg = as_aggregate_fn(aggregate_fn, compress_fn, average_fn)

    if gated:
        gfinalize = _make_gated_finalize(opt, agg, gate_fn, live=live,
                                         stateful=stateful)

        def round_body(stacked_params, opt_state, residual, batches, mask,
                       live_row, global_epoch0, sched, total, sync_ref,
                       delta, agg_weights=None):
            T_i = jax.tree.leaves(batches)[0].shape[0]
            (params, opt_out), (losses, lrs) = scan_epochs(
                stacked_params, opt_state, batches, 0, T_i, global_epoch0,
                sched, total, mask, live_row)
            res_in = (residual,) if stateful else ()
            if live:
                out = gfinalize(params, opt_out, *res_in, sync_ref, delta,
                                live_row, agg_weights)
            else:
                out = gfinalize(params, opt_out, *res_in, sync_ref, delta,
                                agg_weights)
            if stateful:
                out_p, out_o, rel, div, do_sync, new_ref, out_res = out
            else:
                out_p, out_o, rel, div, do_sync, new_ref = out
            aux = {"losses": losses, "lrs": lrs, "rel": rel, "div": div,
                   "synced": do_sync, "new_avg": new_ref}
            if stateful:
                aux["residual"] = out_res
            return out_p, out_o, aux
    else:
        finalize = _make_finalize(opt, agg, live=live, stateful=stateful)

        def round_body(stacked_params, opt_state, residual, batches, mask,
                       live_row, global_epoch0, sched, total,
                       agg_weights=None):
            T_i = jax.tree.leaves(batches)[0].shape[0]
            if live:
                # round entry: every LIVE slot holds the shared model
                # w̄^{i-1} (warm-join restores joined slots host-side
                # before the round executes), so read the first live row
                old_avg = unstack_first_live(stacked_params, live_row)
            else:
                # round entry: every slot holds the shared model w̄^{i-1}
                old_avg = averaging.unstack_participant(stacked_params, 0)
            (params, opt_out), (losses, lrs) = scan_epochs(
                stacked_params, opt_state, batches, 0, T_i, global_epoch0,
                sched, total, mask, live_row)
            res_in = (residual,) if stateful else ()
            if live:
                # dead rows carry their opt state through the round
                out = finalize(params, opt_out, *res_in, old_avg, live_row,
                               agg_weights)
            else:
                del opt_out  # paper: local opt state is discarded at agg
                out = finalize(params, *res_in, old_avg, agg_weights)
            if stateful:
                averaged, fresh_opt, rel, new_avg, new_res = out
            else:
                averaged, fresh_opt, rel, new_avg = out
            aux = {"losses": losses, "lrs": lrs, "rel": rel,
                   "new_avg": new_avg}
            if stateful:
                aux["residual"] = new_res
            return averaged, fresh_opt, aux

    round_fn = _bind_mask_live(round_body, masked, live, stateful=stateful)
    donate_argnums = ((0, 1, 2) if stateful else (0, 1)) if donate else ()
    return jax.jit(round_fn, donate_argnums=donate_argnums)


def make_fused_epochs(loss_fn, opt, *, lr_fn=None, spmd_axis_name=None,
                      masked=False, live=False, donate=True):
    """Memory-bounded building block: a scan over ONE CHUNK of epochs.

    Returns epochs_fn(stacked_params, opt_state, batches, j0, T_i, ge0,
    sched, total) -> (stacked_params, opt_state, losses (C,K), lrs (C,)).
    j0/T_i/ge0/sched/total are traced, so the executable is shared across
    chunks, across T_i doublings, across budget updates, and across
    built-in schedule swaps; only a distinct chunk length C recompiles.
    ``masked=True``: epochs_fn takes a traced (K, n_batches) bool
    ``batch_mask`` right after ``batches`` (ragged shards, identity-carry
    masking — same contract as ``make_fused_round``). ``live=True``: a
    traced (K,) liveness row follows (dead rows are identity carries;
    membership changes never recompile).
    """
    if lr_fn is None:
        lr_fn = switch_lr
    scan_epochs = _make_epoch_scan(
        make_epoch_fn(loss_fn, opt, spmd_axis_name, masked=masked,
                      live=live), lr_fn, masked=masked, live=live)

    def epochs_body(stacked_params, opt_state, _residual, batches, mask,
                    live_row, j0, T_i, global_epoch0, sched, total):
        # epochs never touch the codec residual (it only moves at the
        # finalize); _bind_mask_live binds it to None here
        (params, ostate), (losses, lrs) = scan_epochs(
            stacked_params, opt_state, batches, j0, T_i, global_epoch0,
            sched, total, mask, live_row)
        return params, ostate, losses, lrs

    epochs_fn = _bind_mask_live(epochs_body, masked, live)
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(epochs_fn, donate_argnums=donate_argnums)


def make_fused_finalize(opt, *, compress_fn=None, average_fn=None,
                        aggregate_fn=None, gated=False, gate_fn=None,
                        live=False, stateful=False, donate=True):
    """End-of-round executable for the chunked path: aggregation + Eq. 4 +
    opt reset. finalize_fn(params, old_avg, agg_weights=None) ->
    (aggregated, fresh_opt, rel, new_avg); ``params`` is donated. The
    aggregation surface matches ``make_fused_round`` (aggregate_fn or the
    legacy compress_fn/average_fn pair).

    ``gated=True``: finalize_fn(params, opt_state, sync_ref, delta,
    agg_weights=None) -> (params', opt', rel, div, synced, new_ref), the
    divergence-gated select of ``make_fused_round(gated=True)`` (params
    and opt_state donated).

    ``live=True`` (elastic membership): the ungated variant becomes
    finalize_fn(params, opt_state, old_avg, live_row, agg_weights=None)
    — opt_state rides along so dead rows keep theirs — and the gated one
    takes the traced ``live_row`` after ``delta``; dead rows are identity
    carries and ``new_avg``/divergence follow the live set (see
    ``make_fused_round``).

    ``stateful=True`` (error-feedback codec): the residual enters right
    after ``opt_state`` (right after ``params`` on the opt-free ungated
    static variant), is donated with it, ``aggregate_fn`` must be the
    3-arg stateful form, and the new residual is appended LAST to the
    returned tuple (see ``_make_finalize`` / ``_make_gated_finalize``)."""
    agg = as_aggregate_fn(aggregate_fn, compress_fn, average_fn)
    if gated:
        return jax.jit(
            _make_gated_finalize(opt, agg, gate_fn, live=live,
                                 stateful=stateful),
            donate_argnums=((0, 1, 2) if stateful else (0, 1))
            if donate else ())
    if live:
        return jax.jit(
            _make_finalize(opt, agg, live=True, stateful=stateful),
            donate_argnums=((0, 1, 2) if stateful else (0, 1))
            if donate else ())
    return jax.jit(
        _make_finalize(opt, agg, stateful=stateful),
        donate_argnums=((0, 1) if stateful else (0,)) if donate else ())
