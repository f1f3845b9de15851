"""Algorithm 1 — the co-learning protocol, as a thin round-strategy driver.

The global-server logic (round state, Eq. 4 T_i control, failure restarts)
is plain Python; the heavy steps (K-participant local SGD epochs, the
aggregation step) are jitted JAX. The same `CoLearner` drives both the
simulation path (K participants vmapped on one host — used by every
paper-claims experiment) and the production path (K = pods,
`spmd_axis_name='pod'`).

A learner composes five strategy objects (``repro.core.api``):

  * ``codec`` — the wire format of one participant's upload. ``ExactF32()``
    (paper-faithful), ``LeafwiseInt8(block, impl)`` (per-leaf int8
    reference roundtrip), ``FlatFusedInt8(block, impl)`` (flat-buffer wire
    format, one fused quantize->average->dequantize kernel under full
    averaging, exact byte accounting).
  * ``aggregator`` — who averages what: ``FullAverage()`` (paper Eq. 2),
    ``PartialParticipation(m=...)`` (FedAvg-style sampled uploads),
    ``RingGossip()`` (serverless neighbor exchange on a fixed ring).
  * ``round_engine`` — ``PythonEngine()`` (reference host loop, one jit
    dispatch per epoch) or ``FusedEngine(chunk=...)`` (the whole round as
    one donated executable, ``repro.core.engine``; long rounds chain chunk
    executables, still one host sync).
  * ``schedule`` — the Eq. 3 family: ``CLR()`` (paper per-round restart),
    ``ELR()`` (global anneal), ``WarmupCLR(warmup_rounds=...)``,
    ``CosineCyclical()``. Per-round parameters (η^i, decay, the epoch
    budget) ride into the fused executables as traced arguments, so
    warmups, budget updates, and built-in swaps
    (``set_schedule``) never recompile.
  * ``sync_policy`` — Eq. 4 generalized: ``ILE(epsilon=...)`` (paper
    doubling), ``FLE()`` (fixed T), ``DivergenceTrigger(delta=...)``
    (Kamp-style: skip the averaging/wire step — and its comm bill — on
    rounds where the local models haven't diverged past δ).

Registry names resolve too: ``CoLearner(ccfg, loss_fn, codec="leafwise",
aggregator="partial", round_engine="fused", schedule="clr",
sync_policy="ile")``; leaving ``schedule``/``sync_policy`` as None resolves
the legacy ``CoLearnConfig.schedule``/``epochs_rule`` strings through the
same registries, bit-for-bit. The pre-PR-3 flag surface (``engine=``,
``compress=``, ``compress_impl=``, ``compress_fn=``, ``compress_block=``,
``fused_chunk=``) lives on as ``CoLearner.from_flags`` — see ROADMAP.md
§Round strategy API for the flag -> object migration table. Engine
equivalence and flag/object parity are asserted in tests/test_engine.py,
tests/test_api.py, and tests/test_policies.py.
"""
from __future__ import annotations

from dataclasses import dataclass
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api, averaging, engine as engine_mod
from repro.core import membership as membership_mod
from repro.optim.optimizers import get_optimizer


@dataclass
class RoundLog:
    round: int
    T: int
    lr_first: float
    lr_last: float
    rel_change: float        # Eq. 4 metric; the divergence on skipped rounds
    local_losses: list
    comm_bytes: int          # 0 on rounds a gated sync policy skipped
    synced: bool = True
    live: int = -1           # live participants this round (K when static;
                             # -1 only on legacy hand-built logs)


@dataclass
class CoLearner:
    """K-participant co-learning driver over (codec, aggregator, engine).

    loss_fn(params, batch) -> (loss, metrics) for ONE participant.
    data: per-participant iterables of epochs; see ``run_round``.

    codec / aggregator / round_engine / schedule / sync_policy each accept
    a strategy object from ``repro.core.api``, a registry name ("exact" |
    "leafwise" | "fused", "full" | "partial" | "ring", "python" | "fused",
    "clr" | "elr" | "warmup_clr" | "cosine", "ile" | "fle" | "divtrigger"),
    or None for the paper-faithful default (exact f32 wire, full Eq. 2
    averaging, python reference engine, and the ``cfg.schedule``/
    ``cfg.epochs_rule`` strings resolved through the registries). Use
    ``CoLearner.from_flags(...)`` for the legacy keyword surface.
    """
    cfg: Any                                  # CoLearnConfig
    loss_fn: Callable
    optimizer_name: str = "sgd"
    codec: Any = None                         # WireCodec | name | None
    aggregator: Any = None                    # Aggregator | name | None
    round_engine: Any = None                  # RoundEngine | name | None
    schedule: Any = None                      # LRSchedule | name | None
    sync_policy: Any = None                   # SyncPolicy | name | None
    #: per-participant example counts (``ParticipantData.sizes``). When
    #: given, a PartialParticipation aggregator with no explicit weights is
    #: auto-wired to the FedAvg shard-size weighting — the learner never
    #: silently falls back to a uniform average on unequal shards.
    shard_sizes: Any = None
    #: (K, n_batches) bool validity mask for ragged shards
    #: (``ParticipantData.batch_mask``). None = equal shards, the classic
    #: bit-compatible unmasked path; when given, both engines thread it
    #: through the epoch bodies as traced data (masked step = identity
    #: carry), so no shard is clamped to the global minimum length.
    batch_mask: Any = None
    #: elastic membership (``repro.core.membership``): a ChurnSchedule, a
    #: registry name ("none" | "scripted" | "random"), or None. A static
    #: schedule (``is_static``) keeps the learner on the exact pre-
    #: membership code path — bit-identical to a learner with no churn
    #: argument at all. An active schedule threads a traced (K,) liveness
    #: row through the engines: dead slots are identity carries (no
    #: training, no upload, no download) and rejoins warm-start from the
    #: last synced model via ``restart_participant``.
    churn: Any = None
    #: False = ablation baseline for benchmarks/churn.py: keep the STATIC
    #: mixing matrix under churn (dead rows' stale models pollute the
    #: mean) while the engine-side identity carries still apply. True
    #: (default) renormalizes the aggregator over the live set.
    liveness_aware: bool = True

    def __post_init__(self):
        self.codec = api.get_codec(self.codec)
        # error-feedback codecs carry per-participant residual memory
        # through the round state (init/run_round/restart/checkpoint)
        self._codec_stateful = getattr(self.codec, "stateful", False)
        self.aggregator = api.get_aggregator(self.aggregator)
        # stateful aggregators (D² correction) ride the same round-state
        # slot; either side being stateful turns on the residual plumbing
        self._round_stateful = (self._codec_stateful
                                or getattr(self.aggregator, "stateful",
                                           False))
        # topology-backed aggregators carry a connectivity guard: reject
        # graphs that can never reach consensus at this K up front
        validate = getattr(self.aggregator, "validate", None)
        if validate is not None:
            validate(self.cfg.n_participants)
        self.round_engine = api.get_engine(self.round_engine)
        # None resolves the legacy cfg.schedule / cfg.epochs_rule strings
        # through the same registries the names go through
        self.schedule = api.get_schedule(self.schedule, self.cfg)
        self.sync_policy = api.get_sync_policy(self.sync_policy, self.cfg)
        self.churn = membership_mod.get_churn(self.churn)
        # static schedules bypass the membership machinery entirely, so
        # "no churn" is bit-for-bit the pre-membership static-K path
        self._churn_active = not self.churn.is_static
        if self.shard_sizes is not None:
            self.shard_sizes = tuple(int(s) for s in self.shard_sizes)
            if len(self.shard_sizes) != self.cfg.n_participants:
                raise ValueError(
                    f"shard_sizes has {len(self.shard_sizes)} entries for "
                    f"K={self.cfg.n_participants} participants")
            if (isinstance(self.aggregator, api.PartialParticipation)
                    and self.aggregator.weights is None):
                import dataclasses as _dc
                self.aggregator = _dc.replace(self.aggregator,
                                              weights=self.shard_sizes)
        if self.batch_mask is not None:
            mask = jnp.asarray(self.batch_mask, bool)
            if mask.ndim != 2 or mask.shape[0] != self.cfg.n_participants:
                raise ValueError(
                    f"batch_mask must be (K={self.cfg.n_participants}, "
                    f"n_batches); got shape {mask.shape}")
            if not bool(mask.any(axis=1).all()):
                raise ValueError("batch_mask leaves some participant with "
                                 "zero valid batches")
            self.batch_mask = mask
        self.opt = get_optimizer(self.optimizer_name)
        # the ONE local-epoch body (engine_mod.make_epoch_fn) is shared:
        # the python engine jits it per-epoch, the fused engine scans over
        # it, so the SGD semantics cannot diverge
        self._jit_epoch = jax.jit(engine_mod.make_epoch_fn(
            self.loss_fn, self.opt, masked=self.batch_mask is not None,
            live=self._churn_active))
        # aggregate(stacked, weights): codec roundtrip + participant mixing;
        # dynamic = the matrix renormalizes over the live set per round
        self._aggregate_fn = self.aggregator.make_aggregate_fn(
            self.codec, dynamic=self._churn_active and self.liveness_aware)
        self._comm_cache = None

        # crash/join handling as ONE jitted row write (traced slot index:
        # one executable per params geometry, zero per-slot recompiles).
        # Eager .at[k].set dispatches scatters whose index scalars are
        # implicit H2D — restarts fire mid-round-loop, inside no_transfer.
        def _restart_row(stacked, opt_state, shared, k):
            new_p = jax.tree.map(lambda t, s: t.at[k].set(s),
                                 stacked, shared)
            fresh = self.opt.init(shared)
            new_o = jax.tree.map(lambda o, f: o.at[k].set(f),
                                 opt_state, fresh)
            return new_p, new_o
        self._jit_restart = jax.jit(_restart_row)
        self._jit_zero_row = jax.jit(
            lambda tree, k: jax.tree.map(lambda e: e.at[k].set(0.0), tree))
        self._runner = self.round_engine.bind(self)

    @classmethod
    def from_flags(cls, cfg, loss_fn, *, optimizer_name: str = "sgd",
                   compress_fn: Callable | None = None,
                   engine: str = "python", fused_chunk: int = 32,
                   compress: str | None = None, compress_block: int = 256,
                   compress_impl: str | None = None, aggregator=None):
        """The pre-PR-3 flag surface, mapped onto strategy objects.

        engine="python"|"fused" (+ fused_chunk) -> round_engine;
        compress=None|"leafwise"|"fused" (+ compress_block/compress_impl)
        -> codec; compress_fn stays the low-level escape hatch (an opaque
        stacked->stacked wire transform, mutually exclusive with
        compress="fused"); compress_impl=None runs the codec's kernels on a
        TPU backend and the reference elsewhere. Behavior is flag-for-flag
        identical to the old constructor; parity is asserted in
        tests/test_api.py.
        """
        if engine not in ("python", "fused"):
            raise ValueError(f"unknown engine {engine!r}")
        if compress not in (None, "leafwise", "fused"):
            raise ValueError(f"unknown compress {compress!r}")
        if compress == "fused":
            if compress_fn is not None:
                raise ValueError(
                    "compress='fused' replaces compress_fn entirely; "
                    "pass one or the other")
            codec = api.FlatFusedInt8(block=compress_block,
                                      impl=compress_impl)
        elif compress_fn is not None:
            codec = api.CustomFn(compress_fn)
        elif compress == "leafwise":
            codec = api.LeafwiseInt8(block=compress_block,
                                     impl=compress_impl)
        else:
            codec = api.ExactF32()
        round_engine = (api.FusedEngine(chunk=fused_chunk)
                        if engine == "fused" else api.PythonEngine())
        return cls(cfg, loss_fn, optimizer_name=optimizer_name, codec=codec,
                   aggregator=aggregator, round_engine=round_engine)

    # -- Algorithm 1 ---------------------------------------------------------
    def init(self, params):
        K = self.cfg.n_participants
        self._comm_cache = None      # params shapes may differ from last init
        stacked = averaging.stack_participants(params, K)
        opt_state = jax.vmap(self.opt.init)(stacked)
        ctrl = self.sync_policy.init_state(self.cfg.T0)
        # membership starts at the schedule's round-0 mask so initially-
        # dead standby slots log no synthetic leave events; static runs
        # carry the all-live record for checkpoint uniformity
        if self._churn_active:
            mem = membership_mod.Membership(live=tuple(
                bool(a) for a in self.churn.live_mask(0, K)))
        else:
            mem = membership_mod.Membership.all_live(K)
        # stateful rounds start from zero memory — the codec's EF residual
        # (codec owns the mirror structure: leafwise trees / the flat wire
        # buffer), the aggregator's state (D² correction), or both
        residual = self.aggregator.init_round_state(self.codec, stacked)
        return {"params": stacked, "opt": opt_state, "ctrl": ctrl,
                "round": 0, "global_epoch": 0, "prev_avg": None, "log": [],
                "membership": mem, "residual": residual}

    def epochs_budget(self, state):
        """The ELR anneal denominator for the round about to run: epochs
        already run + the policy's extrapolation over the remaining rounds
        (= T0·max_rounds for fixed-T policies; re-estimated after every
        ILE doubling — the old static budget stranded the ELR anneal short
        once T_i doubled). Rides into the fused executables traced, so the
        per-round update is free."""
        return self.sync_policy.epochs_budget(
            state["ctrl"].T, state["round"], state["global_epoch"],
            self.cfg.max_rounds)

    def set_schedule(self, spec):
        """Swap the learning-rate schedule mid-run.

        All built-in schedules share one traced body, so swapping among
        them (or re-parameterizing one) reuses the fused engine's compiled
        executables — the new parameters simply ride in as the next
        round's traced arguments. A custom schedule with its own
        ``traced_lr`` rebinds the engine (one-time retrace)."""
        self.schedule = api.get_schedule(spec, self.cfg)
        # compare against the runner's COMPILED body (not the previous
        # schedule attribute) so a swap also repairs a direct assignment
        bound = getattr(self._runner, "_traced_lr", None)
        if bound is not None and api.traced_body(self.schedule) is not bound:
            self._runner = self.round_engine.bind(self)
        return self

    def set_sync_policy(self, spec):
        """Swap the sync policy mid-run.

        Threshold/epsilon changes ride in as the next round's host/traced
        values; only flipping the divergence gate itself (e.g. ILE ->
        DivergenceTrigger) or changing the traced gate body rebinds the
        fused engine, whose round executables are compiled with or
        without the on-device gate."""
        bound_gated = getattr(self._runner, "_gated", None)
        bound_gate = getattr(self._runner, "_traced_gate", None)
        self.sync_policy = api.get_sync_policy(spec, self.cfg)
        if bound_gated is not None and (
                self.sync_policy.divergence_gated != bound_gated
                or type(self.sync_policy).traced_should_sync
                is not bound_gate):
            self._runner = self.round_engine.bind(self)
        return self

    def param_bytes(self, state):
        one = averaging.unstack_participant(state["params"], 0)
        return sum(t.size * t.dtype.itemsize for t in jax.tree.leaves(one))

    def round_weights(self, round_index, state=None):
        """The aggregator's (K, K) mixing matrix for this round as a device
        array (None for statically-known schemes, e.g. Eq. 2).

        Under active churn with ``liveness_aware`` the matrix renormalizes
        over the round's live set (read from ``state["membership"]``), so
        a matrix is always produced — the aggregate fn was built dynamic.
        """
        if self._churn_active and self.liveness_aware:
            live = (state["membership"].live_mask() if state is not None
                    else None)
            return engine_mod.stage(self.aggregator.mixing_matrix(
                round_index, self.cfg.n_participants, live=live),
                np.float32)
        if not self.aggregator.uses_weights:
            return None
        return engine_mod.stage(self.aggregator.mixing_matrix(
            round_index, self.cfg.n_participants), np.float32)

    def _live_np(self, state):
        """The round's bool (K,) liveness row (None on the static path —
        the engines then run the pre-membership executables)."""
        if not self._churn_active:
            return None
        return state["membership"].live_mask()

    def _round_delta(self, state):
        """The round's divergence threshold: the sync policy's, possibly
        moved by this round's membership events (a join forces the sync so
        the rejoined slot gets the current shared model)."""
        events = (state["membership"].round_events(state["round"])
                  if self._churn_active else ())
        return self.sync_policy.round_delta(events)

    def run_round(self, state, epoch_batches_fn, on_round_end=None):
        """One communication round.

        epoch_batches_fn(round, epoch) -> (K, n_batches, B, ...) pytree for
        that local epoch (each participant sees only its own disjoint shard —
        the data never crosses participants, only parameters do).

        Dispatches to the bound round engine; both engines apply the
        identical state transition (params, opt reset, controller, log).
        Under active churn the membership advances FIRST: the schedule's
        round mask is stepped into ``state["membership"]`` (logging
        join/leave events) and every slot that joined this round warm-
        starts from the last synced shared model before any epoch runs.

        ``on_round_end(learner, state)``, when given, fires after the
        round's state transition lands — the publication hook for
        continuous operation (e.g. ``ModelBank.publish_from``). Its
        return value is ignored; the round's state is returned unchanged.
        """
        if self._churn_active:
            i = state["round"]
            new_live = self.churn.live_mask(i, self.cfg.n_participants)
            if not np.any(new_live):
                raise ValueError(
                    f"churn schedule {self.churn.name!r} leaves zero live "
                    f"participants at round {i}")
            state["membership"] = state["membership"].step(i, new_live)
            for k in state["membership"].joined(i):
                # warm join: restart local training from the last SYNCED
                # shared model (paper failure semantics, elastic form)
                self.restart_participant(state, k)
        state = self._runner.run_round(state, epoch_batches_fn)
        if on_round_end is not None:
            on_round_end(self, state)
        return state

    def _finish_round(self, state, i, T_i, rel, local_losses, lr_first,
                      lr_last, averaged, fresh_opt, new_avg, synced=True,
                      residual=None):
        """The one round state transition, shared verbatim by both engines.

        ``fresh_opt`` is the per-participant opt reset (opt state is
        intentionally NOT averaged: the paper restarts local training from
        the shared model each round). ``new_avg`` stays device-side — no
        full-model host transfer per round. On a round a gated sync policy
        skipped (``synced=False``) the runner passes the untouched local
        params/opt, the unchanged sync reference, and the divergence as
        ``rel`` — and the round bills zero wire bytes. ``residual`` is the
        error-feedback codec's post-round memory (None for stateless
        codecs or when the runner already stored it on ``state``).
        """
        state["params"], state["opt"] = averaged, fresh_opt
        state["prev_avg"] = new_avg
        if residual is not None:
            state["residual"] = residual
        if self._churn_active:
            mem = state["membership"]
            events, n_live = mem.round_events(i), mem.n_live
        else:
            events, n_live = (), self.cfg.n_participants
        state["ctrl"] = self.sync_policy.update(state["ctrl"], i, rel,
                                                synced, events=events)
        state["global_epoch"] += T_i
        # comm volume per participant, priced by the aggregator through the
        # codec (compressed upload + raw download; gossip pays wire both
        # ways); round-independent accounting (all built-in aggregators) is
        # computed once — flat-codec pricing rebuilds a host-side layout
        # table, which must stay off the per-round path. Under active churn
        # the live set changes the bill per round, so the cache is bypassed
        # and only live rows are billed.
        if not synced:
            comm = 0
        elif self._churn_active:
            comm = self.aggregator.comm_bytes(
                self.codec, state["params"], i,
                live=state["membership"].live_mask())
        elif self.aggregator.static_comm:
            if self._comm_cache is None:
                self._comm_cache = self.aggregator.comm_bytes(
                    self.codec, state["params"], i)
            comm = self._comm_cache
        else:
            comm = self.aggregator.comm_bytes(self.codec, state["params"], i)
        state["round"] = i + 1
        state["log"].append(RoundLog(i, T_i, lr_first, lr_last, rel,
                                     local_losses, comm, synced,
                                     live=n_live))
        return state

    # legacy handles used by tests/benchmarks to poke at the fused
    # executables' compilation caches
    def _fused_handle(self, attr):
        if not hasattr(self._runner, attr):
            raise AttributeError(
                f"_fused{attr} is only available with "
                f"round_engine=FusedEngine(); this learner runs "
                f"{self.round_engine.name!r}")
        return getattr(self._runner, attr)

    @property
    def _fused_round(self):
        return self._fused_handle("_round")

    @property
    def _fused_epochs(self):
        return self._fused_handle("_epochs")

    @property
    def _fused_finalize(self):
        return self._fused_handle("_finalize")

    def shared_model(self, state):
        # under churn the canonical slot is the first LIVE one — a dead
        # slot 0 holds the stale pre-crash model, not the shared average
        live = self._live_np(state)
        k0 = 0 if live is None else int(np.argmax(live))
        return averaging.unstack_participant(state["params"], k0)

    def _sync_ref(self, state):
        """The last synced shared model — the Eq. 4 / divergence reference
        both engines measure against. Before the first sync (round 0, when
        every slot still holds the init model) it is slot 0 of the entry
        params; afterwards ``prev_avg``, which gated runs advance only on
        synced rounds."""
        if state["prev_avg"] is not None:
            return state["prev_avg"]
        live = self._live_np(state)
        k0 = 0 if live is None else int(np.argmax(live))
        return averaging.unstack_participant(state["params"], k0)

    # -- failure handling (paper: restart the participant's local training) --
    def restart_participant(self, state, k):
        """Reset participant k's replica to the last SYNCED shared model.

        Both the parameters AND the optimizer state row are reset (a stale
        momentum/Adam moment would keep pushing the restarted replica along
        its pre-failure trajectory — the paper's failure semantics restart
        local training from the shared model outright).

        The reference is ``_sync_ref`` (``prev_avg``, i.e. the last synced
        average), NOT slot 0 of the current params: under ``RingGossip``
        the rows stay distinct, and after a quiet ``DivergenceTrigger``
        round slot 0 holds a locally-drifted model — resetting from either
        would hand the restarted participant some peer's private
        trajectory instead of the shared model the contract promises.
        """
        shared = self._sync_ref(state)
        k_dev = engine_mod.stage(k, np.int32)
        state["params"], state["opt"] = self._jit_restart(
            state["params"], state["opt"], shared, k_dev)
        if self._round_stateful and state.get("residual") is not None:
            # restart also forgets the round-state memory (quantization
            # error residual and/or D² correction): it tracked a
            # trajectory that no longer exists
            state["residual"] = self._jit_zero_row(state["residual"], k_dev)
        return state
