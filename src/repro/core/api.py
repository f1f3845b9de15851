"""Composable round-strategy API:
WireCodec x Aggregator x RoundEngine x LRSchedule x SyncPolicy.

The paper's Algorithm 1 is one point in a family of decentralized-averaging
protocols — FedAvg-style partial participation (McMahan et al., 1602.05629)
and dynamic/partial model averaging (Kamp et al., 1807.03210) differ from it
only in *who aggregates what, over which wire, with which engine, under
which local-training policy*. This module factors those five axes into
small protocols so a new aggregation scheme or local-training rule is a new
class, not another constructor flag plus an ``if`` in three files:

* :class:`WireCodec` — how one participant's stacked parameters travel:
  ``encode``/``decode`` (whose composition is the in-sim wire-roundtrip
  emulation) plus exact per-participant ``wire_bytes`` accounting.
  Instances: :class:`ExactF32` (the paper-faithful f32 wire),
  :class:`LeafwiseIntN` (per-leaf blockwise roundtrip at 8/4/1 bits,
  ``core.compression``; sub-block leaves bypass the codec and are billed
  at raw rates), :class:`FlatFusedIntN` (the flat-buffer wire format,
  ``core.flatbuf`` + ``kernels.comm`` — every element on the wire format,
  bytes exact by construction). Both take ``error_feedback=True`` for
  residual-memory compensation (a STATEFUL codec — the engines thread the
  residual through the round executables as traced data);
  :class:`LeafwiseInt8` / :class:`FlatFusedInt8` remain the bit-for-bit
  ``bits=8`` points.

* :class:`Aggregator` — who averages what. Each aggregator is a row-
  stochastic ``(K, K)`` *mixing matrix* per round applied over the
  participant axis of the codec-roundtripped params (the classic gossip-
  matrix formulation). Instances: :class:`FullAverage` (paper Eq. 2 —
  uniform matrix, routed through the codec's fused-mean kernel when it has
  one), :class:`PartialParticipation` (FedAvg-style: ``m <= K`` sampled
  participants per round, weighted by shard size, broadcast back to all),
  :class:`GraphGossip` (serverless gossip over any
  :mod:`repro.core.topology` graph — ring, torus, hypercube, time-varying
  one-peer exponential, Erdős–Rényi — the rows stay distinct),
  :class:`RingGossip` (the legacy fixed ring, now
  ``GraphGossip(RingTopology())``), :class:`D2Gossip` (graph gossip plus
  the D² variance-reduction correction for non-IID shards — a STATEFUL
  aggregator whose per-participant correction rides the same engine state
  slot as the codec error-feedback residual). Aggregators also own the
  per-round comm-byte accounting, priced through the codec.

* :class:`RoundEngine` — how the round executes. :class:`PythonEngine`
  (reference host loop, one jit dispatch per epoch) and
  :class:`FusedEngine` (one donated executable per round via
  ``repro.core.engine``, chunked past ``chunk`` staged epochs). Engines
  ``bind(learner)`` into runners holding the compiled artifacts.

* :class:`LRSchedule` — the Eq. 3 family: the per-epoch learning rate as a
  traced function of (round, epoch_j, T_i, global_epoch, total_budget),
  plus a per-round *host hook* (``round_params``) producing the scalar
  parameter pack (η^i, decay, ...) that rides into the round executable as
  a traced argument. Instances: :class:`CLR` (paper Eq. 3 — per-round
  exponential restart), :class:`ELR` (the non-cyclical anneal baseline),
  :class:`WarmupCLR` (η^i ramped over the first rounds — the host hook in
  action: the ramp never recompiles), :class:`CosineCyclical` (SGDR-style
  per-round cosine). All built-ins share ONE traced body
  (``schedule.switch_lr``), so swapping them reuses the fused executables.

* :class:`SyncPolicy` — Eq. 4 generalized: decides next round's T_i *and*
  whether the round communicates at all, owning the host-side
  :class:`SyncState` (T, (round, rel, T) history, skipped rounds).
  Instances: :class:`ILE` (paper Eq. 4 — double T_i once the shared model
  stabilizes), :class:`FLE` (fixed T_i), :class:`DivergenceTrigger`
  (Kamp et al., 1807.03210: sync only while the local models' divergence
  from the last synced model exceeds δ — quiet rounds skip the averaging
  step and bill zero wire bytes).

``CoLearner(codec=..., aggregator=..., round_engine=..., schedule=...,
sync_policy=...)`` composes the five; string registry names ("leafwise",
"partial", "fused", "clr", "divtrigger", ...) resolve through
:data:`CODECS` / :data:`AGGREGATORS` / :data:`ENGINES` / :data:`SCHEDULES`
/ :data:`SYNC_POLICIES`. The legacy flag surface lives on in
``CoLearner.from_flags`` and the ``CoLearnConfig.schedule``/``epochs_rule``
strings (see the migration table in ROADMAP.md §Round strategy API).
"""
from __future__ import annotations

import abc
import dataclasses
import inspect
import math
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import spans
from repro.core import averaging, compression, engine as engine_mod, flatbuf
from repro.core import schedule as sched_mod
from repro.core.schedule import (LR_COS_ROUND, LR_EXP_GLOBAL, LR_EXP_ROUND,
                                 N_SCHED_PARAMS, clr_lr, cosine_lr, elr_lr,
                                 relative_change, switch_lr)
from repro.kernels import ops as kops
from repro.kernels.quantize import DEFAULT_BLOCK


def participant_bytes(stacked) -> int:
    """Raw per-participant bytes of a stacked ``(K, ...)`` params tree at
    its native dtypes — the f32/bf16 download side of the accounting."""
    total = 0
    for t in jax.tree.leaves(stacked):
        total += (t.size // t.shape[0]) * jnp.dtype(t.dtype).itemsize
    return total


def _one_participant_shapes(stacked):
    """ShapeDtypeStruct tree of ONE participant (leading K stripped)."""
    return jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape[1:], t.dtype), stacked)


# ---------------------------------------------------------------------------
# WireCodec
# ---------------------------------------------------------------------------
class WireCodec(abc.ABC):
    """What one participant's upload looks like on the wire.

    ``decode(encode(stacked))`` is the in-sim wire emulation (identity for
    the exact codec, a blockwise quantization roundtrip otherwise);
    ``roundtrip`` is that composition and is what aggregators trace into
    the round executable. ``wire_bytes`` is the exact per-participant
    upload byte count, bypasses and padding included.

    A codec may carry per-participant STATE — error-feedback residual
    memory, the standard trick that keeps sub-int8 quantization convergent.
    ``stateful`` advertises it, ``init_state(stacked)`` builds the zero
    residual, and ``roundtrip_ef(stacked, residual)`` is the stateful wire
    emulation returning ``(roundtripped, new_residual)``. Aggregators then
    build ``aggregate(stacked, weights, residual) -> (mixed, new_residual)``
    and the engines thread the residual through the round executables as
    traced data (no retraces, see ``CoLearner``/``core.engine``).
    """

    name: str = "codec"

    @property
    def stateful(self) -> bool:
        """True when the codec carries per-participant residual memory."""
        return False

    def init_state(self, stacked):
        """Zero codec state for a stacked ``(K, ...)`` tree (accepts
        ``ShapeDtypeStruct`` trees too); None for stateless codecs."""
        return None

    def roundtrip_ef(self, stacked, residual):
        """Stateful wire emulation: quantize ``x + e``, return
        ``(roundtripped, new_residual)`` with ``e' = (x + e) - dequant``."""
        raise NotImplementedError(
            f"codec {self.name!r} is stateless (no error feedback)")

    @abc.abstractmethod
    def encode(self, stacked):
        """Stacked ``(K, ...)`` params tree -> wire representation."""

    @abc.abstractmethod
    def decode(self, wire):
        """Wire representation -> stacked params tree (original dtypes)."""

    def roundtrip(self, stacked):
        """The wire emulation the aggregator applies before mixing."""
        return self.decode(self.encode(stacked))

    @abc.abstractmethod
    def wire_bytes(self, stacked) -> int:
        """Exact bytes ONE participant uploads for this stacked tree."""

    def make_fused_mean(self, mesh=None, axis="pod", weighted=False,
                        stateful=False):
        """Optional codec-owned Eq. 2 fast path (wire roundtrip + mean as
        one fused pass). ``None`` means the aggregator composes
        ``roundtrip`` with a generic mean instead. ``FullAverage`` consults
        this so the flat-buffer kernel keeps owning its pod shard_map.
        ``weighted=True`` asks for the example-count-weighted variant —
        ``fn(stacked, wrow)`` with a traced normalized length-K weight row
        (FedAvg's unequal-shard generalization of Eq. 2). ``stateful=True``
        asks for the error-feedback variant, whose fn takes the residual
        as its last argument and returns ``(mean_tree, new_residual)``."""
        return None


@dataclasses.dataclass(frozen=True)
class ExactF32(WireCodec):
    """The paper-faithful wire: parameters travel at their raw dtypes."""

    name = "exact"

    def encode(self, stacked):
        return stacked

    def decode(self, wire):
        return wire

    def wire_bytes(self, stacked) -> int:
        return participant_bytes(stacked)


@dataclasses.dataclass(frozen=True)
class LeafwiseIntN(WireCodec):
    """Per-leaf blockwise quantization roundtrip at ``bits`` ∈ {8, 4, 1}
    (the tested reference wire path; int4 packs two codes per byte, 1-bit
    is sign + per-block mean-|x| scale — ``repro.kernels.quantize``).

    Leaves smaller than one quantization ``block`` (and scalars) bypass the
    codec and travel uncompressed; ``wire_bytes`` bills them at raw-dtype
    rates (``compression.compressed_bytes``). Note the emulation runs on
    the STACKED tree, so the bypass threshold sees ``K * size`` — see
    ``core.compression`` for the accounting caveat at small K.

    ``error_feedback=True`` makes the codec STATEFUL: each participant
    keeps an f32 residual mirror of the params, quantizes ``x + e`` and
    carries ``e' = (x + e) - dequant`` to the next round — the standard
    compensation that keeps int4/1-bit wires convergent. ``bits=8,
    error_feedback=False`` is bit-for-bit :class:`LeafwiseInt8`.
    """

    block: int = DEFAULT_BLOCK
    impl: str | None = None          # None: the kernel on TPU, else ref
    bits: int = 8
    error_feedback: bool = False

    def __post_init__(self):
        from repro.kernels.quantize import check_bits
        check_bits(self.bits)
        object.__setattr__(self, "impl", kops.resolve_impl(self.impl))

    @property
    def name(self):
        tag = "leafwise" if self.bits == 8 else f"leafwise-int{self.bits}"
        return tag + "+ef" if self.error_feedback else tag

    @property
    def stateful(self) -> bool:
        return self.error_feedback

    def init_state(self, stacked):
        if not self.error_feedback:
            return None
        # f32 mirror of every stacked leaf; bypassed leaves keep zero
        # residual forever (roundtrip_ef passes them through untouched)
        return jax.tree.map(
            lambda t: jnp.zeros(t.shape, jnp.float32), stacked)

    def roundtrip_ef(self, stacked, residual):
        return compression.quantize_roundtrip_ef(
            stacked, residual, block=self.block, impl=self.impl,
            bits=self.bits)

    def encode(self, stacked):
        leaves, treedef = jax.tree.flatten(stacked)
        enc = []
        for t in leaves:
            if t.ndim == 0 or t.size < self.block:
                enc.append(("raw", t, None))
            else:
                enc.append((f"q{self.bits}", kops.quantize_blockwise(
                    t, block=self.block, bits=self.bits, impl=self.impl),
                    t.dtype))
        return (treedef, tuple(enc))

    def decode(self, wire):
        treedef, enc = wire
        leaves = []
        for kind, payload, dtype in enc:
            if kind == "raw":
                leaves.append(payload)
            else:
                q, scale, shape = payload
                leaves.append(kops.dequantize_blockwise(
                    q, scale, shape, bits=self.bits,
                    impl=self.impl).astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    # roundtrip = decode(encode(x)) — the inherited default. It applies the
    # identical per-leaf branch + kernels as the PR-2 reference
    # ``compression.quantize_roundtrip``; tests/test_api.py pins the two
    # bitwise so the bypass threshold can never drift between them.

    def wire_bytes(self, stacked) -> int:
        return compression.compressed_bytes(_one_participant_shapes(stacked),
                                            block=self.block, bits=self.bits)


@dataclasses.dataclass(frozen=True)
class LeafwiseInt8(LeafwiseIntN):
    """The PR-2 int8 reference wire, now the ``bits=8`` point of
    :class:`LeafwiseIntN` (kept as a named class for the registry and the
    bit-for-bit compatibility pin in tests/test_api.py)."""

    name = "leafwise"


@dataclasses.dataclass(frozen=True)
class FlatFusedIntN(WireCodec):
    """The flat-buffer wire format at ``bits`` ∈ {8, 4, 1}: one contiguous
    ``(K, N_pad)`` buffer, every leaf on the packed-payload + per-block-
    scale format, bytes exact by construction (``core.flatbuf``). Under
    :class:`FullAverage` the whole quantize->average->dequantize pass runs
    as ONE kernel (``kernels.comm.quant_avg_dequant``), on the pod mesh as
    one shard_map psum of one buffer.

    ``error_feedback=True`` makes the codec STATEFUL: the residual is one
    ``(K, N_pad)`` f32 buffer riding the same flat layout, and the fused
    kernel becomes ``quant_avg_dequant_ef`` — mean AND new residual in one
    pass. ``bits=8, error_feedback=False`` is bit-for-bit
    :class:`FlatFusedInt8`."""

    block: int = DEFAULT_BLOCK
    impl: str | None = None          # None: the kernel on TPU, else ref
    bits: int = 8
    error_feedback: bool = False

    def __post_init__(self):
        from repro.kernels.quantize import check_bits
        check_bits(self.bits)
        object.__setattr__(self, "impl", kops.resolve_impl(self.impl))

    @property
    def name(self):
        tag = "fused" if self.bits == 8 else f"fused-int{self.bits}"
        return tag + "+ef" if self.error_feedback else tag

    @property
    def stateful(self) -> bool:
        return self.error_feedback

    def init_state(self, stacked):
        if not self.error_feedback:
            return None
        layout = flatbuf.make_layout(stacked, block=self.block)
        return jnp.zeros((layout.k, layout.n_pad), jnp.float32)

    def roundtrip_ef(self, stacked, residual):
        layout = flatbuf.make_layout(stacked, block=self.block)
        buf = flatbuf.flatten(stacked, layout)
        y = buf + residual
        q, scale, shape = kops.quantize_blockwise(y, block=self.block,
                                                  bits=self.bits,
                                                  impl=self.impl)
        dq = kops.dequantize_blockwise(q, scale, shape, bits=self.bits,
                                       impl=self.impl)
        return flatbuf.unflatten(dq, layout), y - dq

    def encode(self, stacked):
        layout = flatbuf.make_layout(stacked, block=self.block)
        buf = flatbuf.flatten(stacked, layout)
        q, scale, shape = kops.quantize_blockwise(buf, block=self.block,
                                                  bits=self.bits,
                                                  impl=self.impl)
        return (layout, q, scale, shape)

    def decode(self, wire):
        layout, q, scale, shape = wire
        buf = kops.dequantize_blockwise(q, scale, shape, bits=self.bits,
                                        impl=self.impl)
        return flatbuf.unflatten(buf, layout)

    def wire_bytes(self, stacked) -> int:
        return compression.flat_compressed_bytes(stacked, block=self.block,
                                                 bits=self.bits)

    def make_fused_mean(self, mesh=None, axis="pod", weighted=False,
                        stateful=False):
        if stateful and not self.error_feedback:
            raise ValueError("stateful fused mean requires error_feedback")
        return engine_mod.make_fused_compressed_average(
            block=self.block, impl=self.impl, bits=self.bits, mesh=mesh,
            axis=axis, weighted=weighted, stateful=stateful)


@dataclasses.dataclass(frozen=True)
class FlatFusedInt8(FlatFusedIntN):
    """The PR-3 flat-buffer int8 wire, now the ``bits=8`` point of
    :class:`FlatFusedIntN` (kept as a named class for the registry and the
    bit-for-bit compatibility pin in tests)."""

    name = "fused"


@dataclasses.dataclass(frozen=True)
class CustomFn(WireCodec):
    """Escape hatch wrapping an arbitrary stacked->stacked wire transform
    (the legacy ``CoLearner(compress_fn=...)``). The encoding is opaque, so
    ``wire_bytes`` conservatively bills raw-dtype bytes."""

    fn: Callable
    name = "custom"

    def encode(self, stacked):
        return self.fn(stacked)

    def decode(self, wire):
        return wire

    def wire_bytes(self, stacked) -> int:
        return participant_bytes(stacked)


# ---------------------------------------------------------------------------
# Aggregator
# ---------------------------------------------------------------------------
def mix_participants(stacked, weights):
    """Apply a row-stochastic ``(K, K)`` mixing matrix over the participant
    axis: slot k receives ``sum_j W[k, j] * w_j``. Uniform rows give Eq. 2;
    a circulant gives ring gossip; broadcast sampled rows give FedAvg-style
    partial participation."""
    W = weights.astype(jnp.float32)

    def one(t):
        mixed = jnp.einsum("kj,j...->k...", W, t.astype(jnp.float32))
        return mixed.astype(t.dtype)

    return jax.tree.map(one, stacked)


def _check_one_row_per_pod(aggregator, stacked, mesh, axis):
    """The weighted pod specializations permute/scale whole local blocks,
    so they are only correct with exactly one participant row per pod —
    fail loudly instead of silently mixing the wrong rows."""
    k_rows = jax.tree.leaves(stacked)[0].shape[0]
    k_pods = mesh.shape[axis]
    if k_rows != k_pods:
        raise ValueError(
            f"pod-path {aggregator.name!r} aggregation requires one "
            f"participant row per pod: params have K={k_rows}, mesh axis "
            f"{axis!r} has {k_pods} pods")


def _make_weighted_psum_aggregate(aggregator, codec, mesh, param_specs,
                                  axis):
    """Pod-path broadcast-weighted mean, shared by the aggregators whose
    mixing matrix has identical rows (weighted ``FullAverage``,
    ``PartialParticipation``): every pod downloads the same weighted mean,
    so the pod path psums each pod's weight-scaled, codec-roundtripped
    local row (one psum per leaf, f32 payloads, combinable by XLA) —
    O(model) cross-pod traffic and never a K-way gather; the single-buffer
    quantized collective remains the flat-codec weighted/uniform fast path.

    For a STATEFUL codec the local row's roundtrip is the error-feedback
    one (``roundtrip_ef``) — each pod's residual stays resident on that
    pod (it never crosses the wire) and the aggregate returns it alongside
    the mean: ``aggregate(stacked, weights, residual) -> (mixed, new_res)``
    with the residual sharded like the params (leafwise mirror tree)."""
    from jax.sharding import PartitionSpec as P

    if getattr(codec, "stateful", False):
        def aggregate_ef(stacked, weights, residual):
            _check_one_row_per_pod(aggregator, stacked, mesh, axis)

            def local_mix(local, wrow, lres):
                rt, new_res = codec.roundtrip_ef(local, lres)
                k = jax.lax.axis_index(axis)

                def one(t):
                    s = jax.lax.psum(wrow[k] * t.astype(jnp.float32), axis)
                    return s.astype(t.dtype)
                return jax.tree.map(one, rt), new_res

            return jax.shard_map(
                local_mix, mesh=mesh, in_specs=(param_specs, P(),
                                                param_specs),
                out_specs=(param_specs, param_specs),
                check_vma=False)(stacked, weights[0], residual)
        return aggregate_ef

    def aggregate(stacked, weights):
        _check_one_row_per_pod(aggregator, stacked, mesh, axis)

        def local_mix(local, wrow):
            rt = codec.roundtrip(local)         # local row only: the upload
            k = jax.lax.axis_index(axis)

            def one(t):
                s = jax.lax.psum(wrow[k] * t.astype(jnp.float32), axis)
                return s.astype(t.dtype)
            return jax.tree.map(one, rt)

        return jax.shard_map(
            local_mix, mesh=mesh, in_specs=(param_specs, P()),
            out_specs=param_specs, check_vma=False)(stacked, weights[0])
    return aggregate


def normalized_weights(weights, K: int) -> np.ndarray:
    """Validate per-participant averaging weights (e.g. shard example
    counts) and return them normalized to sum 1 as a length-K f64 array."""
    w = np.asarray(weights, np.float64)
    if w.shape != (K,):
        raise ValueError(f"weights must have length K={K}; got {w.shape}")
    if not np.isfinite(w).all() or (w < 0).any():
        raise ValueError(f"weights must be finite and >= 0; got {w}")
    if not w.sum() > 0:
        raise ValueError("weights must not all be zero")
    return w / w.sum()


class Aggregator(abc.ABC):
    """Who aggregates what: a per-round mixing matrix + byte accounting.

    ``make_aggregate_fn(codec, ...)`` returns ``aggregate(stacked, weights)``
    — traced into the round executable; ``weights`` is the ``(K, K)``
    matrix from ``mixing_matrix`` (or ``None`` when ``uses_weights`` is
    False and the matrix is statically known, e.g. Eq. 2's uniform mean).
    ``comm_bytes`` prices the round per participant through the codec.
    """

    name: str = "aggregator"
    #: False => the aggregate fn ignores the weights argument (statically
    #: known matrix); the driver then passes None and avoids the transfer.
    uses_weights: bool = True
    #: True => ``comm_bytes`` is round-independent for fixed param shapes,
    #: so the driver computes it once per learner instead of per round.
    #: Aggregators whose accounting varies per round must set this False.
    #: (Under elastic membership the driver bypasses the cache anyway —
    #: the live set changes the bill per round.)
    static_comm: bool = True

    @abc.abstractmethod
    def mixing_matrix(self, round_index: int, K: int,
                      live=None) -> np.ndarray:
        """Row-stochastic (K, K) f32 matrix for this round (host-side).

        ``live`` (elastic membership): a bool (K,) liveness row. The
        matrix must then mix over LIVE columns only — renormalized
        averaging rows, live-sampled participants, routed gossip edges —
        and dead rows may be anything row-stochastic (the engine restores
        dead rows to their own params after mixing, so by convention they
        get identity or broadcast rows). ``None`` is the static-K matrix.
        """

    def make_aggregate_fn(self, codec: WireCodec, *, mesh=None,
                          param_specs=None, axis="pod", dynamic=False):
        """Build ``aggregate(stacked, weights)``. Dispatches to the pod-path
        specialization hook when a mesh is given; subclasses customize via
        ``_make_mesh_aggregate_fn`` / ``_make_host_aggregate_fn`` so the
        mesh dispatch cannot be accidentally bypassed.

        ``dynamic=True`` (elastic membership): the mixing matrix changes
        per round (live-set renormalization), so the built fn must honor
        the traced ``weights`` argument every call — specializations that
        bake a static matrix (uniform fused means, static gossip permutes)
        are skipped in favor of the weighted paths."""
        if mesh is not None and param_specs is not None:
            fn = self._make_mesh_aggregate_fn(codec, mesh, param_specs, axis,
                                              dynamic=dynamic)
            if fn is not None:
                return fn
        return self._make_host_aggregate_fn(codec)

    def _make_host_aggregate_fn(self, codec):
        """Simulation-path aggregation (single host, all K rows visible).

        Stateful codecs (error feedback) change the signature to
        ``aggregate(stacked, weights, residual) -> (mixed, new_residual)``
        — the residual is traced data alongside the params."""
        if getattr(codec, "stateful", False):
            def aggregate_ef(stacked, weights, residual):
                rt, new_res = codec.roundtrip_ef(stacked, residual)
                return mix_participants(rt, weights), new_res
            return aggregate_ef

        def aggregate(stacked, weights):
            return mix_participants(codec.roundtrip(stacked), weights)
        return aggregate

    def _make_mesh_aggregate_fn(self, codec, mesh, param_specs, axis,
                                dynamic=False):
        """Pod-path specialization hook: return an aggregate fn whose only
        cross-pod traffic is the aggregator's actual wire pattern (a psum,
        a permute, ...). None falls back to the dense mixing einsum — which
        under GSPMD gathers every pod's replica across ``axis``, so any
        aggregator meant for the pod path should override this.
        ``dynamic=True``: the per-round matrix varies (elastic membership);
        return None unless the specialization honors ``weights``."""
        return None

    @abc.abstractmethod
    def comm_bytes(self, codec: WireCodec, stacked, round_index: int,
                   live=None) -> int:
        """Per-participant wire bytes for this round (upload + download).

        ``live`` (elastic membership): a bool (K,) liveness row — only
        live rows upload/download, so the per-live-participant bill
        changes with the live set."""

    @property
    def stateful(self) -> bool:
        """True when the AGGREGATOR carries per-participant round state
        (e.g. :class:`D2Gossip`'s variance-reduction correction). The
        engines thread ONE state slot — ``state["residual"]`` — through
        the donated round executables; it holds the codec's
        error-feedback memory, the aggregator's state, or both (see
        ``init_round_state``), and the aggregate fn takes the 3-arg
        stateful form ``aggregate(stacked, weights, state) ->
        (mixed, new_state)`` whenever either side is stateful."""
        return False

    def init_round_state(self, codec: WireCodec, stacked):
        """Zero per-participant round state for this (codec, aggregator)
        pair — the pytree the engines thread through the round
        executables, or None when neither side is stateful. Whatever
        structure this returns is persisted by ``checkpoint/io.py``,
        carried unchanged through quiet sync-policy rounds, frozen for
        dead slots via ``select_live``, and zeroed per-row on
        ``restart_participant`` — all generically over the pytree."""
        if getattr(codec, "stateful", False):
            return codec.init_state(stacked)
        return None


@dataclasses.dataclass(frozen=True)
class FullAverage(Aggregator):
    """Paper Eq. 2: every participant uploads, the server averages, everyone
    downloads the shared model.

    ``weights=None`` (the default) is the paper's uniform mean, routed
    through the codec's fused-mean kernel when it has one (flat-buffer
    path: one quant->avg->dequant pass; on a pod mesh one shard_map psum of
    one buffer), else through ``averaging.average_pjit`` /
    ``make_average_shard_map`` over the codec-roundtripped params —
    bit-for-bit the PR-2 behavior.

    ``weights=(n_1, ..., n_K)`` — per-participant example counts (any
    nonnegative weights; normalized internally) — is FedAvg's
    generalization of Eq. 2 to unequal shards (McMahan et al., 1602.05629):
    w̄ = Σ_k (n_k/n) w_k. The weight row rides into the round executables
    as a traced mixing-matrix row (``mix_participants`` plumbing), the
    flat-buffer codec keeps a fused weighted-mean pass
    (``make_fused_compressed_average(weighted=True)``), and the pod path
    psums the weight-scaled local rows.
    """

    weights: tuple | None = None
    name = "full"

    @property
    def uses_weights(self):
        # uniform Eq. 2 is statically known (no weight transfer, fused
        # kernel fast path); explicit weights ride in traced per round
        return self.weights is not None

    def mixing_matrix(self, round_index, K, live=None):
        if live is None:
            if self.weights is None:
                return np.full((K, K), 1.0 / K, np.float32)
            w = normalized_weights(self.weights, K)
            # every row identical: all K download the same weighted mean
            return np.broadcast_to(w, (K, K)).astype(np.float32)
        # elastic membership: renormalize the (possibly weighted) averaging
        # row over the LIVE participants — a dead row's stale model must
        # not drag the mean (the benchmarks/churn.py ablation measures
        # exactly this against the naive static row)
        base = (np.ones(K, np.float64) if self.weights is None
                else np.asarray(self.weights, np.float64))
        if base.shape != (K,):
            raise ValueError(f"weights must have length K={K}")
        if not np.isfinite(base).all() or (base < 0).any():
            raise ValueError(f"weights must be finite and >= 0; got {base}")
        w = base * np.asarray(live, bool)
        if not w.sum() > 0:
            raise ValueError(
                "no live participant carries averaging weight at round "
                f"{round_index} (live={np.asarray(live, bool)})")
        w /= w.sum()
        # every row identical: all LIVE rows download the same mean (the
        # engine restores dead rows to their own params after mixing)
        return np.broadcast_to(w, (K, K)).astype(np.float32)

    def make_aggregate_fn(self, codec, *, mesh=None, param_specs=None,
                          axis="pod", dynamic=False):
        stateful = getattr(codec, "stateful", False)
        if self.weights is not None or dynamic:
            # per-round weight row (explicit weights and/or live-set
            # renormalization) — always the weighted paths
            fused = codec.make_fused_mean(mesh=mesh, axis=axis,
                                          weighted=True, stateful=stateful)
            if fused is not None:
                if stateful:
                    return lambda stacked, weights, residual: fused(
                        stacked, weights[0], residual)
                return lambda stacked, weights: fused(stacked, weights[0])
            if mesh is not None and param_specs is not None:
                return _make_weighted_psum_aggregate(
                    self, codec, mesh, param_specs, axis)
            return self._make_host_aggregate_fn(codec)
        fused = codec.make_fused_mean(mesh=mesh, axis=axis,
                                      stateful=stateful)
        if fused is not None:
            if stateful:
                return lambda stacked, weights, residual: fused(stacked,
                                                                residual)
            return lambda stacked, weights=None: fused(stacked)
        if mesh is not None and param_specs is not None:
            if stateful:
                # EF uniform mean on the pod mesh without a fused kernel:
                # the broadcast-weighted psum with a baked uniform row —
                # each pod's residual stays resident (never on the wire)
                psum = _make_weighted_psum_aggregate(
                    self, codec, mesh, param_specs, axis)
                K = mesh.shape[axis]
                uni = jnp.full((K, K), 1.0 / K, jnp.float32)
                return lambda stacked, weights, residual: psum(
                    stacked, uni, residual)
            sm = averaging.make_average_shard_map(mesh, param_specs, axis)
            return lambda stacked, weights=None: sm(codec.roundtrip(stacked))
        if stateful:
            def aggregate_ef(stacked, weights, residual):
                rt, new_res = codec.roundtrip_ef(stacked, residual)
                return averaging.average_pjit(rt), new_res
            return aggregate_ef
        return lambda stacked, weights=None: averaging.average_pjit(
            codec.roundtrip(stacked))

    def comm_bytes(self, codec, stacked, round_index, live=None):
        # upload on the codec's wire + f32/raw download of the shared
        # model; under elastic membership only live rows touch the wire,
        # so the PER-LIVE-PARTICIPANT bill is the same expression
        return codec.wire_bytes(stacked) + participant_bytes(stacked)


@dataclasses.dataclass(frozen=True)
class PartialParticipation(Aggregator):
    """FedAvg-style partial participation (McMahan et al., 1602.05629):
    each round samples ``m <= K`` participants without replacement and the
    new shared model is their weighted average, broadcast back to every
    participant (all K keep training locally; only the sampled uploads
    cross the WAN).

    ``weights``: optional length-K per-participant weights — pass the shard
    example counts for FedAvg's shard-size-weighted average. When omitted
    the average falls back to UNIFORM over the sampled participants (the
    equal-shard special case); ``CoLearner(shard_sizes=...)`` auto-wires
    the shard sizes in, so a learner that knows its data never silently
    uses the uniform fallback on unequal shards. Sampling is deterministic
    in (seed, round) so the python and fused engines see identical rounds.
    """

    m: int = 2
    weights: tuple | None = None
    seed: int = 0
    name = "partial"

    def mixing_matrix(self, round_index, K, live=None):
        if not 1 <= self.m <= K:
            raise ValueError(f"need 1 <= m <= K, got m={self.m} K={K}")
        base = (np.asarray(self.weights, np.float64) if self.weights
                is not None else np.ones(K))
        if base.shape != (K,):
            raise ValueError(f"weights must have length K={K}")
        if not np.isfinite(base).all() or (base < 0).any():
            raise ValueError(f"weights must be finite and >= 0; got {base}")
        if live is not None:
            # elastic membership: only live participants can be sampled;
            # a shrunken live set shrinks the draw (m_eff = min(m, live))
            # rather than erroring — error only when NOTHING is live
            base = base * np.asarray(live, bool)
            if not (base > 0).any():
                raise ValueError(
                    "partial participation has zero live participants "
                    f"with positive weight at round {round_index} "
                    f"(live={np.asarray(live, bool)})")
        # only participants with weight can be sampled — a zero-weight-only
        # sample would otherwise normalize 0/0 into a NaN mixing matrix
        eligible = np.nonzero(base > 0)[0]
        m_eff = min(self.m, len(eligible)) if live is not None else self.m
        if len(eligible) < m_eff:
            raise ValueError(
                f"need m={m_eff} participants with positive weight; "
                f"only {len(eligible)} of K={K} have one")
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, round_index]))
        sel = rng.choice(eligible, size=m_eff, replace=False)
        w = np.zeros(K, np.float64)
        w[sel] = base[sel]
        w /= w.sum()
        # every row identical: all K download the same new shared model
        return np.broadcast_to(w, (K, K)).astype(np.float32)

    def _make_mesh_aggregate_fn(self, codec, mesh, param_specs, axis,
                                dynamic=False):
        # rows of the mixing matrix are identical (everyone downloads the
        # same weighted mean), so the broadcast-weighted psum specialization
        # applies — shared with weighted FullAverage; the weight row is
        # honored per call, so it serves the dynamic (live-set) case too
        return _make_weighted_psum_aggregate(self, codec, mesh, param_specs,
                                             axis)

    def comm_bytes(self, codec, stacked, round_index, live=None):
        K = jax.tree.leaves(stacked)[0].shape[0]
        up = codec.wire_bytes(stacked)          # only m of K pay the upload
        if live is not None:
            n_live = max(int(np.asarray(live, bool).sum()), 1)
            m_eff = min(self.m, n_live)
            # only the n_live rows touch the wire; the sampled-upload cost
            # amortizes over them, every live row pays the download
            return (math.ceil(m_eff * up / n_live)
                    + participant_bytes(stacked))
        return math.ceil(self.m * up / K) + participant_bytes(stacked)


@dataclasses.dataclass(frozen=True)
class GraphGossip(Aggregator):
    """One gossip exchange per round over an arbitrary sparse topology
    (consensus SGD, Jiang et al., 1706.07880): no server — participant k
    mixes its model with its graph neighbors' through the topology's
    row-stochastic (all-live: doubly stochastic) mixing matrix, so
    repeated rounds contract toward consensus at the graph's
    spectral-gap rate while models stay distinct within a round.

    ``topology`` is a :mod:`repro.core.topology` instance or registry
    name (``"ring"`` | ``"grid2d"`` | ``"hypercube"`` | ``"exponential"``
    | ``"erdos_renyi"`` | ``"complete"``); None is the ring. Time-varying
    graphs ride the per-round matrix into the unchanged donated
    executables as traced data — a graph change is never a recompile.
    Disconnected topologies are rejected at learner construction
    (``validate``). Liveness renormalizes over the live subgraph: the
    topology routes around dead nodes or drops their edges, a sole
    survivor keeps its own model, and if churn splits the graph, mixing
    proceeds component-wise with a logged warning. Per-round matrices
    are memoized per (round-key, K, live-set), so a static all-live
    graph builds its matrix exactly once.

    Pod path: the wire pattern is one ``jax.lax.ppermute`` per neighbor
    permutation (``topology.edge_perms``) — O(degree) cross-pod traffic,
    never the dense-einsum K-way gather; irregular graphs (erdos_renyi)
    fall back to the dense traced mixing."""

    topology: Any = None

    def __post_init__(self):
        from repro.core import topology as topo_mod
        object.__setattr__(self, "topology",
                           topo_mod.get_topology(self.topology))
        object.__setattr__(self, "_mix_cache", {})

    @property
    def name(self):  # noqa: D401 — shadowed by subclass class attrs
        return f"graph[{self.topology.name}]"

    @property
    def static_comm(self):
        # a time-varying graph's live edge count (and so its bill) can
        # change per round even with every participant up
        return not self.topology.time_varying

    def validate(self, K: int) -> "GraphGossip":
        """Connectivity guard — raises ValueError when the topology can
        never reach consensus at this K (CoLearner calls this once at
        construction)."""
        self.topology.validate(K)
        return self

    def _round_key(self, round_index, K):
        topo = self.topology
        return (round_index % topo.period(K)) if topo.time_varying else 0

    def mixing_matrix(self, round_index, K, live=None):
        lkey = (None if live is None
                else tuple(bool(x) for x in np.asarray(live, bool)))
        key = (self._round_key(round_index, K), K, lkey)
        W = self._mix_cache.get(key)
        if W is None:
            W = self.topology.mixing_matrix(round_index, K, live=live)
            W.flags.writeable = False           # cached: nobody may edit
            if len(self._mix_cache) >= 512:     # random churn could grow
                self._mix_cache.clear()         # the live-key space: bound
            self._mix_cache[key] = W
        return W

    def _make_host_aggregate_fn(self, codec):
        # serverless: a participant's OWN model never crosses the wire, so
        # only the received (off-diagonal) leg goes through the codec —
        # quantizing the diagonal too would overstate compression error
        def _mix(stacked, rt, weights):
            W = weights.astype(jnp.float32)
            d = jnp.diagonal(W)
            off = W - jnp.diag(d)

            def one(t, q):
                local = d.reshape((-1,) + (1,) * (t.ndim - 1)) \
                    * t.astype(jnp.float32)
                recv = jnp.einsum("kj,j...->k...", off,
                                  q.astype(jnp.float32))
                return (local + recv).astype(t.dtype)

            return jax.tree.map(one, stacked, rt)

        if getattr(codec, "stateful", False):
            def aggregate_ef(stacked, weights, residual):
                rt, new_res = codec.roundtrip_ef(stacked, residual)
                return _mix(stacked, rt, weights), new_res
            return aggregate_ef

        def aggregate(stacked, weights):
            return _mix(stacked, codec.roundtrip(stacked), weights)
        return aggregate

    def _mesh_perm_setup(self, mesh, axis, dynamic):
        """The sparse pod wire pattern: the graph's edge permutations and,
        per permutation, the (K,) "k receives from src[k]" gather map used
        to pick each leg's weight out of the traced matrix. None — dense
        fallback — when the graph is irregular (no circulant/regular perm
        decomposition), time-varying (per-round wire pattern), or elastic
        membership may route edges outside the baked pattern."""
        if dynamic:
            return None
        topo = self.topology
        if topo.time_varying:
            return None
        K = mesh.shape[axis]
        perms = topo.edge_perms(0, K)
        if not perms:
            return None
        srcs = []
        for perm in perms:
            if len(perm) != K or len({d for _, d in perm}) != K:
                return None         # partial permute: some pod gets zeros
            src = np.zeros(K, np.int64)
            for s, d in perm:
                src[d] = s
            srcs.append(jnp.asarray(src))
        return tuple(tuple(p) for p in perms), tuple(srcs)

    def _make_mesh_aggregate_fn(self, codec, mesh, param_specs, axis,
                                dynamic=False):
        if getattr(codec, "stateful", False):
            # the permute pattern has no residual plumbing; the host path
            # carries the error-feedback state correctly
            return None
        setup = self._mesh_perm_setup(mesh, axis, dynamic)
        if setup is None:
            return None
        perms, srcs = setup
        # the graph's wire pattern is one collective permute per neighbor
        # permutation: each pod codec-roundtrips its own row (the send
        # leg) and receives exactly degree rows (per-leaf ppermutes, f32
        # payloads, combinable by XLA) — O(degree) point-to-point traffic,
        # no all-gather, the local half stays exact, and the per-leg
        # weights are gathered from the traced matrix at the pod's index
        from jax.sharding import PartitionSpec as P

        def aggregate(stacked, weights):
            _check_one_row_per_pod(self, stacked, mesh, axis)

            def local_mix(local, W):
                rt = codec.roundtrip(local)
                k = jax.lax.axis_index(axis)
                Wf = W.astype(jnp.float32)
                w_self = Wf[k, k]
                w_recv = [Wf[k, src[k]] for src in srcs]

                def one(t, q):
                    acc = w_self * t.astype(jnp.float32)
                    qf = q.astype(jnp.float32)
                    for perm, w in zip(perms, w_recv):
                        acc = acc + w * jax.lax.ppermute(qf, axis,
                                                         list(perm))
                    return acc.astype(t.dtype)
                return jax.tree.map(one, local, rt)

            return jax.shard_map(
                local_mix, mesh=mesh, in_specs=(param_specs, P()),
                out_specs=param_specs, check_vma=False)(stacked, weights)
        return aggregate

    def comm_bytes(self, codec, stacked, round_index, live=None):
        # serverless: every directed live edge moves one encoded model
        # across the wire, and each participant pays for its send AND
        # receive legs — amortized per live participant that is
        # 2 * live_edges / n_live encoded models, O(degree), never O(K)
        K = jax.tree.leaves(stacked)[0].shape[0]
        n = K
        if live is not None:
            n = int(np.asarray(live, bool).sum())
            if n <= 1:
                return 0             # a sole survivor has nobody to gossip
        W = self.mixing_matrix(round_index, K, live=live)
        n_edges = (int(np.count_nonzero(W))
                   - int(np.count_nonzero(np.diagonal(W))))
        if n_edges == 0:
            return 0
        return math.ceil(2 * n_edges * codec.wire_bytes(stacked) / n)


@dataclasses.dataclass(frozen=True)
class RingGossip(GraphGossip):
    """One neighbor-exchange step over a fixed ring (decentralized, no
    server): participant k averages its model with its ring predecessor's,
    ``w_k' = (w_k + w_{(k-1) mod K}) / 2``. The mixing matrix is doubly
    stochastic, so repeated rounds contract toward consensus while models
    stay distinct within a round (``shared_model`` tracks slot 0).

    Since the topology subsystem this IS ``GraphGossip(RingTopology())``
    — the named class survives for the ``"ring"`` registry name and to
    pin the legacy behavior: the all-live and routed live matrices, host
    mixing, comm bill, and the static per-leaf ppermute pod fast path
    below are bit-identical to the original hand-rolled aggregator
    (asserted in tests/test_topology.py)."""

    name = "ring"

    def __post_init__(self):
        super().__post_init__()
        from repro.core.topology import RingTopology
        if not isinstance(self.topology, RingTopology):
            raise ValueError(
                "RingGossip is fixed to the ring topology; use "
                f"GraphGossip(topology={self.topology.name!r}) instead")

    def _make_mesh_aggregate_fn(self, codec, mesh, param_specs, axis,
                                dynamic=False):
        if getattr(codec, "stateful", False):
            # the static ppermute has no residual plumbing; the host path
            # carries the error-feedback state correctly
            return None
        if dynamic:
            # the static ppermute bakes the all-live ring; under elastic
            # membership the routed matrix must be honored per round, so
            # fall back to the dense host mixing (correctness over the
            # specialized wire pattern — revisit with a traced permute)
            return None
        # the ring's wire pattern is a collective permute: each pod codec-
        # roundtrips its own row (the send leg) and receives exactly one
        # neighbor row (one ppermute per leaf, f32 payloads, combinable by
        # XLA) — O(model) point-to-point traffic, no all-gather, and the
        # local half stays exact
        K = mesh.shape[axis]
        perm = [(j, (j + 1) % K) for j in range(K)]

        def aggregate(stacked, weights):
            del weights                         # the ring matrix is static
            _check_one_row_per_pod(self, stacked, mesh, axis)

            def local_mix(local):
                rt = codec.roundtrip(local)

                def one(t, q):
                    recv = jax.lax.ppermute(q.astype(jnp.float32), axis,
                                            perm)
                    return (0.5 * t.astype(jnp.float32)
                            + 0.5 * recv).astype(t.dtype)
                return jax.tree.map(one, local, rt)

            return jax.shard_map(
                local_mix, mesh=mesh, in_specs=(param_specs,),
                out_specs=param_specs, check_vma=False)(stacked)
        return aggregate

    def comm_bytes(self, codec, stacked, round_index, live=None):
        # each participant sends its encoded model to one neighbor and
        # receives one encoded model back — both legs on the wire format
        # (kept verbatim from the pre-topology aggregator: the general
        # per-live-edge bill reduces to this for every ring live set)
        if live is not None and int(np.asarray(live, bool).sum()) <= 1:
            return 0                 # a sole survivor has nobody to gossip
        return 2 * codec.wire_bytes(stacked)


@dataclasses.dataclass(frozen=True)
class D2Gossip(GraphGossip):
    """:class:`GraphGossip` plus the D² variance-reduction correction
    (Tang et al., 1803.07068) in round form: plain gossip over non-IID
    shards drags each participant toward its local optimum between
    exchanges, leaving a bias sparse mixing never clears (the Dirichlet
    α=0.1 collapse measured in benchmarks/ablation.py). D² cancels it
    with one extra model-shaped memory per participant and ZERO extra
    wire traffic:

        v_k   = y_k + c_k        post-training model + correction
        x_k'  = Σ_j W[k,j] v_j   the usual gossip mix (v on the wire)
        c_k'  = x_k' - y_k       next round's correction

    With c = x - y_prev this telescopes to x' = W (x + y - y_prev) —
    D²'s update ``W (2 X_t - X_{t-1} - γ (G_t - G_{t-1}))`` generalized
    from one SGD step to a local training round. On identical shards the
    correction stays exactly zero and D² IS plain gossip (pinned in
    tests); on non-IID shards it removes the across-shard drift so
    sparse gossip recovers full-averaging accuracy
    (benchmarks/topology.py).

    The correction is AGGREGATOR round state riding the same engine slot
    as the codec error-feedback residual (``stateful`` /
    ``init_round_state``): threaded traced through round/chunk/finalize
    executables, persisted by ``checkpoint/io.py``, carried unchanged
    through quiet ``DivergenceTrigger`` rounds, frozen for dead slots
    via ``select_live``, and zeroed per-row on ``restart_participant``.
    With an error-feedback codec both memories ride together as
    ``{"corr": ..., "res": ...}``."""

    @property
    def name(self):
        return f"d2[{self.topology.name}]"

    @property
    def stateful(self):
        return True

    def init_round_state(self, codec, stacked):
        corr = jax.tree.map(
            lambda t: jnp.zeros(t.shape, jnp.float32), stacked)
        if getattr(codec, "stateful", False):
            return {"corr": corr, "res": codec.init_state(stacked)}
        return corr

    def _make_host_aggregate_fn(self, codec):
        codec_ef = getattr(codec, "stateful", False)

        def aggregate(stacked, weights, state):
            corr = state["corr"] if codec_ef else state
            # corrected value v = y + c, carried in f32; v replaces the
            # raw model on the wire, and as in plain gossip only the
            # received (off-diagonal) leg goes through the codec
            vf = jax.tree.map(lambda t, c: t.astype(jnp.float32) + c,
                              stacked, corr)
            vw = jax.tree.map(lambda t, v: v.astype(t.dtype), stacked, vf)
            if codec_ef:
                rt, new_res = codec.roundtrip_ef(vw, state["res"])
            else:
                rt = codec.roundtrip(vw)
            W = weights.astype(jnp.float32)
            d = jnp.diagonal(W)
            off = W - jnp.diag(d)

            def one(v, q):
                local = d.reshape((-1,) + (1,) * (v.ndim - 1)) * v
                recv = jnp.einsum("kj,j...->k...", off,
                                  q.astype(jnp.float32))
                return local + recv

            mixed_f = jax.tree.map(one, vf, rt)
            mixed = jax.tree.map(lambda t, m: m.astype(t.dtype),
                                 stacked, mixed_f)
            new_corr = jax.tree.map(
                lambda m, t: m - t.astype(jnp.float32), mixed_f, stacked)
            return mixed, ({"corr": new_corr, "res": new_res}
                           if codec_ef else new_corr)
        return aggregate

    def _make_mesh_aggregate_fn(self, codec, mesh, param_specs, axis,
                                dynamic=False):
        if getattr(codec, "stateful", False):
            # composing the EF residual with the correction on the pod
            # path needs codec state plumbing the permutes don't have;
            # the host path carries both correctly
            return None
        setup = self._mesh_perm_setup(mesh, axis, dynamic)
        if setup is None:
            return None
        perms, srcs = setup
        from jax.sharding import PartitionSpec as P

        def aggregate(stacked, weights, corr):
            _check_one_row_per_pod(self, stacked, mesh, axis)

            def local_mix(local, W, lcorr):
                vf = jax.tree.map(lambda t, c: t.astype(jnp.float32) + c,
                                  local, lcorr)
                vw = jax.tree.map(lambda t, v: v.astype(t.dtype),
                                  local, vf)
                rt = codec.roundtrip(vw)
                k = jax.lax.axis_index(axis)
                Wf = W.astype(jnp.float32)
                w_self = Wf[k, k]
                w_recv = [Wf[k, src[k]] for src in srcs]

                def one(v, q):
                    acc = w_self * v
                    qf = q.astype(jnp.float32)
                    for perm, w in zip(perms, w_recv):
                        acc = acc + w * jax.lax.ppermute(qf, axis,
                                                         list(perm))
                    return acc

                mixed_f = jax.tree.map(one, vf, rt)
                mixed = jax.tree.map(lambda t, m: m.astype(t.dtype),
                                     local, mixed_f)
                new_c = jax.tree.map(
                    lambda m, t: m - t.astype(jnp.float32),
                    mixed_f, local)
                return mixed, new_c

            return jax.shard_map(
                local_mix, mesh=mesh,
                in_specs=(param_specs, P(), param_specs),
                out_specs=(param_specs, param_specs),
                check_vma=False)(stacked, weights, corr)
        return aggregate


# ---------------------------------------------------------------------------
# LRSchedule (Eq. 3 family)
# ---------------------------------------------------------------------------
class LRSchedule(abc.ABC):
    """The per-epoch learning rate policy (the Eq. 3 axis).

    Two surfaces, one semantics:

    * ``lr(round_i, epoch_j, T_i, global_epoch, total_budget)`` — the
      reference rate, host-evaluable with plain scalars (the python engine
      calls it once per epoch). Implementations keep the math compatible
      with traced inputs where the formula allows.
    * ``round_params(round_i)`` — the per-round HOST hook: returns
      ``(kind, p)``, the branch index and scalar pack that
      ``schedule.switch_lr`` (the shared traced body, :attr:`traced_lr`)
      consumes *as traced arguments* inside the fused round executable. A
      schedule whose parameters move per round (a warmup ramping η^i, a
      policy-aware budget) therefore never retriggers compilation, and
      swapping between built-ins reuses the same executable outright.

    Custom subclasses may override :attr:`traced_lr` with their own traced
    function — at the cost of one retrace when swapping to/from it
    (``CoLearner.set_schedule`` rebinds the engine in that case).
    """

    name: str = "schedule"
    #: the traced body the fused engine embeds; shared by every built-in
    #: (one lax.switch over the ``schedule.LR_*`` branch family)
    traced_lr = staticmethod(switch_lr)

    @abc.abstractmethod
    def lr(self, round_i, epoch_j, T_i, global_epoch, total_budget):
        """The epoch's learning rate (reference/host form)."""

    @abc.abstractmethod
    def round_params(self, round_i):
        """Host hook: ``(kind, (p0, p1, p2, p3))`` for ``switch_lr``."""

    def device_round_params(self, round_i):
        """``round_params`` as the traced argument pack the engine takes
        (staged explicitly — it lands on the no_transfer round path)."""
        kind, p = self.round_params(round_i)
        p = tuple(p) + (0.0,) * (N_SCHED_PARAMS - len(p))
        return {"kind": engine_mod.stage(kind, np.int32),
                "p": engine_mod.stage(p, np.float32)}


def traced_body(schedule: LRSchedule):
    """The schedule's traced lr function as a plain callable.

    Unwraps the bound-method descriptor a subclass gets when it overrides
    ``traced_lr`` with a plain function instead of a ``staticmethod`` —
    both so identity comparison (the hot-swap check) works and so the
    engine calls it as ``lr_fn(sched, j, T_i, ge, total)`` without the
    instance sneaking in as the first argument."""
    fn = schedule.traced_lr
    return getattr(fn, "__func__", fn)


@dataclasses.dataclass(frozen=True)
class CLR(LRSchedule):
    """Paper Eq. 3: η_j^i = η^i · r^(j/T_i), restarting at η^i every round
    (the cycle period is the communication round itself)."""

    eta0: float = 0.01
    decay_rate: float = 0.25
    name = "clr"

    def round_eta(self, round_i) -> float:
        """The round's shared base rate η^i (constant for plain CLR)."""
        return self.eta0

    def lr(self, round_i, epoch_j, T_i, global_epoch, total_budget):
        return clr_lr(self.round_eta(round_i), self.decay_rate, epoch_j, T_i)

    def round_params(self, round_i):
        return LR_EXP_ROUND, (self.round_eta(round_i), self.decay_rate)


@dataclasses.dataclass(frozen=True)
class ELR(LRSchedule):
    """The non-cyclical ablation baseline: one exponential anneal over the
    run's whole epoch budget, never restarting. The budget arrives traced
    each round (``SyncPolicy.epochs_budget``), so ILE doublings of T_i
    stretch the anneal correctly instead of stranding it short."""

    eta0: float = 0.01
    decay_rate: float = 0.25
    name = "elr"

    def lr(self, round_i, epoch_j, T_i, global_epoch, total_budget):
        return elr_lr(self.eta0, self.decay_rate, global_epoch,
                      max(total_budget, 1))

    def round_params(self, round_i):
        return LR_EXP_GLOBAL, (self.eta0, self.decay_rate)


@dataclasses.dataclass(frozen=True)
class WarmupCLR(CLR):
    """CLR with η^i linearly ramped over the first ``warmup_rounds``
    communication rounds: η^i = η0 · min(1, (i+1)/warmup_rounds). The ramp
    lives entirely in the per-round host hook — the fused executable sees
    only a different traced η^i each round, so warmup costs zero retraces.
    """

    warmup_rounds: int = 3
    name = "warmup_clr"

    def round_eta(self, round_i) -> float:
        ramp = min(1.0, (round_i + 1) / max(self.warmup_rounds, 1))
        return self.eta0 * ramp


@dataclasses.dataclass(frozen=True)
class CosineCyclical(LRSchedule):
    """SGDR-style cyclical cosine: within round i the rate anneals from
    η^i to ``eta_min`` on a half-cosine over the round's T_i epochs and
    restarts at η^i at the next round boundary (same cycle structure as
    Eq. 3, smoother tail)."""

    eta0: float = 0.01
    eta_min: float = 0.0
    name = "cosine"

    def lr(self, round_i, epoch_j, T_i, global_epoch, total_budget):
        return cosine_lr(self.eta0, self.eta_min, epoch_j, T_i)

    def round_params(self, round_i):
        return LR_COS_ROUND, (self.eta0, 0.0, self.eta_min)


# ---------------------------------------------------------------------------
# SyncPolicy (Eq. 4 generalized)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SyncState:
    """Host-side per-run state owned by a :class:`SyncPolicy`.

    ``history`` logs one ``(round, rel_change, next_T)`` triple per
    completed round; ``skipped`` lists the rounds a divergence-gated
    policy decided not to communicate.
    """

    T: int
    history: tuple = ()
    skipped: tuple = ()


class SyncPolicy(abc.ABC):
    """Who syncs when: next round's T_i + the communicate-at-all decision.

    Absorbs the legacy ``EpochController``: the policy owns a
    :class:`SyncState` (created by ``init_state``, advanced by ``update``
    after every round) and, for divergence-gated policies, the per-round
    ``should_sync`` decision plus the traced threshold ``delta`` the fused
    engine embeds. ``epochs_budget`` is the policy-aware total-epoch
    estimate the ELR anneal divides by — it rides into the executables as
    a traced argument, so the per-round re-estimate (after an ILE
    doubling) is free.
    """

    name: str = "sync"
    #: True => the round executable is built with the divergence gate and
    #: quiet rounds skip the aggregation/wire step (Kamp et al.).
    divergence_gated: bool = False
    #: the traced divergence threshold (gated policies only)
    delta: float = float("inf")

    def init_state(self, T0: int) -> SyncState:
        return SyncState(T=int(T0))

    @abc.abstractmethod
    def update(self, state: SyncState, round_i: int, rel_change: float,
               synced: bool = True, events: tuple = ()) -> SyncState:
        """Post-round host hook: fold the round's Eq. 4 metric (or, on a
        skipped round, the divergence) into the state; returns the state
        whose ``T`` drives round ``round_i + 1``.

        ``events`` (elastic membership): the round's ``(round, slot,
        "join"|"leave")`` membership events. On a churn round the Eq. 4
        metric jumps because the LIVE SET moved, not because training
        converged — policies reading rel_change as a convergence signal
        (ILE's doubling, the trigger's optional ε) should hold their
        decision on such rounds."""

    def should_sync(self, div: float, round_i: int, delta=None) -> bool:
        """Host-side gate decision (python engine). Must implement the
        same decision as :meth:`traced_should_sync`; ``delta`` overrides
        the policy's static threshold when :meth:`round_delta` moved it
        for this round (membership-forced syncs)."""
        return True

    def round_delta(self, events: tuple = ()):
        """The round's divergence threshold as the engines consume it —
        traced into the fused gate, passed to :meth:`should_sync` by the
        python engine. The base is the static ``delta``; gated policies
        may move it per round (e.g. force a sync when the membership
        changed). Host hook: never retraces."""
        return self.delta

    def traced_should_sync(self, div, delta):
        """The gate as the fused engine embeds it on-device: ``div`` is
        the traced divergence, ``delta`` the traced threshold. Override
        together with :meth:`should_sync` (the engines' equivalence
        depends on the two agreeing); swaps between policies with
        different traced gates go through ``CoLearner.set_sync_policy``
        so the engine can rebind."""
        return div > delta

    def epochs_budget(self, T: int, round_i: int, global_epoch: int,
                      max_rounds: int) -> int:
        """Policy-aware total-epoch estimate at the start of ``round_i``:
        epochs already run plus the current T_i extrapolated over the
        remaining rounds. Exact for fixed-T policies (= T0·max_rounds);
        re-estimated after every ILE doubling — which the old static
        ``T0 * max_rounds`` budget ignored, stranding the ELR anneal far
        from its floor."""
        return max(global_epoch + T * max(max_rounds - round_i, 1), 1)


@dataclasses.dataclass(frozen=True)
class ILE(SyncPolicy):
    """Paper Eq. 4: double T_i when the relative change of the averaged
    model falls to <= ε; always communicates."""

    epsilon: float = 0.01
    name = "ile"

    def update(self, state, round_i, rel_change, synced=True, events=()):
        # hold the doubling on membership-change rounds: the Eq. 4 metric
        # moved because the live set did, not because training stabilized
        T = (2 * state.T if rel_change <= self.epsilon and not events
             else state.T)
        return dataclasses.replace(
            state, T=T, history=state.history + ((round_i, rel_change, T),))


@dataclasses.dataclass(frozen=True)
class FLE(SyncPolicy):
    """Fixed local epochs (the FedAvg-style ablation baseline): T_i = T0
    forever; always communicates."""

    name = "fle"

    def update(self, state, round_i, rel_change, synced=True, events=()):
        return dataclasses.replace(
            state,
            history=state.history + ((round_i, rel_change, state.T),))


@dataclasses.dataclass(frozen=True)
class DivergenceTrigger(SyncPolicy):
    """Dynamic model averaging (Kamp et al., 1807.03210): communicate only
    while the local models diverge.

    After the round's local epochs, the engines compute the participants'
    RMS relative drift from the last *synced* shared model
    (``schedule.divergence_traced``). While that stays <= δ the round is
    *quiet*: the averaging/wire step is skipped outright, the participants
    keep their local params and optimizer state, and the round bills ZERO
    comm bytes. Once accumulated drift exceeds δ the next round syncs as
    usual. ``epsilon`` optionally adds the Eq. 4 doubling on synced rounds
    (None = keep T fixed, the equal-budget baseline).
    """

    delta: float = 0.05
    epsilon: float | None = None
    name = "divtrigger"
    divergence_gated = True

    def should_sync(self, div, round_i, delta=None):
        return div > (self.delta if delta is None else delta)

    def round_delta(self, events=()):
        # a membership change forces the sync: a rejoining participant
        # needs the current shared model on the wire, and a leave shifts
        # the live average — the divergence (>= 0) always exceeds -1, so
        # the round communicates regardless of how quiet the locals are.
        # Pure traced data: the forced round reuses the compiled gate.
        if events:
            return -1.0
        return self.delta

    def update(self, state, round_i, rel_change, synced=True, events=()):
        T = state.T
        if (synced and not events and self.epsilon is not None
                and rel_change <= self.epsilon):
            T = 2 * state.T
        skipped = state.skipped if synced else state.skipped + (round_i,)
        return dataclasses.replace(
            state, T=T, skipped=skipped,
            history=state.history + ((round_i, rel_change, T),))


# ---------------------------------------------------------------------------
# RoundEngine
# ---------------------------------------------------------------------------
class RoundEngine(abc.ABC):
    """How a round executes. ``bind(learner)`` compiles the engine's
    artifacts against the learner's loss/opt/aggregate and returns a runner
    with ``run_round(state, epoch_batches_fn) -> state``. Both engines
    apply the identical state transition (``CoLearner._finish_round``)."""

    name: str = "engine"

    @abc.abstractmethod
    def bind(self, learner):
        """Return a runner object for this learner."""


@dataclasses.dataclass(frozen=True)
class PythonEngine(RoundEngine):
    """Reference path: a host loop dispatching one jitted epoch at a time,
    host-side Eq. 3 learning rates and Eq. 4 metric."""

    name = "python"

    def bind(self, learner):
        return _PythonRunner(learner)


@dataclasses.dataclass(frozen=True)
class FusedEngine(RoundEngine):
    """One donated XLA executable per round (``repro.core.engine``): T_i-
    epoch scan with the Eq. 3 schedule traced in-scan, aggregation, and the
    on-device Eq. 4 metric, one host sync. Rounds longer than ``chunk``
    epochs chain traced-offset chunk executables + a finalize executable to
    bound staged-batch memory (still one final sync)."""

    chunk: int = 32
    name = "fused"

    def bind(self, learner):
        return _FusedRunner(learner, self.chunk)


def _sum_counters(parts):
    """Host sums, by name, of the counters of a round's fetched parts."""
    out = {}
    for part in parts:
        for name, value in part.items():
            out[name] = out.get(name, 0) + int(np.sum(value))
    return out


def _live_loss_means(losses, live_np):
    """Per-epoch mean loss over the LIVE participants (all K when
    ``live_np`` is None — the static path, bit-compatible)."""
    if live_np is None:
        return [float(np.asarray(x).mean()) for x in losses]
    w = np.asarray(live_np, np.float32)
    n_live = max(float(w.sum()), 1.0)
    return [float((np.asarray(x) * w).sum() / n_live) for x in losses]


def _gate_accepts_delta(policy) -> bool:
    """Whether the policy's host gate takes the per-round ``delta``
    override. Subclasses written before elastic membership override
    ``should_sync(self, div, round_i)`` without it; they still gate on
    the static threshold, so call them with the legacy signature."""
    try:
        params = inspect.signature(type(policy).should_sync).parameters
    except (TypeError, ValueError):
        return True
    return "delta" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())


class _PythonRunner:
    def __init__(self, learner):
        self.learner = learner
        self._stateful = getattr(learner, "_round_stateful",
                                 getattr(learner.codec, "stateful", False))
        self._jit_agg = jax.jit(engine_mod.as_aggregate_fn(
            learner._aggregate_fn))

    def run_round(self, state, epoch_batches_fn):
        """The reference loop: per epoch, the batches (``colearn.input``),
        one jitted epoch (``colearn.dispatch``) and its loss fetch
        (``colearn.fetch``); then the aggregate, whose Eq. 4 metric and
        divergence are fetched too."""
        learner = self.learner
        policy = learner.sync_policy
        i = state["round"]
        T_i = state["ctrl"].T
        ge0 = state["global_epoch"]
        with spans.span("colearn.dispatch"):
            total = learner.epochs_budget(state)
            sync_ref = learner._sync_ref(state)
            mask = learner.batch_mask
            # elastic membership: the liveness row rides into the jitted
            # epoch as traced data (None on the static path — bit-identical)
            live_np = learner._live_np(state)
            live_row = (None if live_np is None
                        else engine_mod.stage(live_np, np.float32))
        lrs, losses = [], []
        for j in range(T_i):
            with spans.span("colearn.input"):
                batches = epoch_batches_fn(i, j)
            with spans.span("colearn.dispatch"):
                lr = float(learner.schedule.lr(i, j, T_i, ge0 + j, total))
                lrs.append(lr)
                args = (batches, lr)
                if mask is not None:
                    args += (mask,)
                if live_row is not None:
                    args += (live_row,)
                params, opt, l = learner._jit_epoch(
                    state["params"], state["opt"], *args)
                state["params"], state["opt"] = params, opt
            with spans.span("colearn.fetch"):
                losses.append(jax.device_get(l))

        if policy.divergence_gated:
            with spans.span("colearn.fetch"):
                div = sched_mod.divergence(state["params"], sync_ref, live_np)
            if _gate_accepts_delta(policy):
                synced = bool(policy.should_sync(
                    div, i, delta=learner._round_delta(state)))
            else:
                # legacy SyncPolicy subclass: should_sync(div, round_i)
                # predates the membership delta override — honor it as-is
                synced = bool(policy.should_sync(div, i))
        else:
            div, synced = None, True
        if synced:
            # aggregate (Eq. 2 / partial / gossip) over the codec's wire;
            # a stateful codec (error feedback) threads the residual in
            # and out of the same jitted aggregate
            with spans.span("colearn.dispatch"):
                if self._stateful:
                    averaged, new_res = self._jit_agg(
                        state["params"], learner.round_weights(i, state),
                        state["residual"])
                else:
                    averaged = self._jit_agg(state["params"],
                                             learner.round_weights(i, state))
                    new_res = None
                k0 = 0 if live_np is None else int(np.argmax(live_np))
                new_avg = averaging.unstack_participant(averaged, k0)
            with spans.span("colearn.fetch"):
                rel = (float("inf") if state["prev_avg"] is None
                       else relative_change(new_avg, state["prev_avg"]))
            with spans.span("colearn.dispatch"):
                fresh_opt = jax.vmap(learner.opt.init)(averaged)
                if live_row is not None:
                    # dead rows: identity carry — no download, own opt
                    # kept, and (stateful) their residual memory is frozen
                    averaged = engine_mod.select_live(live_row, averaged,
                                                      state["params"])
                    fresh_opt = engine_mod.select_live(live_row, fresh_opt,
                                                       state["opt"])
                    if self._stateful:
                        new_res = engine_mod.select_live(
                            live_row, new_res, state["residual"])
        else:
            # quiet round (Kamp): keep local params AND optimizer state,
            # reference unchanged, nothing crosses the wire (the residual
            # memory is untouched — nothing was quantized)
            averaged, fresh_opt = state["params"], state["opt"]
            new_avg, rel = sync_ref, div
            new_res = state.get("residual")
        return learner._finish_round(state, i, T_i, rel,
                                     _live_loss_means(losses, live_np),
                                     lrs[0], lrs[-1], averaged, fresh_opt,
                                     new_avg, synced=synced,
                                     residual=new_res)


class _FusedRunner:
    def __init__(self, learner, chunk):
        self.learner = learner
        self.chunk = chunk
        self._gated = learner.sync_policy.divergence_gated
        self._masked = learner.batch_mask is not None
        # the traced schedule body / sync gate the executables were
        # compiled against; every built-in LRSchedule shares
        # schedule.switch_lr (and built-in policies the default gate), so
        # CoLearner.set_schedule/set_sync_policy hot-swap without
        # touching the caches
        self._traced_lr = traced_body(learner.schedule)
        self._traced_gate = type(learner.sync_policy).traced_should_sync
        gate_fn = learner.sync_policy.traced_should_sync
        # elastic membership: build the live-row variants once; membership
        # changes then ride in as traced data (zero retraces)
        self._live = learner._churn_active
        # stateful round (codec error feedback and/or aggregator state,
        # e.g. the D² correction): the state rides through the round/
        # finalize executables as traced data right after opt_state (the
        # chunk executables never touch it — it is consumed at finalize)
        self._stateful = getattr(learner, "_round_stateful",
                                 getattr(learner.codec, "stateful", False))
        self._round = engine_mod.make_fused_round(
            learner.loss_fn, learner.opt, lr_fn=self._traced_lr,
            aggregate_fn=learner._aggregate_fn, gated=self._gated,
            gate_fn=gate_fn, masked=self._masked, live=self._live,
            stateful=self._stateful)
        self._epochs = engine_mod.make_fused_epochs(
            learner.loss_fn, learner.opt, lr_fn=self._traced_lr,
            masked=self._masked, live=self._live)
        self._finalize = engine_mod.make_fused_finalize(
            learner.opt, aggregate_fn=learner._aggregate_fn,
            gated=self._gated, gate_fn=gate_fn, live=self._live,
            stateful=self._stateful)

    def run_round(self, state, epoch_batches_fn):
        """One round as one (or, past ``chunk`` epochs, a few chained)
        donated executables — zero host syncs until the final aux fetch."""
        learner = self.learner
        if traced_body(learner.schedule) is not self._traced_lr:
            raise RuntimeError(
                "the learner's schedule carries a different traced_lr than "
                "the compiled round executables; swap schedules with "
                "CoLearner.set_schedule(...) so the engine can rebind")
        if (learner.sync_policy.divergence_gated != self._gated
                or type(learner.sync_policy).traced_should_sync
                is not self._traced_gate):
            raise RuntimeError(
                "the learner's sync policy gating does not match the "
                "compiled round executables; swap policies with "
                "CoLearner.set_sync_policy(...) so the engine can rebind")
        gated = self._gated
        i = state["round"]
        T_i = state["ctrl"].T
        first_round = state["prev_avg"] is None
        single = T_i <= self.chunk
        # batches are staged before anything is donated, so an exception
        # in epoch_batches_fn leaves state untouched
        if single:
            with spans.span("colearn.input"):
                batches = engine_mod.stack_epoch_batches(
                    [epoch_batches_fn(i, j) for j in range(T_i)])
        with spans.span("colearn.dispatch"):
            # per-round host quantities are staged EXPLICITLY (device_put
            # via engine_mod.stage): an implicit transfer here — jnp.int32
            # on a python scalar, numpy riding into the donated call — is
            # exactly what guards.no_transfer() pins the round loop against
            ge0 = engine_mod.stage(state["global_epoch"], np.int32)
            sched = learner.schedule.device_round_params(i)
            total = engine_mod.stage(learner.epochs_budget(state), np.int32)
            agg_w = learner.round_weights(i, state)
            if gated:
                sync_ref = learner._sync_ref(state)
                delta = engine_mod.stage(learner._round_delta(state),
                                         np.float32)
            # the ragged-shard validity mask rides in traced right after the
            # staged batches (absent entirely on the unmasked executables);
            # the liveness row (elastic membership) follows the same way
            mask_args = (learner.batch_mask,) if self._masked else ()
            live_np = learner._live_np(state)
            if self._live:
                live_row = engine_mod.stage(live_np, np.float32)
                mask_args = mask_args + (live_row,)
            # state["params"]/["opt"] are reassigned immediately after every
            # donating call below, so an exception mid-round can never leave
            # state holding deleted buffers.
            if single:
                # stateful codec: the residual rides in right after
                # opt_state and comes back in the aux dict (device-side,
                # like new_avg)
                lead = ((state["params"], state["opt"], state["residual"])
                        if self._stateful
                        else (state["params"], state["opt"]))
                if not gated:
                    # Eq. 4 compares against the entry params inside the
                    # executable, so the previous shared model is dead
                    # here: release it before the dispatch (a whole model
                    # of device memory at published widths)
                    state["prev_avg"] = None
                if gated:
                    out_p, out_o, aux = self._round(
                        *lead, batches, *mask_args,
                        ge0, sched, total, sync_ref, delta, agg_w)
                else:
                    out_p, out_o, aux = self._round(
                        *lead, batches, *mask_args,
                        ge0, sched, total, agg_w)
                state["params"], state["opt"] = out_p, out_o
                if self._stateful:
                    state["residual"] = aux["residual"]
                new_avg = aux["new_avg"]
                # the round's single host sync (scalars/loss curves only —
                # the aggregated model itself stays on device)
                wanted = (aux["losses"], aux["lrs"], aux["rel"],
                          aux["counters"])
                if gated:
                    wanted += (aux["div"], aux["synced"])
            elif not gated:
                # the entry shared model sits in the first LIVE slot (slot
                # 0 on the static path)
                k0 = 0 if live_np is None else int(np.argmax(live_np))
                old_avg = averaging.unstack_participant(state["params"], k0)
        if not single:
            # staging all T_i epochs at once would cost device memory linear
            # in T_i (which ILE doubles); chain chunk executables instead.
            # j0/T_i/ge0/sched/total are traced, so chunks reuse one
            # compiled program across doublings AND schedule swaps.
            lparts, rparts, cparts, j0 = [], [], [], 0
            while j0 < T_i:
                C = min(self.chunk, T_i - j0)
                with spans.span("colearn.input"):
                    batches = engine_mod.stack_epoch_batches(
                        [epoch_batches_fn(i, j) for j in range(j0, j0 + C)])
                with spans.span("colearn.dispatch"):
                    params, opt_st, l, r, c = self._epochs(
                        state["params"], state["opt"], batches, *mask_args,
                        engine_mod.stage(j0, np.int32),
                        engine_mod.stage(T_i, np.int32), ge0, sched, total)
                    state["params"], state["opt"] = params, opt_st
                lparts.append(l)
                rparts.append(r)
                cparts.append(c)
                j0 += C
            with spans.span("colearn.dispatch"):
                # stateful codec: the residual enters finalize right after
                # opt_state (after params on the opt-free static variant)
                # and a new residual is appended to the outputs
                res_in = (state["residual"],) if self._stateful else ()
                if gated:
                    fin_args = ((sync_ref, delta, live_row, agg_w)
                                if self._live else (sync_ref, delta, agg_w))
                    out = self._finalize(state["params"], state["opt"],
                                         *res_in, *fin_args)
                    if self._stateful:
                        (out_p, out_o, rel_t, div_t, sync_t, new_avg,
                         out_res) = out
                        state["residual"] = out_res
                    else:
                        out_p, out_o, rel_t, div_t, sync_t, new_avg = out
                    wanted = (lparts, rparts, rel_t, cparts, div_t, sync_t)
                else:
                    if self._live:
                        # live variant threads opt_state so dead rows keep
                        # it
                        out = self._finalize(
                            state["params"], state["opt"], *res_in, old_avg,
                            live_row, agg_w)
                    else:
                        out = self._finalize(
                            state["params"], *res_in, old_avg, agg_w)
                    if self._stateful:
                        out_p, out_o, rel_t, new_avg, out_res = out
                        state["residual"] = out_res
                    else:
                        out_p, out_o, rel_t, new_avg = out
                    wanted = (lparts, rparts, rel_t, cparts)
                state["params"], state["opt"] = out_p, out_o
        with spans.span("colearn.fetch"):
            got = jax.device_get(wanted)
        if gated:
            losses, lrs, rel_dev, counts, div_dev, sync_dev = got
        else:
            (losses, lrs, rel_dev, counts), div_dev, sync_dev = (got, None,
                                                                 True)
        if single:
            counts = [counts]
        else:
            losses = np.concatenate(losses)
            lrs = np.concatenate(lrs)
        synced = bool(sync_dev)
        if not synced:
            rel = float(div_dev)
        elif first_round:
            rel = float("inf")
        else:
            rel = float(rel_dev)
        return learner._finish_round(state, i, T_i, rel,
                                     _live_loss_means(losses, live_np),
                                     float(lrs[0]), float(lrs[-1]),
                                     out_p, out_o, new_avg, synced=synced,
                                     counters=_sum_counters(counts))


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------
#: name -> factory(**kw) -> WireCodec. Codec factories accept block=/impl=.
CODECS: dict = {}
#: name -> factory(**kw) -> Aggregator.
AGGREGATORS: dict = {}
#: name -> factory(**kw) -> RoundEngine. Engine factories accept chunk=.
ENGINES: dict = {}
#: name -> factory(**kw) -> LRSchedule. Factories accept eta0=/decay_rate=.
SCHEDULES: dict = {}
#: name -> factory(**kw) -> SyncPolicy. Factories accept epsilon=/delta=.
SYNC_POLICIES: dict = {}


def register_codec(name, factory):
    CODECS[name] = factory
    return factory


def register_aggregator(name, factory):
    AGGREGATORS[name] = factory
    return factory


def register_engine(name, factory):
    ENGINES[name] = factory
    return factory


def register_schedule(name, factory):
    SCHEDULES[name] = factory
    return factory


def register_sync_policy(name, factory):
    SYNC_POLICIES[name] = factory
    return factory


def _leafwise_codec(block=DEFAULT_BLOCK, impl=None, bits=8,
                    error_feedback=False):
    """``bits=8`` without error feedback resolves to the LeafwiseInt8
    class so registry/back-compat isinstance pins keep holding."""
    if bits == 8 and not error_feedback:
        return LeafwiseInt8(block=block, impl=impl)
    return LeafwiseIntN(block=block, impl=impl, bits=bits,
                        error_feedback=error_feedback)


def _flat_codec(block=DEFAULT_BLOCK, impl=None, bits=8,
                error_feedback=False):
    if bits == 8 and not error_feedback:
        return FlatFusedInt8(block=block, impl=impl)
    return FlatFusedIntN(block=block, impl=impl, bits=bits,
                         error_feedback=error_feedback)


register_codec("exact", lambda block=DEFAULT_BLOCK, impl=None, bits=8,
               error_feedback=False: ExactF32())
register_codec("none", CODECS["exact"])
register_codec("leafwise", _leafwise_codec)
register_codec("int8", _leafwise_codec)        # legacy CLI alias
register_codec("fused", _flat_codec)
register_codec("flat", _flat_codec)            # alias
register_aggregator("full", FullAverage)
register_aggregator("partial", PartialParticipation)
register_aggregator("ring", RingGossip)
register_aggregator("graph", GraphGossip)
register_aggregator("d2", D2Gossip)
register_engine("python", lambda chunk=32: PythonEngine())
register_engine("fused", FusedEngine)
register_schedule("clr", lambda eta0=0.01, decay_rate=0.25:
                  CLR(eta0, decay_rate))
register_schedule("elr", lambda eta0=0.01, decay_rate=0.25:
                  ELR(eta0, decay_rate))
register_schedule("warmup_clr", lambda eta0=0.01, decay_rate=0.25:
                  WarmupCLR(eta0, decay_rate))
register_schedule("warmup", SCHEDULES["warmup_clr"])       # alias
register_schedule("cosine", lambda eta0=0.01, decay_rate=0.25:
                  CosineCyclical(eta0))
# Sync-policy factories take (epsilon, delta, cfg_epsilon): ``epsilon`` is
# an EXPLICIT caller value, ``cfg_epsilon`` the CoLearnConfig fallback —
# split so divtrigger's optional Eq. 4 doubling engages only when asked
# for (the cfg's ε parameterizes ILE, not the trigger).
register_sync_policy("ile", lambda epsilon=None, delta=None,
                     cfg_epsilon=None:
                     ILE(epsilon=next(e for e in (epsilon, cfg_epsilon,
                                                  0.01) if e is not None)))
register_sync_policy("fle", lambda epsilon=None, delta=None,
                     cfg_epsilon=None: FLE())
register_sync_policy("divtrigger", lambda epsilon=None, delta=None,
                     cfg_epsilon=None:
                     DivergenceTrigger(
                         delta=0.05 if delta is None else delta,
                         epsilon=epsilon))
register_sync_policy("divergence", SYNC_POLICIES["divtrigger"])  # alias


def _resolve(spec, registry, default, proto, kind, **kw):
    if spec is None:
        return default()
    if isinstance(spec, proto):
        return spec
    if isinstance(spec, str):
        try:
            factory = registry[spec]
        except KeyError:
            raise KeyError(f"unknown {kind} {spec!r}; registered: "
                           f"{sorted(registry)}") from None
        return factory(**kw)
    raise TypeError(f"{kind} must be None, a registry name, or a "
                    f"{proto.__name__}; got {spec!r}")


def get_codec(spec=None, *, block=DEFAULT_BLOCK, impl=None, bits=8,
              error_feedback=False) -> WireCodec:
    """None | registry name | WireCodec instance -> WireCodec.

    ``bits`` (8 | 4 | 1) and ``error_feedback`` parameterize the
    quantizing registry names ("leafwise"/"int8", "fused"/"flat"); the
    exact codecs ignore them and instances pass through unchanged."""
    return _resolve(spec, CODECS, ExactF32, WireCodec, "codec",
                    block=block, impl=impl, bits=bits,
                    error_feedback=error_feedback)


def get_aggregator(spec=None, **kw) -> Aggregator:
    """None | registry name | Aggregator instance -> Aggregator.

    Registered names: ``"full"`` (Eq. 2 / example-count-weighted FedAvg),
    ``"partial"`` (FedAvg-style sampled participation), ``"ring"`` (the
    legacy directed-ring gossip — ``GraphGossip`` over ``RingTopology``),
    ``"graph"`` (gossip over any :mod:`repro.core.topology` graph; pass
    ``topology="grid2d" | "hypercube" | "exponential" | "erdos_renyi" |
    "complete"`` or a Topology instance), ``"d2"`` (``GraphGossip`` plus
    the D² variance-reduction correction for non-IID shards)."""
    return _resolve(spec, AGGREGATORS, FullAverage, Aggregator,
                    "aggregator", **kw)


def get_engine(spec=None, *, chunk=32) -> RoundEngine:
    """None | registry name | RoundEngine instance -> RoundEngine."""
    return _resolve(spec, ENGINES, PythonEngine, RoundEngine, "engine",
                    chunk=chunk)


def get_schedule(spec=None, cfg=None, *, eta0=None,
                 decay_rate=None) -> LRSchedule:
    """None | registry name | LRSchedule instance -> LRSchedule.

    ``None`` resolves the legacy ``cfg.schedule`` string ("clr" | "elr");
    registry names take η0/decay from ``cfg`` (or the explicit keywords),
    so ``CoLearner(schedule="clr")`` is the flag surface, object-shaped.
    """
    if spec is None:
        spec = cfg.schedule if cfg is not None else "clr"
    if eta0 is None:
        eta0 = cfg.eta0 if cfg is not None else 0.01
    if decay_rate is None:
        decay_rate = cfg.decay_rate if cfg is not None else 0.25
    return _resolve(spec, SCHEDULES, CLR, LRSchedule, "schedule",
                    eta0=eta0, decay_rate=decay_rate)


def get_sync_policy(spec=None, cfg=None, *, epsilon=None,
                    delta=None) -> SyncPolicy:
    """None | registry name | SyncPolicy instance -> SyncPolicy.

    ``None`` resolves the legacy ``cfg.epochs_rule`` string ("ile" |
    "fle"). "ile" takes ε from the explicit keyword, else from ``cfg``;
    "divtrigger" takes ``delta`` plus an optional EXPLICIT ``epsilon`` to
    enable Eq. 4 doubling on synced rounds (the cfg's ε does NOT leak into
    the trigger — its default is fixed-T, the equal-budget baseline).
    """
    if spec is None:
        spec = cfg.epochs_rule if cfg is not None else "ile"
    return _resolve(spec, SYNC_POLICIES, ILE, SyncPolicy, "sync policy",
                    epsilon=epsilon, delta=delta,
                    cfg_epsilon=cfg.epsilon if cfg is not None else None)
