"""Learning-rate math (Eq. 3 family) and Eq. 4 metrics, as pure functions.

CLR — the paper's "modified cyclical learning rate": within round *i* the
rate decays exponentially from the shared η^i over the round's T_i epochs,
``η_j^i = η^i · r^(j/T_i)`` (r = 1/4), and *restarts* at η^i when the next
round begins — the cycle period is the communication round itself.

ELR — the non-cyclical ablation baseline: the same exponential anneal but
over *global* epochs, never restarting.

The *policy* layer — which formula a run uses, the per-round η^i, and the
Eq. 4 local-epoch control — lives in ``repro.core.api`` as the
:class:`~repro.core.api.LRSchedule` / :class:`~repro.core.api.SyncPolicy`
protocols. This module keeps the formulas themselves plus the shared traced
combinator (:func:`switch_lr`) the fused engine embeds: every built-in
schedule lowers to the same ``lax.switch`` over the branch family below
with its scalars riding in as traced arguments, so swapping schedules or
re-parameterizing one mid-run reuses the compiled round executables.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp


def clr_lr(eta_i: float, decay_rate: float, epoch_j, T_i):
    """Eq. 3: η_j^i = η^i · r^(j/T_i). Any argument may be traced."""
    return eta_i * decay_rate ** (epoch_j / T_i)


def elr_lr(eta_0: float, decay_rate: float, global_epoch, total_epochs):
    """Non-cyclical baseline: one long anneal over the whole run."""
    return eta_0 * decay_rate ** (global_epoch / total_epochs)


def cosine_lr(eta_i: float, eta_min: float, epoch_j, T_i):
    """Cosine anneal within the round, restarting at η^i each round (the
    SGDR-style cyclical variant of Eq. 3)."""
    phase = jnp.cos(jnp.pi * (epoch_j / T_i))
    return eta_min + 0.5 * (eta_i - eta_min) * (1.0 + phase)


# --- the shared traced combinator ------------------------------------------
# Branch indices of ``switch_lr``. Every built-in LRSchedule compiles to the
# SAME jaxpr — a lax.switch over these branches with (kind, p) traced — so
# the fused round executables are reused across schedule swaps and per-round
# re-parameterizations (e.g. a warmup ramping η^i).
LR_EXP_ROUND = 0      # η · r^(j/T_i)            — CLR / WarmupCLR (Eq. 3)
LR_EXP_GLOBAL = 1     # η · r^(ge/total)         — ELR
LR_COS_ROUND = 2      # cosine anneal within the round, per-round restart
N_SCHED_PARAMS = 4    # fixed length of the traced parameter vector ``p``


def switch_lr(sched, epoch_j, T_i, global_epoch, total_epochs):
    """The traced per-epoch learning rate shared by all built-in schedules.

    ``sched`` is ``{"kind": int32, "p": float32[N_SCHED_PARAMS]}`` — the
    device form of ``LRSchedule.round_params`` — with
    ``p = [eta_i, decay_rate, aux0, aux1]``. All other arguments may be
    traced; nothing here retriggers compilation.
    """
    p = sched["p"]

    def exp_round():
        return clr_lr(p[0], p[1], epoch_j, T_i)

    def exp_global():
        return elr_lr(p[0], p[1], global_epoch,
                      jnp.maximum(total_epochs, 1))

    def cos_round():
        return cosine_lr(p[0], p[2], epoch_j, T_i)

    return jax.lax.switch(sched["kind"],
                          (exp_round, exp_global, cos_round))


def round_lr(colearn_cfg, round_i: int, epoch_j, T_i: int, global_epoch,
             total_epochs: int):
    """Legacy flag-surface helper: the per-epoch rate under the config's
    ``schedule`` string ("clr" | "elr"). Kept for the pre-PR-4 callers and
    tests; new code goes through ``api.get_schedule(...).lr(...)``."""
    if colearn_cfg.schedule == "clr":
        return clr_lr(colearn_cfg.eta0, colearn_cfg.decay_rate, epoch_j, T_i)
    return elr_lr(colearn_cfg.eta0, colearn_cfg.decay_rate, global_epoch,
                  max(total_epochs, 1))


# ---------------------------------------------------------------------------
# Eq. 4 controller (legacy shim — see api.SyncPolicy for the protocol form)
# ---------------------------------------------------------------------------
@dataclass
class EpochController:
    """Server-side state deciding T_i each round (Eq. 4).

    Legacy flag-driven controller; the composable replacement is
    ``api.ILE`` / ``api.FLE`` / ``api.DivergenceTrigger`` operating on an
    ``api.SyncState``. Kept for direct users of the old surface.
    """
    T: int
    epsilon: float
    rule: str = "ile"                 # ile | fle
    history: tuple = ()               # (round, rel_change, T) triples

    def update(self, rel_change: float) -> "EpochController":
        """Called after round i computed w̄^i; returns controller for i+1.

        The stored round index is the number of completed updates — one
        ``update`` per round, starting at round 0.
        """
        T = self.T
        if self.rule == "ile" and rel_change <= self.epsilon:
            T = 2 * self.T
        entry = (len(self.history), rel_change, T)
        return dataclasses.replace(self, T=T, history=self.history + (entry,))


#: the named scope of the Eq. 4 metric and the gated divergence in a
#: compiled round (``repro.core.engine``)
EQ4_REL = "eq4_rel"


def relative_change_traced(new_avg, old_avg):
    """Eq. 4 metric as a traced scalar — usable inside jit/scan.

    ‖w̄^i − w̄^{i−1}‖ / ‖w̄^{i−1}‖ over the flattened parameter pytree,
    accumulated on-device in float32. The fused round engine embeds this
    right after Eq. 2 averaging so the whole round has one host sync.
    """
    with jax.named_scope(EQ4_REL):
        num = jnp.zeros((), jnp.float32)
        den = jnp.zeros((), jnp.float32)
        for a, b in zip(jax.tree.leaves(new_avg), jax.tree.leaves(old_avg)):
            d = a.astype(jnp.float32) - b.astype(jnp.float32)
            num += jnp.sum(d * d)
            den += jnp.sum(b.astype(jnp.float32) ** 2)
        return jnp.sqrt(num) / jnp.maximum(jnp.sqrt(den), 1e-12)


@jax.jit
def _relative_change_jit(new_avg, old_avg):
    return relative_change_traced(new_avg, old_avg)


def relative_change(new_avg, old_avg) -> float:
    """Host-facing Eq. 4 metric: one jitted reduction, one device_get.

    (The previous implementation pulled two scalars to the host per
    parameter leaf — 2·n_leaves blocking transfers per round.)
    """
    return float(jax.device_get(_relative_change_jit(new_avg, old_avg)))


def divergence_traced(stacked, ref, live=None):
    """Kamp-style (1807.03210) local-model divergence, traced.

    RMS over the K participants of the drift from the last *synced* shared
    model, relative to that model's norm:
    ``sqrt(mean_k ‖w_k − w_ref‖²) / ‖w_ref‖``. A
    :class:`~repro.core.api.DivergenceTrigger` sync policy communicates
    only while this exceeds its δ — quiet rounds skip the wire entirely.

    ``live`` (elastic membership): a traced 0/1 float (K,) liveness row;
    the RMS then runs over the LIVE participants only — a dead slot's
    stale parameters neither inflate nor dilute the drift signal. ``None``
    keeps the exact static-K reduction (bit-compatible).
    """
    with jax.named_scope(EQ4_REL):
        num = jnp.zeros((), jnp.float32)
        den = jnp.zeros((), jnp.float32)
        K = jax.tree.leaves(stacked)[0].shape[0]
        if live is None:
            for t, r in zip(jax.tree.leaves(stacked), jax.tree.leaves(ref)):
                d = t.astype(jnp.float32) - r.astype(jnp.float32)[None]
                num += jnp.sum(d * d)
                den += jnp.sum(r.astype(jnp.float32) ** 2)
            return jnp.sqrt(num / K) / jnp.maximum(jnp.sqrt(den), 1e-12)

        w = live.astype(jnp.float32)
        for t, r in zip(jax.tree.leaves(stacked), jax.tree.leaves(ref)):
            d = t.astype(jnp.float32) - r.astype(jnp.float32)[None]
            per_k = jnp.sum(d * d, axis=tuple(range(1, d.ndim)))
            num += jnp.sum(w * per_k)
            den += jnp.sum(r.astype(jnp.float32) ** 2)
        n_live = jnp.maximum(jnp.sum(w), 1.0)
        return jnp.sqrt(num / n_live) / jnp.maximum(jnp.sqrt(den), 1e-12)


@jax.jit
def _divergence_jit(stacked, ref):
    return divergence_traced(stacked, ref)


@jax.jit
def _divergence_live_jit(stacked, ref, live):
    return divergence_traced(stacked, ref, live)


def divergence(stacked, ref, live=None) -> float:
    """Host-facing divergence: one jitted reduction, one device_get."""
    if live is None:
        return float(jax.device_get(_divergence_jit(stacked, ref)))
    return float(jax.device_get(_divergence_live_jit(
        stacked, ref, jnp.asarray(live, jnp.float32))))
