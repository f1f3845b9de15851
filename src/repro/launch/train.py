"""End-to-end co-learning training driver (CPU-scale, real training).

Trains a reduced-config model of any assigned architecture with the paper's
Algorithm 1 on synthetic-LM shards split across K participants, logging
per-round losses, the Eq.4 controller decisions, and communication volume.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      --participants 5 --rounds 6 --t0 2 --steps-per-epoch 8
  ... --widths published --n-layers 2   # published widths, depth cut to 2

The model is the arch's reduced smoke config by default; ``--widths
published`` loads its published config instead, and ``--n-layers`` keeps
only the first N layers of either (``configs.cut_depth``).

Round strategy (see repro.core.api): --codec picks the wire format of the
uploads (exact f32 | leafwise int8 | fused flat-buffer), --aggregator picks
who averages what (full Eq. 2 | FedAvg-style partial participation with
--partial-m sampled uploads per round | ring gossip | graph gossip over an
arbitrary --topology sparse graph | d2 graph gossip with the D² non-IID
correction), --engine picks the round executor, --lr-schedule the Eq. 3
family member (clr | elr |
warmup_clr | cosine; defaults to the legacy --schedule flag), and
--sync-policy the Eq. 4 rule (ile | fle | divtrigger with --trigger-delta;
defaults to the legacy --epochs-rule flag). --compress remains the legacy
spelling of --codec, resolved through the api.CODECS registry aliases.

Data scenario (see repro.data.partition): --partition picks the split
(iid | dirichlet label-skew with --dirichlet-alpha | sizes quantity skew
with --sizes), --weighted-avg switches Eq. 2 to FedAvg's example-count
weighting, and ragged shards automatically thread their validity mask into
the engines (no shard is clamped, no example silently dropped;
--drop-remainder restores the paper's exactly-equal split explicitly).

Elastic membership (see repro.core.membership): --churn injects per-round
participant failures — scripted (--churn-events "crash:2:1,rejoin:4:1")
or random i.i.d. (--churn-p per-round failure probability, deterministic
in --churn-seed) — and --k-max reserves standby slots beyond
--participants that start dead and can warm-join mid-run. Dead slots are
identity carries inside the same compiled round executables; the
aggregators renormalize over the live set.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import save_round_state
from repro.configs import cut_depth, get_config, get_smoke_config
from repro.configs.base import CoLearnConfig
from repro.core import api
from repro.core.colearn import CoLearner
from repro.data import partition as part_mod
from repro.data.pipeline import ParticipantData
from repro.data.synthetic import lm_examples
from repro.launch import compile_cache
from repro.models import transformer as tr


def build_data(cfg, K, batch_size, seq_len, n_examples, seed=0,
               partition="iid", dirichlet_alpha=0.5, sizes=None,
               drop_remainder=False, k_max=None):
    """Shard the synthetic LM corpus under the chosen data scenario.

    partition="iid": the paper's random split (remainder round-robin, or
    dropped with ``drop_remainder``). "dirichlet": label-skew non-IID over
    a coarse sequence label (the first target token bucketed into 10
    classes — a deterministic proxy for topic skew on synthetic text).
    "sizes": quantity skew with the given counts/fractions.
    """
    x, y = lm_examples(seed, n_examples, seq_len, cfg.vocab_size)
    idx = part_mod.scenario_indices(
        len(x), K, seed, scenario=partition, labels=y[:, 0] % 10,
        dirichlet_alpha=dirichlet_alpha, sizes=sizes, min_size=batch_size,
        drop_remainder=drop_remainder)
    shards = part_mod.shard_by_indices([x, y], idx)
    return ParticipantData(shards, batch_size, seed, k_max=k_max)


# Module-level so every eval batch reuses one compiled executable; a
# jax.jit created inside the loop is a fresh wrapper (and retrace) per batch.
_eval_loss_step = jax.jit(tr.loss_fn, static_argnums=(1,))


def eval_loss(params, cfg, x, y, batch):
    tot, n = 0.0, 0
    for i in range(0, len(x) - batch + 1, batch):
        b = {"tokens": jnp.asarray(x[i:i + batch]),
             "labels": jnp.asarray(y[i:i + batch])}
        loss, _ = _eval_loss_step(params, cfg, b)
        tot += float(loss) * batch
        n += batch
    return tot / max(n, 1)


def main(argv=None, on_round_end=None):
    """Run the CLI. ``on_round_end(learner, state, seconds)``, when given,
    fires after each round with the round's wall time (eval excluded) —
    how an in-process caller such as ``chip_smoke.py`` reads the run."""
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--widths", default="smoke",
                    choices=["smoke", "published"],
                    help="smoke = the arch's reduced CPU-test config; "
                         "published = its published widths")
    ap.add_argument("--n-layers", type=int, default=0,
                    help="keep only the first N layers (0 = the config's "
                         "own depth)")
    ap.add_argument("--participants", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--t0", type=int, default=2)
    ap.add_argument("--eta0", type=float, default=0.01)
    ap.add_argument("--epsilon", type=float, default=0.05)
    ap.add_argument("--schedule", default="clr", choices=["clr", "elr"],
                    help="legacy spelling of --lr-schedule")
    ap.add_argument("--epochs-rule", default="ile", choices=["ile", "fle"],
                    help="legacy spelling of --sync-policy")
    ap.add_argument("--lr-schedule", default="",
                    choices=["", "clr", "elr", "warmup_clr", "cosine"],
                    help="Eq. 3 family member (api.SCHEDULES): clr = paper "
                         "per-round restart; elr = global anneal; "
                         "warmup_clr = clr with eta ramped over the first "
                         "rounds; cosine = per-round cosine anneal")
    ap.add_argument("--sync-policy", default="",
                    choices=["", "ile", "fle", "divtrigger"],
                    help="Eq. 4 rule (api.SYNC_POLICIES): ile = paper "
                         "doubling; fle = fixed T; divtrigger = Kamp-style "
                         "divergence-triggered sync (quiet rounds skip the "
                         "wire and bill 0 bytes)")
    ap.add_argument("--trigger-delta", type=float, default=0.05,
                    help="divergence threshold for --sync-policy divtrigger")
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--n-examples", type=int, default=1280)
    ap.add_argument("--steps-per-epoch", type=int, default=0,
                    help="truncate each epoch to this many batches (0=full)")
    ap.add_argument("--partition", default="iid",
                    choices=["iid", "dirichlet", "sizes"],
                    help="data scenario: iid = the paper's random equal "
                         "split (remainder round-robin); dirichlet = "
                         "label-skew non-IID (--dirichlet-alpha); sizes = "
                         "quantity skew (--sizes)")
    ap.add_argument("--dirichlet-alpha", type=float, default=0.5,
                    help="Dirichlet concentration for --partition "
                         "dirichlet (small = more skew)")
    ap.add_argument("--sizes", default="",
                    help="comma-separated per-participant counts or "
                         "fractions for --partition sizes, e.g. "
                         "'0.5,0.2,0.1,0.1,0.1'")
    ap.add_argument("--drop-remainder", action="store_true",
                    help="paper-faithful exactly-equal IID shards (the "
                         "n %% K remainder is EXPLICITLY discarded; "
                         "default distributes it round-robin)")
    ap.add_argument("--weighted-avg", action="store_true",
                    help="example-count-weighted Eq. 2 (FedAvg weighting; "
                         "uniform = paper-faithful default). full "
                         "aggregator only")
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "fused"],
                    help="legacy alias for --codec: int8 = leafwise, "
                         "fused = flat-buffer")
    ap.add_argument("--codec", default="",
                    choices=["", "exact", "leafwise", "fused"],
                    help="wire codec for uploads: exact f32 | leafwise "
                         "int8 quantize-roundtrip | fused flat-buffer "
                         "(one quant->avg->dequant kernel pass)")
    ap.add_argument("--codec-bits", type=int, default=8, choices=[8, 4, 1],
                    help="wire payload bit width for the quantizing codecs "
                         "(leafwise/fused): 8 = int8, 4 = packed int4, "
                         "1 = sign + per-block scale")
    ap.add_argument("--error-feedback", action="store_true",
                    help="error-feedback residual memory for the quantizing "
                         "codecs: each participant quantizes x + e and "
                         "carries e' = (x + e) - dequant to the next round "
                         "(recommended at 4/1 bits)")
    ap.add_argument("--aggregator", default="full",
                    choices=["full", "partial", "ring", "graph", "d2"],
                    help="aggregation strategy: full = paper Eq. 2; "
                         "partial = FedAvg-style sampled uploads "
                         "(--partial-m per round); ring = one neighbor-"
                         "exchange gossip step over a fixed ring; graph = "
                         "gossip over --topology; d2 = graph gossip + the "
                         "D2 variance-reduction correction (non-IID "
                         "shards)")
    ap.add_argument("--partial-m", type=int, default=2,
                    help="participants sampled per round (partial only)")
    ap.add_argument("--topology", default="ring",
                    choices=["ring", "grid2d", "torus", "hypercube",
                             "exponential", "erdos_renyi", "complete"],
                    help="gossip graph for --aggregator graph|d2 "
                         "(repro.core.topology registry): ring cycle | "
                         "2-D torus | hypercube (K a power of two) | "
                         "time-varying one-peer exponential | Erdos-Renyi "
                         "G(K, --er-p) | complete")
    ap.add_argument("--er-p", type=float, default=0.5,
                    help="edge probability for --topology erdos_renyi")
    ap.add_argument("--er-seed", type=int, default=0,
                    help="graph draw seed for --topology erdos_renyi")
    ap.add_argument("--engine", default="fused", choices=["fused", "python"],
                    help="round engine: fused = one executable per round "
                         "(repro.core.engine); python = reference loop")
    ap.add_argument("--churn", default="none",
                    choices=["none", "scripted", "random"],
                    help="elastic-membership fault injection "
                         "(repro.core.membership): scripted = deterministic "
                         "crash/rejoin trace (--churn-events); random = "
                         "i.i.d. per-round failures (--churn-p, "
                         "deterministic in --churn-seed)")
    ap.add_argument("--churn-events", default="",
                    help="scripted trace: comma-separated kind:round:slot "
                         "triples, e.g. 'crash:2:1,rejoin:4:1'")
    ap.add_argument("--churn-p", type=float, default=0.2,
                    help="per-round failure probability for --churn random")
    ap.add_argument("--churn-seed", type=int, default=0,
                    help="churn RNG seed (--churn random; the trace is a "
                         "pure function of (seed, round))")
    ap.add_argument("--k-max", type=int, default=0,
                    help="total participant slots (>= --participants); the "
                         "extra slots start dead as standby capacity a "
                         "rejoin can warm-join. 0 = no standby slots")
    ap.add_argument("--naive-membership", action="store_true",
                    help="ablation: keep the static mixing matrix under "
                         "churn (dead rows pollute the mean) — the "
                         "baseline benchmarks/churn.py measures against")
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.codec and args.compress != "none":
        ap.error("pass --codec or the legacy --compress, not both")
    codec_spec = args.codec or args.compress
    if (args.codec_bits != 8 or args.error_feedback) and codec_spec in (
            "", "none", "exact"):
        ap.error("--codec-bits/--error-feedback require a quantizing codec "
                 "(--codec leafwise|fused or --compress int8|fused)")
    # the legacy --compress spellings ("none"/"int8"/"fused") are registry
    # aliases in api.CODECS, so both flags resolve through the one registry
    codec = api.get_codec(codec_spec, bits=args.codec_bits,
                          error_feedback=args.error_feedback)

    # partial participation samples from the participant pool — a sample
    # size beyond the pool is a config bug, caught here instead of rounds
    # later inside the mixing-matrix draw
    if args.aggregator == "partial" and args.partial_m > args.participants:
        ap.error(f"--partial-m {args.partial_m} exceeds --participants "
                 f"{args.participants}")
    if args.aggregator == "partial" and args.partial_m < 1:
        ap.error("--partial-m must be >= 1")

    # topology sub-flags only make sense for the graph-structured gossips
    if args.topology != "ring" and args.aggregator not in ("graph", "d2"):
        ap.error("--topology requires --aggregator graph|d2")
    if ((args.er_p != 0.5 or args.er_seed)
            and args.topology != "erdos_renyi"):
        ap.error("--er-p/--er-seed require --topology erdos_renyi")

    # elastic-membership flag surface: churn sub-flags must match --churn
    if args.churn_events and args.churn != "scripted":
        ap.error("--churn-events requires --churn scripted")
    if (args.churn_p != 0.2 or args.churn_seed) and args.churn != "random":
        ap.error("--churn-p/--churn-seed require --churn random")
    if args.k_max and args.churn == "none":
        ap.error("--k-max requires --churn scripted|random (standby slots "
                 "only join through membership events)")
    if args.k_max and args.k_max < args.participants:
        ap.error(f"--k-max {args.k_max} smaller than --participants "
                 f"{args.participants}")
    k_max = args.k_max or args.participants
    churn = None
    if args.churn != "none":
        from repro.core import membership as membership_mod
        init_live = args.participants if k_max > args.participants else None
        if args.churn == "random":
            churn = membership_mod.RandomChurn(
                p_fail=args.churn_p, seed=args.churn_seed,
                initial_live=init_live)
        else:
            events = []
            for spec in filter(None, args.churn_events.split(",")):
                try:
                    kind, r, k = spec.split(":")
                    events.append((kind, int(r), int(k)))
                except ValueError:
                    ap.error(f"bad --churn-events entry {spec!r} "
                             "(want kind:round:slot)")
            try:
                churn = membership_mod.ScriptedChurn(
                    events=tuple(events), initial_live=init_live)
            except ValueError as e:
                ap.error(str(e))
    if args.naive_membership and churn is None:
        ap.error("--naive-membership requires --churn")

    cfg = (get_config(args.arch) if args.widths == "published"
           else get_smoke_config(args.arch))
    if args.n_layers:
        try:
            cfg = cut_depth(cfg, args.n_layers)
        except ValueError as e:
            ap.error(str(e))
    K = k_max
    ccfg = CoLearnConfig(
        n_participants=K, T0=args.t0, eta0=args.eta0, epsilon=args.epsilon,
        schedule=args.schedule, epochs_rule=args.epochs_rule,
        max_rounds=args.rounds)

    # scenario flags must match --partition — silently ignoring them would
    # let a user believe they benchmarked a skew they never ran
    if args.sizes and args.partition != "sizes":
        ap.error("--sizes requires --partition sizes")
    if not args.sizes and args.partition == "sizes":
        ap.error("--partition sizes requires --sizes")
    if args.dirichlet_alpha != 0.5 and args.partition != "dirichlet":
        ap.error("--dirichlet-alpha requires --partition dirichlet")
    if args.drop_remainder and args.partition != "iid":
        ap.error("--drop-remainder only applies to --partition iid")
    sizes = ([float(s) for s in args.sizes.split(",")] if args.sizes
             else None)
    data = build_data(cfg, args.participants, args.batch_size, args.seq_len,
                      args.n_examples, args.seed, partition=args.partition,
                      dirichlet_alpha=args.dirichlet_alpha, sizes=sizes,
                      drop_remainder=args.drop_remainder,
                      k_max=k_max if args.k_max else None)
    ex, ey = lm_examples(args.seed + 99, 256, args.seq_len, cfg.vocab_size)

    def loss_fn(params, batch):
        x, y = batch
        return tr.loss_fn(params, cfg, {"tokens": x, "labels": y})

    if args.weighted_avg and args.aggregator != "full":
        ap.error("--weighted-avg only applies to --aggregator full")
    if args.aggregator == "partial":
        aggregator = api.PartialParticipation(m=args.partial_m,
                                              seed=args.seed)
    elif args.weighted_avg:
        aggregator = api.FullAverage(weights=data.sizes)
    elif args.aggregator in ("graph", "d2"):
        from repro.core import topology as topo_mod
        if args.topology == "erdos_renyi":
            topo = topo_mod.ErdosRenyiTopology(p=args.er_p,
                                               seed=args.er_seed)
        else:
            topo = topo_mod.get_topology(args.topology)
        cls = api.D2Gossip if args.aggregator == "d2" else api.GraphGossip
        aggregator = cls(topology=topo)
    else:
        aggregator = api.get_aggregator(args.aggregator)
    # ragged shards (unequal batch counts): thread the validity mask into
    # the engines so every shard trains on exactly its own batches
    batch_mask = data.batch_mask if data.ragged else None
    if batch_mask is not None and args.steps_per_epoch:
        batch_mask = batch_mask[:, :args.steps_per_epoch]
    # --lr-schedule/--sync-policy override the legacy string flags; either
    # way the objects come out of the same registries
    schedule = api.get_schedule(args.lr_schedule or None, ccfg)
    sync_policy = api.get_sync_policy(args.sync_policy or None, ccfg,
                                      delta=args.trigger_delta)
    learner = CoLearner(ccfg, loss_fn, optimizer_name=args.optimizer,
                        codec=codec, aggregator=aggregator,
                        round_engine=args.engine, schedule=schedule,
                        sync_policy=sync_policy, shard_sizes=data.sizes,
                        batch_mask=batch_mask, churn=churn,
                        liveness_aware=not args.naive_membership)
    params = tr.init_params(jax.random.PRNGKey(args.seed), cfg, jnp.float32)
    n_params = tr.count_params(params)
    state = learner.init(params)
    # the stacked copies are all the learner needs; at published widths the
    # unstacked tree is a whole model's worth of device memory
    del params
    shard_s = (f" shards={list(data.sizes)}" if args.partition != "iid"
               or data.ragged else "")
    if churn is not None:
        shard_s += (f" churn={learner.churn.name}"
                    + (f" k_max={k_max}" if args.k_max else "")
                    + (" naive" if args.naive_membership else ""))
    print(f"co-learning {cfg.name}: K={K} params="
          f"{n_params:,} rounds={args.rounds} T0={args.t0} "
          f"{learner.schedule.name}+{learner.sync_policy.name} "
          f"engine={args.engine} codec={learner.codec.name} "
          f"aggregator={learner.aggregator.name} "
          f"partition={args.partition}{shard_s}", flush=True)

    def epoch_batches(round_i, epoch_j):
        bx, by = data.epoch_batches(round_i, epoch_j)
        if args.steps_per_epoch:
            bx, by = bx[:, :args.steps_per_epoch], by[:, :args.steps_per_epoch]
        return (jnp.asarray(bx), jnp.asarray(by))

    for _ in range(args.rounds):
        t0 = time.perf_counter()
        # the round ends in its host fetch of the losses, so the clock
        # covers the device work
        state = learner.run_round(state, epoch_batches)
        round_s = time.perf_counter() - t0
        if on_round_end is not None:
            on_round_end(learner, state, round_s)
        log = state["log"][-1]
        t_eval = time.perf_counter()
        ev = eval_loss(learner.shared_model(state), cfg, ex, ey,
                       args.batch_size)
        eval_s = time.perf_counter() - t_eval
        sync_s = "" if log.synced else " SKIP(sync)"
        if churn is not None:
            sync_s += f" live={log.live}/{K}"
        # the round's host phases (ms), from its spans
        host_s = " ".join(f"{name.split('.')[-1]}={ms:.1f}"
                          for name, ms in (log.host_ms or {}).items()
                          if name != "colearn.round")
        print(f"round {log.round}: T={log.T} lr {log.lr_first:.4f}->"
              f"{log.lr_last:.4f} rel_dw={log.rel_change:.4f} "
              f"local_loss={np.mean(log.local_losses):.4f} eval={ev:.4f} "
              f"comm={log.comm_bytes/2**20:.1f}MiB next_T={state['ctrl'].T}"
              f"{sync_s} (round {round_s:.1f}s, eval {eval_s:.1f}s; "
              f"host ms {host_s})", flush=True)

    if args.checkpoint:
        save_round_state(args.checkpoint, state)
        print(f"saved {args.checkpoint}.params.npz")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
