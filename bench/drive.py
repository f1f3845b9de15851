"""One cell on the chip: set-up, the measured window, and the readings of
the first three rounds that the correctness check compares.

Two placements, as the traffic mix says:

* ``vmap``: K participants on one chip, driven through
  ``CoLearner.run_round`` with the fused engine, built as
  ``launch/train.py`` builds it; the data goes through the program's
  ``ParticipantData``, and the benchmark's ``epoch_batches`` callback only
  cuts each epoch to ``steps_per_epoch`` batches.
* ``pod``: one participant a chip over the ``pod`` mesh axis, driven
  through one compiled ``launch.steps.make_fused_round_step(mesh=...)``
  whose stacked parameters are donated; each round's batches are placed
  one participant a chip, and the loop ends each round in the fetch of
  its losses.

Set-up makes the weights from the seed in one jitted call, builds the
round, and runs ``SETUP_ROUNDS`` rounds through the same call the window
uses (the first compiles). It keeps every batch the program was fed in
them, and after rounds 1 and 3 it reads, on the device, each leaf's
change from the start for every participant row and the largest
difference between rows. The window then runs rounds
until ``seconds`` have passed; each round is timed from the call into it
to the host fetch that ends it, inside the host span ``bench.round``
(``bench.input`` around staging the batches, ``bench.fetch`` around the
fetch where the benchmark makes it).
"""
from __future__ import annotations

import gc
import os
import sys
import time

import numpy as np

from bench import gen, model, spec
from bench.spec import ROOT

sys.path.insert(0, os.path.join(ROOT, "src"))

#: the rounds whose state the correctness check reads (1-based)
READ_AFTER = (1, 3)
#: rounds run in set-up: the reference follows them all
SETUP_ROUNDS = max(READ_AFTER)


def corpus(cfg, traffic, seed):
    """The seed's ``(tokens, labels)`` rows."""
    return gen.lm_examples(seed, traffic["n_examples"], traffic["seq_len"],
                           cfg["vocab_size"])


def make_data(x, y, traffic, seed):
    """The corpus split by the program's partitioner and batched by its
    ``ParticipantData``."""
    from repro.data import partition as part_mod
    from repro.data.pipeline import ParticipantData
    idx = part_mod.scenario_indices(
        len(x), traffic["participants"], seed,
        scenario=traffic["partition"], labels=y[:, 0] % 10,
        dirichlet_alpha=traffic["dirichlet_alpha"], sizes=traffic["sizes"],
        min_size=traffic["batch_size"])
    shards = part_mod.shard_by_indices([x, y], idx)
    return ParticipantData(shards, traffic["batch_size"], seed)


def strategies(traffic, data, seed):
    """The program's round strategy objects for a traffic mix."""
    from repro.configs.base import CoLearnConfig
    from repro.core import api
    K = traffic["participants"]
    ccfg = CoLearnConfig(n_participants=K, T0=traffic["t0"],
                         eta0=traffic["eta0"],
                         decay_rate=traffic["decay_rate"],
                         epsilon=traffic["epsilon"],
                         max_rounds=traffic["max_rounds"])
    codec = api.get_codec(traffic["codec"], bits=traffic["codec_bits"],
                          error_feedback=traffic["error_feedback"])
    agg = traffic["aggregator"]
    if agg == "partial":
        aggregator = api.PartialParticipation(m=traffic["partial_m"],
                                              seed=seed)
    elif agg == "full" and traffic["weighted"]:
        aggregator = api.FullAverage(weights=data.sizes)
    elif agg in ("graph", "d2"):
        from repro.core import topology as topo_mod
        cls = api.D2Gossip if agg == "d2" else api.GraphGossip
        aggregator = cls(topology=topo_mod.get_topology(traffic["topology"]))
    else:
        aggregator = api.get_aggregator(agg)
    schedule = api.get_schedule(traffic["schedule"], ccfg)
    sync = api.get_sync_policy(traffic["sync_policy"], ccfg,
                               delta=traffic["trigger_delta"])
    churn = None
    if traffic["churn"] != "none":
        from repro.core import membership as mem
        if traffic["churn"] == "random":
            churn = mem.RandomChurn(p_fail=traffic["churn_p"],
                                    seed=traffic["churn_seed"])
        else:
            churn = mem.ScriptedChurn(events=tuple(
                tuple(e) for e in traffic["churn_events"]))
    return ccfg, codec, aggregator, schedule, sync, churn


def _epoch(data, traffic, round_i, epoch_j):
    bx, by = data.epoch_batches(round_i, epoch_j)
    n = traffic["steps_per_epoch"]
    return np.ascontiguousarray(bx[:, :n]), np.ascontiguousarray(by[:, :n])


class Cell:
    """The program built for one cell, with its state."""

    def __init__(self, cfg, traffic, seed, devices):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.pcfg = spec.arch(cfg).program_config(cfg)
        x, y = corpus(cfg, traffic, seed)
        self.data = make_data(x, y, traffic, seed)
        #: the benchmark's own batches, which the reference trains on
        self.bench_feed = gen.Feed(x, y, traffic, seed)
        self.K = traffic["participants"]
        self.round_i = 0
        self.fed = {}           # set-up round -> [(tokens, labels)] as fed
        self.losses = []        # per set-up round: mean participant loss
        #: training tokens of one local epoch over all participants
        self.tokens_per_epoch = (self.K * traffic["steps_per_epoch"]
                                 * traffic["batch_size"] * traffic["seq_len"])
        self.last_tokens = 0
        self._check_tree()

    def _check_tree(self):
        import jax
        from repro.models import transformer as tr
        want = jax.tree.structure(jax.eval_shape(
            lambda k: tr.init_params(k, self.pcfg),
            jax.ShapeDtypeStruct((2,), np.uint32)))
        got = jax.tree.structure(model.param_shapes(self.cfg),
                                 is_leaf=model.is_spec)
        if got != want:
            raise ValueError(f"benchmark parameter tree {got} is not the "
                             f"program's {want}")

    def record(self, epoch):
        """Keep an epoch the program was fed in a set-up round, in the
        order the program asked for them."""
        if self.round_i < SETUP_ROUNDS:
            self.fed.setdefault(self.round_i, []).append(epoch)

    def readings(self):
        """Per leaf, each participant row's change from the start, and the
        largest difference between rows."""
        return (model.change_norms(self.cfg, self.seed, self.params(),
                                   stacked=True),
                model.row_spread(self.params()))


class VmapCell(Cell):
    def __init__(self, cfg, traffic, seed, devices):
        super().__init__(cfg, traffic, seed, devices)
        from repro.core import api
        from repro.core.colearn import CoLearner
        from repro.models import transformer as tr
        ccfg, codec, aggregator, schedule, sync, churn = strategies(
            traffic, self.data, seed)
        pcfg = self.pcfg

        def loss_fn(params, batch):
            x, y = batch
            return tr.loss_fn(params, pcfg, {"tokens": x, "labels": y})

        batch_mask = self.data.batch_mask if self.data.ragged else None
        if batch_mask is not None:
            batch_mask = batch_mask[:, :traffic["steps_per_epoch"]]
        self.learner = CoLearner(
            ccfg, loss_fn, codec=codec, aggregator=aggregator,
            round_engine=api.FusedEngine(chunk=traffic["chunk"]),
            schedule=schedule, sync_policy=sync,
            shard_sizes=self.data.sizes, batch_mask=batch_mask, churn=churn)
        params = model.init_params(cfg, seed)
        self.state = self.learner.init(params)
        del params

    def params(self):
        return self.state["params"]

    def _epoch_batches(self, round_i, epoch_j):
        import jax
        with jax.profiler.TraceAnnotation("bench.input"):
            epoch = _epoch(self.data, self.traffic, round_i, epoch_j)
            self.record(epoch)
            return epoch

    def run_round(self):
        self.state = self.learner.run_round(self.state, self._epoch_batches)
        self.round_i += 1
        log = self.state["log"][-1]
        self.last_tokens = self.tokens_per_epoch * log.T
        return float(np.mean(log.local_losses))

    def executables(self):
        r = self.learner._runner
        return [r._round, r._epochs, r._finalize]

    def free(self):
        del self.state, self.learner


class PodCell(Cell):
    def __init__(self, cfg, traffic, seed, devices):
        super().__init__(cfg, traffic, seed, devices)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import engine as engine_mod
        from repro.launch import steps
        from repro.launch.mesh import auto_mesh
        if traffic["sync_policy"] != "fle" or traffic["churn"] != "none":
            raise ValueError("the pod placement runs fixed local epochs "
                             "(sync_policy fle) and no churn")
        if len(devices) != self.K:
            raise ValueError(f"pod placement: K={self.K} participants on "
                             f"{len(devices)} chips")
        ccfg, codec, aggregator, schedule, sync, _ = strategies(
            traffic, self.data, seed)
        self.mesh = auto_mesh((self.K,), ("pod",), devices)
        self.aggregator, self.schedule, self.sync = aggregator, schedule, sync
        self.ccfg = ccfg
        specs = jax.tree.map(lambda s: P("pod", *([None] * len(s[0]))),
                             model.param_shapes(cfg), is_leaf=model.is_spec)
        self.shard = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                  specs)
        self.batch_sharding = NamedSharding(self.mesh, P(None, "pod"))
        self.stacked = model.init_params(cfg, seed, stack=self.K,
                                         out_shardings=self.shard)
        self.opt = ()
        self.stateful = (getattr(codec, "stateful", False)
                         or getattr(aggregator, "stateful", False))
        self.residual = None
        if self.stateful:
            self.residual = jax.device_put(
                aggregator.init_round_state(codec, self.stacked),
                NamedSharding(self.mesh, P("pod")))
        fn = steps.make_fused_round_step(
            self.pcfg, ccfg, mesh=self.mesh, param_specs=specs,
            codec=codec, aggregator=aggregator, schedule=schedule,
            expose_schedule_args=True, codec_bits=traffic["codec_bits"],
            error_feedback=traffic["error_feedback"])
        self.fn = jax.jit(fn, donate_argnums=((0, 2) if self.stateful
                                              else (0,)),
                          out_shardings=(self.shard, None, None))
        self.stage = engine_mod.stage
        self.ge = 0

    def params(self):
        return self.stacked

    def run_round(self):
        import jax
        i, T = self.round_i, self.traffic["t0"]
        with jax.set_mesh(self.mesh):
            with jax.profiler.TraceAnnotation("bench.input"):
                epochs = [_epoch(self.data, self.traffic, i, j)
                          for j in range(T)]
                for e in epochs:
                    self.record(e)
                batches = jax.device_put(
                    {"tokens": np.stack([e[0] for e in epochs]),
                     "labels": np.stack([e[1] for e in epochs])},
                    self.batch_sharding)
                ge0 = self.stage(self.ge, np.int32)
                sched = self.schedule.device_round_params(i)
                total = self.stage(self.sync.epochs_budget(
                    T, i, self.ge, self.ccfg.max_rounds), np.int32)
                tail = ()
                if self.aggregator.uses_weights:
                    tail = (self.stage(self.aggregator.mixing_matrix(
                        i, self.K), np.float32),)
            lead = ((self.stacked, self.opt, self.residual) if self.stateful
                    else (self.stacked, self.opt))
            self.stacked, self.opt, aux = self.fn(*lead, batches, ge0, sched,
                                                  total, *tail)
            if self.stateful:
                self.residual = aux["residual"]
            with jax.profiler.TraceAnnotation("bench.fetch"):
                losses = jax.device_get(aux["losses"])
        self.ge += T
        self.round_i += 1
        self.last_tokens = self.tokens_per_epoch * T
        return float(np.mean(losses))

    def executables(self):
        return [self.fn]

    def free(self):
        del self.stacked, self.opt, self.residual, self.fn


def build(cfg, traffic, seed, devices):
    cls = PodCell if traffic["placement"] == "pod" else VmapCell
    return cls(cfg, traffic, seed, devices)


def setup_rounds(cell):
    """The set-up rounds through the window's own call, with the readings
    after rounds 1 and 3."""
    reads = {}
    for r in range(1, SETUP_ROUNDS + 1):
        cell.losses.append(cell.run_round())
        if r in READ_AFTER:
            reads[r] = cell.readings()
    return reads


def compile_count(cell):
    from repro.analysis import guards
    return sum(guards.compile_count(f) for f in cell.executables())


def window(cell, seconds):
    """Rounds until ``seconds`` have passed: their (start, end) host
    times, training tokens and losses."""
    import jax
    times, tokens, losses = [], [], []
    t_start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.round"):
            losses.append(cell.run_round())
        t1 = time.perf_counter()
        times.append((t0, t1))
        tokens.append(cell.last_tokens)
        if t1 - t_start >= seconds:
            return times, tokens, losses


def peak_bytes(devices):
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def free(cell):
    cell.free()
    gc.collect()
