"""Find a cell's files by name: ``BENCHMARK.json`` at the checkout root,
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/limits/<workload>.json``, ``bench/metrics/<metric>.py`` and, by
the configuration's ``model_type``, ``bench/arch/<model_type>.py``.

A cell, a configuration, a traffic mix, a per-layer metric or an
architecture is added by adding its file (and, but for an architecture,
its entry in ``BENCHMARK.json``); nothing here names one of them.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: every option a traffic file may set, with its default. A key outside
#: this table is refused, so a misspelt option never runs silently.
TRAFFIC_DEFAULTS = {
    "placement": "vmap",          # vmap: K participants on one chip; pod:
                                  # one participant per chip
    "participants": 2,            # K
    "batch_size": 4,              # B, examples a local step
    "seq_len": 512,               # S, tokens an example
    "steps_per_epoch": 2,         # local steps an epoch
    "t0": 1,                      # T0, local epochs of the first round
    "chunk": 32,                  # fused engine: epochs staged at once
    "n_examples": 512,            # corpus rows, split over the K shards
    "codec": "exact",             # exact | leafwise | fused
    "codec_bits": 8,              # 8 | 4 | 1
    "error_feedback": False,
    "aggregator": "full",         # full | partial | ring | graph | d2
    "weighted": False,            # full: FedAvg example-count weights
    "partial_m": 2,               # partial: uploads sampled a round
    "topology": "ring",           # graph | d2: the gossip graph
    "sync_policy": "fle",         # fle | ile | divtrigger
    "epsilon": 0.05,              # ile: Eq. 4 threshold
    "trigger_delta": 0.05,        # divtrigger: divergence threshold
    "schedule": "clr",            # clr | elr | warmup_clr | cosine
    "eta0": 0.01,                 # Eq. 3 base rate
    "decay_rate": 0.25,           # Eq. 3 r
    "max_rounds": 1000,           # ELR budget: rounds the run plans for
    "partition": "iid",           # iid | dirichlet | sizes
    "dirichlet_alpha": 0.5,
    "sizes": None,                # sizes: per-participant counts/fractions
    "churn": "none",              # none | scripted | random
    "churn_p": 0.2,
    "churn_seed": 0,
    "churn_events": [],           # scripted: [kind, round, slot] triples
}


class SpecError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found
    or does not hold what it must."""


def _load_json(path, what):
    if not os.path.isfile(path):
        raise SpecError(f"no {what} file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def benchmark(root=ROOT):
    return _load_json(os.path.join(root, "BENCHMARK.json"), "BENCHMARK.json")


def workload(name, root=ROOT):
    """The ``workloads`` entry named ``name``."""
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"BENCHMARK.json has no workload {name!r}")


def config(name):
    return _load_json(os.path.join(BENCH_DIR, "configs", name + ".json"),
                      "configuration")


def traffic(name):
    """The traffic mix ``name`` with every option filled in."""
    raw = _load_json(os.path.join(BENCH_DIR, "traffic", name + ".json"),
                     "traffic")
    return complete_traffic(raw)


def complete_traffic(raw):
    unknown = sorted(set(raw) - set(TRAFFIC_DEFAULTS) - {"why"})
    if unknown:
        raise SpecError(f"unknown traffic options {unknown}")
    out = dict(TRAFFIC_DEFAULTS)
    out.update({k: v for k, v in raw.items() if k != "why"})
    if out["placement"] not in ("vmap", "pod"):
        raise SpecError(f"placement must be vmap or pod, not "
                        f"{out['placement']!r}")
    return out


def limits(workload_name):
    """The limit of each number the correctness check compares."""
    return _load_json(os.path.join(BENCH_DIR, "limits",
                                   workload_name + ".json"), "limits")


def per_layer_metrics(workload_name, root=ROOT):
    """The ``per_layer`` entries this cell reports: those that list it,
    and those without a ``workloads`` key whose end-to-end metric the cell
    reports."""
    bm = benchmark(root)
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    mine = {n for n, m in e2e.items()
            if workload_name in m.get("workloads", [workload_name])}
    out = []
    for m in bm["per_layer"]:
        if "workloads" in m:
            if workload_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in mine:
            out.append(m)
    return out


def end_to_end_metrics(workload_name, root=ROOT):
    return [m for m in benchmark(root)["end_to_end"]
            if workload_name in m.get("workloads", [workload_name])]


def metric_reader(name):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no reader bench/metrics/{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def arch(cfg):
    """The module ``bench/arch/<cfg["model_type"]>.py`` of a configuration.

    It gives ``program_config(cfg)``, the program's model configuration,
    raising on what the program cannot run; ``param_shapes(cfg)``, the
    parameter tree as ``(shape, init)`` leaves, ``init`` a fan-in (a
    truncated normal over its square root), ``"ones"`` or ``"zeros"``;
    ``loss(params, tokens, labels, cfg, prec)``, the plain reference's
    mean next-token cross-entropy of one batch, with any auxiliary term
    the program's loss carries for the architecture; and
    ``train_flops_per_token(cfg, seq_len)``, the model FLOPs of one
    training token over the weights a token passes through."""
    kind = cfg.get("model_type")
    if not isinstance(kind, str) or not kind.isidentifier():
        raise SpecError(f"configuration {cfg.get('name')!r} names no "
                        f"model_type a module can have (got {kind!r})")
    if not os.path.isfile(os.path.join(BENCH_DIR, "arch", kind + ".py")):
        raise SpecError(f"no architecture module bench/arch/{kind}.py")
    return importlib.import_module("bench.arch." + kind)
