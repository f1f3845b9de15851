"""Cells, traffic mixes and metrics are found by name: a new traffic file,
a new metric reader and a new entry in BENCHMARK.json run with no edit to
the harness, and every round option the program has is a traffic key."""
import json
import os
import shutil
import uuid

import jax
import numpy as np
import pytest

from bench import drive, gen, model, spec
from bench.tests import tiny


@pytest.fixture
def new_files():
    """A traffic file and a metric reader under new names, and a copy of
    BENCHMARK.json with a cell and a metric that use them."""
    name = "t" + uuid.uuid4().hex[:8]
    traffic = os.path.join(spec.BENCH_DIR, "traffic", name + ".json")
    reader = os.path.join(spec.BENCH_DIR, "metrics", name + ".py")
    with open(traffic, "w") as f:
        json.dump({"codec": "exact", "steps_per_epoch": 3,
                   "sync_policy": "ile", "epsilon": 0.2}, f)
    with open(reader, "w") as f:
        f.write("def read(ctx):\n    return 2.0 * ctx['x']\n")
    root = os.path.join(spec.ROOT, "bench_out", name)
    os.makedirs(root)
    bm = spec.benchmark()
    bm["workloads"].append({"name": name, "config": "internlm2-1.8b-d2",
                            "traffic": name, "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": name, "unit": "x", "better": "lower",
                            "source": "program_counter", "layer": "test",
                            "moves": "tokens_per_s", "workloads": [name]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    yield name, root
    os.remove(traffic)
    os.remove(reader)
    shutil.rmtree(root)


def test_new_traffic_and_metric_by_name(new_files):
    name, root = new_files
    w = spec.workload(name, root)
    t = spec.traffic(w["traffic"])
    assert t["codec"] == "exact" and t["steps_per_epoch"] == 3
    assert t["sync_policy"] == "ile" and t["epsilon"] == 0.2
    assert t["seq_len"] == spec.TRAFFIC_DEFAULTS["seq_len"]
    names = [m["name"] for m in spec.per_layer_metrics(name, root)]
    assert name in names
    # metrics without a workloads list follow their end-to-end metric
    assert {"device_idle_share", "step_mfu", "mfu"} <= set(names)
    assert "wire_kernel_ms" not in names
    assert spec.metric_reader(name)({"x": 1.5}) == 3.0


def test_every_cell_finds_its_files():
    bm = spec.benchmark()
    for w in bm["workloads"]:
        spec.config(w["config"])
        spec.traffic(w["traffic"])
        assert set(spec.limits(w["name"])) >= {"loss_gap", "update_gap",
                                                "change_gap", "row_spread"}
        for m in spec.per_layer_metrics(w["name"]):
            spec.metric_reader(m["name"])
    for m in bm["per_layer"]:
        spec.metric_reader(m["name"])
    for c in bm["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
    # every configuration resolves its architecture module, whose tree is
    # the one the program's decoder initialises, leaf for leaf
    from repro.models import transformer as tr
    configs = sorted(f[:-5] for f in os.listdir(
        os.path.join(spec.BENCH_DIR, "configs")) if f.endswith(".json"))
    assert {c["name"] for c in bm["configs"]} <= set(configs)
    for name in configs:
        cfg = spec.config(name)
        pcfg = spec.arch(cfg).program_config(cfg)
        want = jax.eval_shape(lambda k: tr.init_params(k, pcfg),
                              jax.ShapeDtypeStruct((2,), np.uint32))
        got = model.param_shapes(cfg)
        assert jax.tree.structure(got, is_leaf=model.is_spec) == \
            jax.tree.structure(want), name
        assert [s for s, _ in jax.tree.leaves(got, is_leaf=model.is_spec)] \
            == [w.shape for w in jax.tree.leaves(want)], name


def test_unknown_traffic_option_is_refused():
    with pytest.raises(spec.SpecError):
        spec.complete_traffic({"codex": "fused"})


@pytest.mark.parametrize("options", [
    {"codec": "fused", "codec_bits": 4, "error_feedback": True},
    {"codec": "leafwise"},
    {"aggregator": "partial", "partial_m": 1},
    {"aggregator": "full", "weighted": True, "partition": "sizes",
     "sizes": [0.75, 0.25]},
    {"aggregator": "graph", "topology": "complete"},
    {"aggregator": "d2", "topology": "complete"},
    {"aggregator": "ring"},
    {"sync_policy": "ile", "epsilon": 0.01},
    {"sync_policy": "divtrigger", "trigger_delta": 0.1},
    {"schedule": "elr"}, {"schedule": "warmup_clr"}, {"schedule": "cosine"},
    {"partition": "dirichlet", "dirichlet_alpha": 0.1},
    {"churn": "random", "churn_p": 0.2, "churn_seed": 3},
    {"churn": "scripted", "churn_events": [["crash", 1, 1],
                                           ["rejoin", 2, 1]]},
    {"t0": 4, "steps_per_epoch": 8, "chunk": 2},
    {"placement": "pod", "participants": 4},
])
def test_every_round_option_builds(options):
    t = tiny.traffic(**options)
    data = drive.make_data(*drive.corpus(tiny.config(), t, 5), t, seed=5)
    ccfg, codec, aggregator, schedule, sync, churn = drive.strategies(
        t, data, seed=5)
    assert ccfg.n_participants == t["participants"]
    assert (churn is None) == (t["churn"] == "none")


@pytest.mark.parametrize("options", [
    {}, {"participants": 3, "n_examples": 40},
    {"partition": "sizes", "sizes": [0.75, 0.25]},
    {"partition": "sizes", "sizes": [20, 12]},
    {"partition": "dirichlet", "dirichlet_alpha": 0.1},
    {"partition": "dirichlet", "dirichlet_alpha": 5.0, "participants": 3},
])
def test_own_feed_is_the_programs_batches(options):
    """The benchmark's own split and batching give, for a sound program,
    the batches its pipeline serves, for every partition."""
    t = tiny.traffic(**options)
    x, y = drive.corpus(tiny.config(), t, 2 ** 33 + 7)
    data = drive.make_data(x, y, t, 2 ** 33 + 7)
    feed = gen.Feed(x, y, t, 2 ** 33 + 7)
    assert feed.sizes == data.sizes
    for r, j in ((0, 0), (1, 0), (2, 3)):
        for a, b in zip(drive._epoch(data, t, r, j), feed(r, j)):
            np.testing.assert_array_equal(a, b)
