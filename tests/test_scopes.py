"""The round's names inside the program: the named scopes its compiled
programs carry in their op names (``local_step``, ``eq2_wire``,
``eq4_rel``), and the host spans a round opens (``analysis.spans``)."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import spans, tracelint
from repro.configs.base import CoLearnConfig
from repro.core import api, membership
from repro.core.colearn import CoLearner

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

#: the phases of one fused round, in the order the round opens them
FUSED_ORDER = ["colearn.round", "colearn.membership", "colearn.input",
               "colearn.dispatch", "colearn.fetch", "colearn.finish",
               "colearn.publish"]


def tiny_loss(params, batch):
    x, y = batch
    loss = jnp.mean((x @ params["w"] + params["b"] - y) ** 2)
    return loss, {"loss": loss}


def tiny_params():
    return {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 1)),
            "b": jnp.zeros((1,))}


def tiny_batches(i, j, K=2):
    x = jax.random.normal(jax.random.PRNGKey(i * 7 + j), (K, 3, 8, 4))
    return np.asarray(x), np.asarray(x @ jnp.arange(1.0, 5.0)[:, None])


def learner(codec, chunk=32, T0=1, **kw):
    cfg = CoLearnConfig(n_participants=2, T0=T0, eta0=0.05, max_rounds=4,
                        epochs_rule="fle")
    return CoLearner(cfg, tiny_loss, codec=codec, aggregator="full",
                     round_engine=api.FusedEngine(chunk=chunk), **kw)


def op_names(hlo):
    """(opcode, op_name) of every instruction of a compiled HLO text."""
    rx = re.compile(r" = .*? ([a-z][a-z0-9-]*)\(.*op_name=\"([^\"]*)\"")
    return [m.groups() for m in map(rx.search, hlo.splitlines()) if m]


def scopes_of(hlo):
    """opcode -> the set of scope paths of its instructions."""
    out = {}
    for opcode, name in op_names(hlo):
        out.setdefault(opcode, set()).add(name)
    return out


def has_scope(names, scope):
    return any(scope in n.split("/") for n in names)


def compiled_round(lrn, T):
    """The fused round executable of ``lrn``, compiled for T epochs (a
    loop of one or two trips is unrolled on the CPU)."""
    state = lrn.init(tiny_params())
    batches = tuple(np.stack([b] * T) for b in tiny_batches(0, 0))
    r = lrn._runner
    return r._round.lower(
        state["params"], state["opt"], batches, jnp.int32(0),
        lrn.schedule.device_round_params(0), jnp.int32(8),
        None).compile().as_text()


@pytest.mark.parametrize("codec", ["exact", "fused"])
def test_one_chip_round_carries_scopes(codec):
    hlo = compiled_round(learner(codec), T=4)
    by_op = scopes_of(hlo)
    # the scope wraps the epoch scan, so the loop op itself carries it
    assert any(re.fullmatch(r"jit\(\w+\)/local_step/while", n)
               for n in by_op["while"])
    names = [n for _, n in op_names(hlo)]
    assert has_scope(names, "eq2_wire") and has_scope(names, "eq4_rel")
    # the Eq. 4 reduction is its own: no op is in two scopes
    assert not any(has_scope([n], "eq2_wire") and has_scope([n], "eq4_rel")
                   for n in names)


def test_chunked_round_carries_scopes():
    lrn = learner("fused", chunk=4, T0=8)
    state = lrn.init(tiny_params())
    r = lrn._runner
    sched = lrn.schedule.device_round_params(0)
    batches = tuple(np.stack([b] * 4) for b in tiny_batches(0, 0))
    i32 = jnp.int32(0)
    epochs = r._epochs.lower(state["params"], state["opt"], batches, i32,
                             jnp.int32(8), i32, sched,
                             jnp.int32(8)).compile().as_text()
    assert any(re.fullmatch(r"jit\(\w+\)/local_step/while", n)
               for n in scopes_of(epochs)["while"])
    old_avg = jax.tree.map(lambda t: t[0], state["params"])
    fin = r._finalize.lower(state["params"], old_avg,
                            None).compile().as_text()
    names = [n for _, n in op_names(fin)]
    assert has_scope(names, "eq2_wire") and has_scope(names, "eq4_rel")
    assert not has_scope(names, "local_step")


POD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.configs.base import CoLearnConfig
from repro.core import averaging, engine
from repro.launch import steps
from repro.launch.mesh import make_sim_mesh
from repro.models import transformer as tr
from repro.sharding import specs as sp

K = 2
mesh = make_sim_mesh((K, 2), ("pod", "data"))
cfg = get_smoke_config("internlm2-1.8b")
ccfg = CoLearnConfig(n_participants=K, T0=1, eta0=0.01, max_rounds=1)
params = jax.eval_shape(lambda k: tr.init_params(k, cfg, jnp.float32),
                        jax.ShapeDtypeStruct((2,), jnp.uint32))
stacked = jax.tree.map(lambda t: jax.ShapeDtypeStruct((K, *t.shape),
                                                      t.dtype), params)
pspecs = sp.param_specs(stacked, cfg, mesh, participant=True)
# four epochs: a loop of one or two trips is unrolled on the CPU
batch = {"tokens": jax.ShapeDtypeStruct((4, K, 1, 4, 16), jnp.int32),
         "labels": jax.ShapeDtypeStruct((4, K, 1, 4, 16), jnp.int32)}
out = {}
for codec in ("exact", "fused"):
    fn = steps.make_fused_round_step(
        cfg, ccfg, mesh=mesh, param_specs=pspecs, codec=codec,
        compress_impl="ref")
    with jax.set_mesh(mesh):
        hlo = jax.jit(fn).lower(stacked, (), batch,
                                jax.ShapeDtypeStruct((), jnp.int32)
                                ).compile().as_text()
    rx = re.compile(r" = .*? ([a-z][a-z0-9-]*)\(.*op_name=\"([^\"]*)\"")
    out[codec] = [m.groups() for m in map(rx.search, hlo.splitlines()) if m]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def pod_round_ops():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", POD_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("codec", ["exact", "fused"])
def test_pod_round_step_carries_scopes(pod_round_ops, codec):
    """``make_fused_round_step(mesh=...)`` on a ``pod`` mesh of CPU
    devices: the same scopes, and the pod's psum inside ``eq2_wire``."""
    ops = pod_round_ops[codec]
    whiles = [n for op, n in ops if op == "while"]
    assert any(re.search(r"/local_step/while$", n) for n in whiles)
    # the Eq. 2 psum over ``pod`` (the local step's and Eq. 4's own
    # reductions over the mesh are other all-reduces)
    psums = [n for op, n in ops
             if op.startswith("all-reduce") and "shard_map" in n.split("/")]
    assert psums and all(has_scope([n], "eq2_wire") for n in psums)
    assert has_scope([n for _, n in ops], "eq4_rel")


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs entries and
    exits in order."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        Recorder.log.append(("enter", self.name))

    def __exit__(self, *exc):
        Recorder.log.append(("exit", self.name))


def traced_round(monkeypatch, lrn, rounds=2):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    state = lrn.init(tiny_params())
    published = []
    for _ in range(rounds):
        Recorder.log = []
        state = lrn.run_round(state, tiny_batches,
                              on_round_end=lambda l, s: published.append(1))
    assert len(published) == rounds
    return state, Recorder.log


def check_nesting(log):
    """Every span closes in order, and all lie inside ``colearn.round``."""
    assert log[0] == ("enter", "colearn.round")
    assert log[-1] == ("exit", "colearn.round")
    stack = []
    for kind, name in log:
        if kind == "enter":
            assert name == "colearn.round" or stack == ["colearn.round"]
            stack.append(name)
        else:
            assert stack.pop() == name
    assert stack == []


def check_record(log_entry):
    rec = log_entry.host_ms
    assert rec is not None and all(ms >= 0 for ms in rec.values())
    assert sum(ms for n, ms in rec.items() if n != "colearn.round") <= \
        rec["colearn.round"]
    last = spans.recent()[-1]
    assert last.host_ms is rec and last.end >= last.start
    return rec


def test_fused_round_opens_its_spans_in_order(monkeypatch):
    # an active (scripted) churn schedule, so the membership phase runs
    lrn = learner("fused", churn=membership.ScriptedChurn(
        events=(("crash", 9, 1),)))
    state, log = traced_round(monkeypatch, lrn)
    check_nesting(log)
    assert [n for k, n in log if k == "enter"] == FUSED_ORDER
    rec = check_record(state["log"][-1])
    assert set(rec) == set(FUSED_ORDER)


def test_python_round_opens_its_spans(monkeypatch):
    cfg = CoLearnConfig(n_participants=2, T0=2, eta0=0.05, max_rounds=4,
                        epochs_rule="fle")
    lrn = CoLearner(cfg, tiny_loss, round_engine="python")
    state, log = traced_round(monkeypatch, lrn)
    check_nesting(log)
    entered = [n for k, n in log if k == "enter"]
    # per epoch: batches, one jitted epoch, its loss fetch
    assert entered.count("colearn.input") == 2
    assert entered.index("colearn.finish") > entered.index("colearn.fetch")
    rec = check_record(state["log"][-1])
    assert set(rec) == {"colearn.round", "colearn.input", "colearn.dispatch",
                        "colearn.fetch", "colearn.finish", "colearn.publish"}


def test_chunked_round_adds_up_its_phases(monkeypatch):
    """Three epochs in chunks of one: the input and dispatch spans open
    once a chunk and add up in the record."""
    lrn = learner("exact", chunk=1, T0=3)
    state, log = traced_round(monkeypatch, lrn, rounds=1)
    check_nesting(log)
    entered = [n for k, n in log if k == "enter"]
    assert entered.count("colearn.input") == 3
    assert entered.count("colearn.fetch") == 1
    check_record(state["log"][-1])


def test_span_outside_a_round_records_nothing():
    n = len(spans.recent())
    with spans.span("colearn.fetch"):
        pass
    assert len(spans.recent()) == n


def test_tracelint_cli_is_clean_on_the_program():
    """``python -m repro.analysis.tracelint src/repro`` against the empty
    committed baseline, project rules on."""
    assert tracelint.main([os.path.join(SRC, "repro")]) == 0
