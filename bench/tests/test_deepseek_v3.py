"""Moonlight-16B-A3B's architecture module (``bench/arch/deepseek_v3.py``)
and the readers of the latent attention and the expert layer: the tree
is the program's, the counts agree with counts made by hand, the program
agrees with the reference leaf by leaf of the gradient at several
attention chunks, and the readers read a trace recorded on the CPU and
the program's counters."""
import functools
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# drive puts the program on the path
from bench import drive, flops, model, scopes, spec, trace  # noqa: F401
from bench.arch import deepseek_v3 as ds

CELL_CONFIG = "moonlight-16b-a3b-d5"
TINY = {
    "hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_attention_heads": 4, "moe_intermediate_size": 24,
    "n_routed_experts": 4, "ep_size": 4, "num_experts_per_tok": 3,
    "vocab_size": 128, "num_hidden_layers": 3,
}
#: attention chunks of the tiny sequence: the program's chunked scan and
#: the reference's query blocks both cut it in four or more
SEQ, CHUNK = 64, 16
ROUNDS = 2


def tiny_cfg(**kw):
    return dict(spec.config(CELL_CONFIG), **TINY, **kw)


def test_tree_is_the_programs_at_the_cells_sizes():
    from repro.models import transformer as tr
    cfg = spec.config(CELL_CONFIG)
    pcfg = ds.program_config(cfg)
    assert (pcfg.n_experts, pcfg.experts_held, pcfg.top_k) == (64, 8, 6)
    assert pcfg.q_lora_rank == 0 and pcfg.router_score == "sigmoid"
    assert pcfg.segments == ((("mla:dense",), 1),) + ((("mla:moe",), 1),) * 4
    want = jax.eval_shape(lambda k: tr.init_params(k, pcfg, jnp.float32),
                          jax.ShapeDtypeStruct((2,), np.uint32))
    got = model.param_shapes(cfg)
    assert jax.tree.structure(got, is_leaf=model.is_spec) == \
        jax.tree.structure(want)
    assert [s for s, _ in jax.tree.leaves(got, is_leaf=model.is_spec)] == \
        [w.shape for w in jax.tree.leaves(want)]
    moe = got["segments"][4]["p0"]["ffn"]
    assert moe["router_bias"] == ((1, 64), "zeros")
    assert moe["router"][0] == (1, 2048, 64)
    assert moe["wi"][0] == (1, 8, 2048, 1408)


def test_counts_by_hand():
    cfg = spec.config(CELL_CONFIG)
    d, H = 2048, 16
    attn = (d * H * 192 + d * 512 + d * 64 + 512 * H * (128 + 128)
            + H * 128 * d)                        # w_q, w_dkv, w_kr, up, o
    dense = 3 * d * 11264
    expert = 3 * d * 1408
    moe = d * 64 + 2 * expert + 6 * 8 / 64 * expert   # router, shared, held
    head = d * 20480
    weights = 5 * attn + dense + 4 * moe + head
    assert weights == 275644416
    causal = 3 * 2 * H * (192 + 128) * 4097 / 2 * 5
    assert flops.train_flops_per_token(cfg, 4096) == 6 * weights + causal
    norms = 2 * d
    dense_layer = norms + attn + 512 + dense               # + kv norm
    moe_layer = norms + attn + 512 + d * 64 + 64 + 8 * expert + 2 * expert
    assert dense_layer == 82973184 and moe_layer == 100405824
    assert flops.param_count(cfg) == dense_layer + 4 * moe_layer \
        + 2 * d * 20480 + d == 568484608


def _program_loss(pcfg):
    from repro.models import transformer as tr

    def loss(params, tokens, labels):
        return tr.loss_fn(params, pcfg, {"tokens": tokens,
                                         "labels": labels})[0]
    return loss


@pytest.fixture
def small_chunks(monkeypatch):
    """The program's attention in CHUNK-row chunks and the reference's in
    CHUNK-row query blocks: SEQ / CHUNK of each."""
    from repro.models import mla
    monkeypatch.setattr(mla, "chunked_attention", functools.partial(
        mla.chunked_attention, chunk_q=CHUNK, chunk_kv=CHUNK))
    monkeypatch.setattr(ds, "Q_BLOCK", CHUNK)


@pytest.mark.parametrize("q_lora_rank", [None, 24])
def test_program_matches_reference_leaf_by_leaf(small_chunks, q_lora_rank):
    """Loss and every leaf of the gradient, program against reference, at
    float32 on the CPU with SEQ / CHUNK = 4 attention chunks. The
    routing bias takes no gradient in either."""
    cfg = tiny_cfg(q_lora_rank=q_lora_rank)
    pcfg = ds.program_config(cfg)
    assert SEQ // CHUNK >= 4
    params = model.init_params(cfg, 3600000101)
    # a nonzero bias, so that the choice it corrects differs from the
    # scores' own
    for seg in params["segments"][1:]:
        seg["p0"]["ffn"]["router_bias"] = jax.random.normal(
            jax.random.key(1), (1, 16)) * 0.1
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 128, (2, SEQ)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 128, (2, SEQ)), jnp.int32)
    hi = jax.lax.Precision.HIGHEST
    lr, gr = jax.value_and_grad(ds.loss)(params, tokens, labels, cfg, hi)
    lp, gp = jax.value_and_grad(_program_loss(pcfg))(params, tokens, labels)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(gr)]
    for path, a, b in zip(paths, jax.tree.leaves(gp), jax.tree.leaves(gr)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if "router_bias" in path:
            assert not a.any() and not b.any(), path
            continue
        gap = np.linalg.norm(a - b) / np.linalg.norm(b)
        assert gap < 1e-4, (path, gap)


def test_refuses_what_the_program_cannot_run():
    for kw in ({"scoring_func": "softmax"}, {"n_group": 8},
               {"topk_method": "greedy"}, {"rope_scaling": {"type": "yarn"}},
               {"num_nextn_predict_layers": 1}):
        with pytest.raises(spec.SpecError):
            ds.program_config(tiny_cfg(**kw))


def test_a_program_without_a_held_share_is_refused(monkeypatch):
    """The parent program's ``ModelConfig`` has no held share: the cell
    fails at once with a ``SpecError`` that says so."""
    import dataclasses
    from repro.configs import base

    @dataclasses.dataclass(frozen=True)
    class Parent:
        name: str
        n_experts: int = 0
    monkeypatch.setattr(base, "ModelConfig", Parent)
    with pytest.raises(spec.SpecError, match="held|share"):
        ds.program_config(spec.config(CELL_CONFIG))


def _hlo_op_names(text):
    """instruction name -> op name, from a compiled module's HLO text."""
    import re
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.-]+) = .*?"
                         r"op_name=\"([^\"]*)\"", text, re.M):
        out[m.group(1)] = m.group(2)
    return out


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """The local steps of the tiny configuration's two participants, as
    the fused engine runs them (the loss's gradient under ``vmap`` in the
    ``local_step`` scope), recorded on the CPU inside ROUNDS program
    rounds whose counters are the loss's: each XLA operation the CPU
    runs, named by its op name in the compiled module."""
    import dataclasses
    import time
    from jax.profiler import ProfileData
    from repro.analysis import spans
    from repro.core.engine import LOCAL_STEP
    from repro.models import transformer as tr
    cfg = tiny_cfg()
    pcfg = ds.program_config(cfg)
    params = model.init_params(cfg, 21, stack=2)
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, 128, (2, 2, 32)), jnp.int32)

    def step(p, x):
        def loss(q, t):
            return tr.loss_fn(q, pcfg, {"tokens": t, "labels": t})
        with jax.named_scope(LOCAL_STEP):
            grads, metrics = jax.vmap(jax.grad(loss, has_aux=True))(p, x)
        return grads, jax.tree.map(jnp.sum, metrics["counters"])
    fn = jax.jit(step)
    jax.block_until_ready(fn(params, tok))
    names = _hlo_op_names(fn.lower(params, tok).compile().as_text())
    out = str(tmp_path_factory.mktemp("cpu_trace"))
    times = []
    jax.profiler.start_trace(out)
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.round"):
            with spans.round_span("colearn.round"):
                _, counts = jax.device_get(fn(params, tok))
                for name, value in counts.items():
                    spans.count(name, int(value))
        times.append((t0, time.perf_counter()))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    evs = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if not line.name.startswith("tf_XLA"):
                continue
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" in stats and stats.get("hlo_module") == \
                        "jit_step":
                    evs.append(trace.Event(
                        e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9, e.name))
    evs.sort(key=lambda e: e.start)
    base = trace.load(path)
    sc = scopes.Scoped(dataclasses.replace(base, devices={0: evs}), [],
                       {0: [names.get(e.name, "") for e in evs]})
    traffic = spec.complete_traffic({"batch_size": 2, "seq_len": 32,
                                     "steps_per_epoch": 1})
    return {"trace_path": path, "scoped": sc, "cfg": cfg, "times": times,
            "traffic": traffic, "tokens": [2 * 2 * 32] * ROUNDS,
            "peaks": flops.peaks("TPU v5 lite")}


def _seconds(ctx, keep):
    sc = ctx["scoped"]
    lo, hi = trace.window(sc.trace)
    return trace.measure(trace.clip(trace.union(
        (e.start, e.end) for e, t in zip(sc.trace.devices[0], sc.tf_ops[0])
        if keep(t)), lo, hi))


@pytest.mark.parametrize("scope", ["mla", "moe"])
def test_scope_readers_count_forward_and_backward(cpu_trace, scope):
    """The scope is a bare component of the op names of the forward pass,
    the remat's forward and the backward pass, and its reader counts the
    device time of all three a round."""
    ctx = cpu_trace
    under = [t for t in ctx["scoped"].tf_ops[0] if scopes.in_scope(t, scope)]
    assert [t for t in under if "transpose(" in t]
    assert [t for t in under if "transpose(" not in t]
    assert [t for t in under if "rematted_computation" in t]
    ms = spec.metric_reader(scope + "_ms")(ctx)
    assert ms == pytest.approx(1e3 * _seconds(
        ctx, lambda t: scopes.in_scope(t, scope)) / ROUNDS, rel=1e-9)


def test_scopes_nest(cpu_trace):
    ops = cpu_trace["scoped"].tf_ops[0]
    gmm = [t for t in ops if scopes.in_scope(t, "moe_gmm")]
    assert gmm and all(scopes.in_scope(t, "moe") for t in gmm)
    assert not any(scopes.in_scope(t, "mla") for t in ops
                   if scopes.in_scope(t, "moe"))


def test_held_pick_share_reads_the_window_counters(cpu_trace):
    """2 participants x 64 tokens x 3 choices x 2 expert layers a round;
    the share is what landed on the 4 held experts of 16."""
    from repro.analysis import spans
    ctx = cpu_trace
    lo, hi = ctx["times"][0][0], ctx["times"][-1][1]
    rounds = [r for r in spans.recent() if lo <= r.start and r.end <= hi]
    assert len(rounds) == ROUNDS
    assert all(r.counters["moe.picks"] == 2 * 64 * 3 * 2 for r in rounds)
    held = sum(r.counters["moe.held_picks"] for r in rounds)
    share = spec.metric_reader("held_pick_share")(ctx)
    assert share == pytest.approx(100 * held / (ROUNDS * 2 * 64 * 3 * 2))
    assert 0 < share < 100


def test_roofline_from_its_count(cpu_trace):
    """The grouped products' work from the held rows the counter gives:
    nine products of 2 x rows x 64 x 24 FLOPs; rows at d and f, and the
    held weights once a layer and a participant-step, in f32."""
    from repro.analysis import spans
    ctx = cpu_trace
    lo, hi = ctx["times"][0][0], ctx["times"][-1][1]
    rows = sum(r.counters["moe.held_picks"] for r in spans.recent()
               if lo <= r.start and r.end <= hi)
    layer_steps = 2 * ROUNDS * 2          # participant-steps x layers
    fl = 9 * 2 * rows * 64 * 24
    by = 4 * 9 * (rows * (64 + 24) + layer_steps * 4 * 64 * 24)
    f, b = ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"]
    secs = _seconds(ctx, lambda t: scopes.in_scope(t, "moe_gmm"))
    assert spec.metric_reader("expert_gmm_roofline")(ctx) == pytest.approx(
        100 * max(fl / f, by / b) / secs, rel=1e-9)


def test_readers_read_nothing_without_a_trace_or_counters(monkeypatch):
    """A run without a trace file, or a program that keeps no counters
    (the parent), reads None and raises nothing."""
    from repro.analysis import spans
    ctx = {"trace_path": None, "peaks": None, "times": [(0.0, 1.0)]}
    for name in ("mla_ms", "moe_ms", "expert_gmm_roofline",
                 "held_pick_share"):
        assert spec.metric_reader(name)(ctx) is None
    monkeypatch.setattr(spans, "recent", lambda: [
        type("Old", (), {"start": 0.1, "end": 0.2, "host_ms": {}})()])
    assert spec.metric_reader("held_pick_share")(ctx) is None
