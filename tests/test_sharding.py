"""Distribution tests on a small forced-device mesh (subprocess: the main
pytest process must keep the plain 1-device backend)."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_sim_mesh
from repro.sharding import specs as sp
from repro.core import averaging
from repro.models import transformer as tr

mesh = make_sim_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_smoke_config("internlm2-1.8b")
out = {}

# 1) vanilla train step lowers+compiles and runs on the 3-axis mesh
params = tr.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
pspecs = sp.param_specs(params, cfg, mesh)
psh = sp.named(mesh, pspecs)
bsh = sp.named(mesh, sp.batch_specs(cfg, mesh, "train"))
step = steps_mod.make_train_step(cfg, lr=0.01)
batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
         "labels": jnp.ones((8, 16), jnp.int32)}
with jax.set_mesh(mesh):
    fn = jax.jit(step, in_shardings=(psh, bsh),
                 out_shardings=(psh, NamedSharding(mesh, P())))
    new_params, loss = fn(params, batch)
out["vanilla_loss_finite"] = bool(jnp.isfinite(loss))

# 2) colearn vmapped step: per-pod replicas stay DIFFERENT after local steps
K = 2
stacked = averaging.stack_participants(params, K)
stacked = jax.tree.map(
    lambda t: t.at[1].multiply(1.5) if t.ndim > 0 else t, stacked)
spshapes = jax.tree.map(lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), stacked)
spsh = sp.named(mesh, sp.param_specs(spshapes, cfg, mesh, participant=True))
cbsh = sp.named(mesh, sp.batch_specs(cfg, mesh, "train", participant=True))
cbatch = {"tokens": jnp.zeros((K, 4, 16), jnp.int32),
          "labels": jnp.ones((K, 4, 16), jnp.int32)}
cstep = steps_mod.make_colearn_train_step(cfg, lr=0.01)
with jax.set_mesh(mesh):
    cfn = jax.jit(cstep, in_shardings=(spsh, cbsh))
    new_stacked, losses = cfn(stacked, cbatch)
out["colearn_losses"] = [float(x) for x in losses]
d = jax.tree.leaves(jax.tree.map(
    lambda t: float(jnp.abs(t[0] - t[1]).max()), new_stacked))
out["replicas_differ"] = max(d) > 0

# 3) averaging: pjit mean == shard_map psum over 'pod'
avg_p = jax.jit(averaging.average_pjit)(new_stacked)
avg_sm_fn = averaging.make_average_shard_map(
    mesh, sp.param_specs(spshapes, cfg, mesh, participant=True))
avg_s = avg_sm_fn(new_stacked)
diffs = [float(jnp.abs(a - b).max()) for a, b in
         zip(jax.tree.leaves(avg_p), jax.tree.leaves(avg_s))]
out["avg_match"] = max(diffs) < 1e-4
out["avg_is_mean"] = bool(np.allclose(
    np.asarray(jax.tree.leaves(avg_p)[0][0]),
    np.asarray(jax.tree.leaves(new_stacked)[0].mean(0)), atol=1e-5))

# 4) fused round engine on the pod mesh: whole round (epoch scan + shard_map
#    Eq. 2 + on-device Eq. 4) as one program; slots converge to the mean
from repro.configs.base import CoLearnConfig
ccfg = CoLearnConfig(n_participants=K, T0=2, eta0=0.01, max_rounds=1)
round_fn = steps_mod.make_fused_round_step(
    cfg, ccfg, mesh=mesh,
    param_specs=sp.param_specs(spshapes, cfg, mesh, participant=True))
rbatch = {"tokens": jnp.zeros((2, K, 1, 4, 16), jnp.int32),
          "labels": jnp.ones((2, K, 1, 4, 16), jnp.int32)}
with jax.set_mesh(mesh):
    averaged, _, aux = round_fn(stacked, (), rbatch, jnp.int32(0))
out["fused_round_losses_finite"] = bool(jnp.isfinite(aux["losses"]).all())
out["fused_round_rel_finite"] = bool(jnp.isfinite(aux["rel"]))
out["fused_round_slots_equal"] = max(
    float(jnp.abs(t[0] - t[1]).max())
    for t in jax.tree.leaves(averaged)) < 1e-4

# 4b) FullAverage x FlatFusedInt8 on the pod mesh (via the round-strategy
#     API): each pod int8-roundtrips its own row, ONE psum over 'pod'
#     aggregates the payloads; result within the int8 error bound of the
#     exact mean, slots equal
from repro.core import api
flat_avg = api.FullAverage().make_aggregate_fn(
    api.FlatFusedInt8(impl="ref"), mesh=mesh)
with jax.set_mesh(mesh):
    favg = jax.jit(flat_avg)(new_stacked)
errs, bounds = [], []
for f, e, s in zip(jax.tree.leaves(favg), jax.tree.leaves(avg_p),
                   jax.tree.leaves(new_stacked)):
    errs.append(float(jnp.abs(f.astype(jnp.float32)
                              - e.astype(jnp.float32)).max()))
    bounds.append(float(jnp.abs(s.astype(jnp.float32)).max()) / 127.0 + 1e-6)
out["flat_avg_within_bound"] = all(e <= b for e, b in zip(errs, bounds))
out["flat_avg_slots_equal"] = max(
    float(jnp.abs(t[0] - t[1]).max()) for t in jax.tree.leaves(favg)) == 0.0

# 4c) FullAverage x LeafwiseInt8 on the pod mesh: per-leaf reference
#     roundtrip in front of the shard_map psum (the third codec of the
#     pod-path acceptance matrix; exact f32 is covered by 3/4 above)
leaf_avg = api.FullAverage().make_aggregate_fn(
    api.LeafwiseInt8(impl="ref"), mesh=mesh,
    param_specs=sp.param_specs(spshapes, cfg, mesh, participant=True))
with jax.set_mesh(mesh):
    lavg = jax.jit(leaf_avg)(new_stacked)
errs = [float(jnp.abs(f.astype(jnp.float32) - e.astype(jnp.float32)).max())
        for f, e in zip(jax.tree.leaves(lavg), jax.tree.leaves(avg_p))]
out["leafwise_avg_within_bound"] = all(
    e <= b for e, b in zip(errs, bounds))
out["leafwise_avg_slots_equal"] = max(
    float(jnp.abs(t[0] - t[1]).max()) for t in jax.tree.leaves(lavg)) == 0.0

# 4d) weighted aggregators on the pod mesh: the psum (partial) and
#     collective-permute (ring) specializations must match the host-side
#     dense-mixing reference — without the all-gather the fallback pays
pspecs_part = sp.param_specs(spshapes, cfg, mesh, participant=True)
for nm, agg in (("partial", api.PartialParticipation(m=2, seed=0)),
                ("ring", api.RingGossip())):
    W = jnp.asarray(agg.mixing_matrix(0, K))
    mesh_fn = agg.make_aggregate_fn(api.ExactF32(), mesh=mesh,
                                    param_specs=pspecs_part)
    host_fn = agg.make_aggregate_fn(api.ExactF32())
    with jax.set_mesh(mesh):
        got = jax.jit(mesh_fn)(new_stacked, W)
    want = host_fn(new_stacked, W)
    out[f"{nm}_mesh_matches_host"] = max(
        float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))) < 1e-5

# 4f) graph-structured gossip on the pod mesh: GraphGossip's sparse
#     per-permutation ppermute specialization and its D2 variant (the
#     correction tree rides the shard_map sharded like the params) must
#     match the host-side dense-mixing reference
for nm, agg in (("graph_hypercube", api.GraphGossip("hypercube")),
                ("graph_grid2d", api.GraphGossip("grid2d"))):
    W = jnp.asarray(agg.mixing_matrix(0, K))
    mesh_fn = agg._make_mesh_aggregate_fn(api.ExactF32(), mesh,
                                          pspecs_part, "pod")
    out[f"{nm}_sparse_path_engaged"] = mesh_fn is not None
    host_fn = agg._make_host_aggregate_fn(api.ExactF32())
    with jax.set_mesh(mesh):
        got = jax.jit(mesh_fn)(new_stacked, W)
    want = host_fn(new_stacked, W)
    out[f"{nm}_mesh_matches_host"] = max(
        float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))) < 1e-5

d2 = api.D2Gossip("hypercube")
W = jnp.asarray(d2.mixing_matrix(0, K))
corr0 = jax.tree.map(
    lambda t: 0.01 * jnp.arange(t.size, dtype=jnp.float32
                                ).reshape(t.shape), new_stacked)
d2_mesh = d2._make_mesh_aggregate_fn(api.ExactF32(), mesh,
                                     pspecs_part, "pod")
out["d2_sparse_path_engaged"] = d2_mesh is not None
d2_host = d2._make_host_aggregate_fn(api.ExactF32())
with jax.set_mesh(mesh):
    gmix, gcorr = jax.jit(d2_mesh)(new_stacked, W, corr0)
wmix, wcorr = d2_host(new_stacked, W, corr0)
out["d2_mesh_matches_host"] = max(
    float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    for a, b in zip(jax.tree.leaves((gmix, gcorr)),
                    jax.tree.leaves((wmix, wcorr)))) < 1e-5

# 4e) heterogeneity scenario on the pod mesh: example-count-weighted Eq. 2
#     rides the shared weighted-psum specialization (matches the host
#     dense-mixing reference), the flat codec keeps a weighted single-
#     buffer psum within the int8 bound, and the masked fused round
#     (ragged per-pod batch counts as traced data) runs end to end
wagg = api.FullAverage(weights=(3.0, 1.0))
W = jnp.asarray(wagg.mixing_matrix(0, K))
wmesh = wagg.make_aggregate_fn(api.ExactF32(), mesh=mesh,
                               param_specs=pspecs_part)
whost = wagg.make_aggregate_fn(api.ExactF32())
with jax.set_mesh(mesh):
    wgot = jax.jit(wmesh)(new_stacked, W)
wwant = whost(new_stacked, W)
out["weighted_full_mesh_matches_host"] = max(
    float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    for a, b in zip(jax.tree.leaves(wgot), jax.tree.leaves(wwant))) < 1e-5

wflat = api.FlatFusedInt8(impl="ref").make_fused_mean(mesh=mesh,
                                                      weighted=True)
with jax.set_mesh(mesh):
    wfgot = jax.jit(wflat)(new_stacked, W[0])
out["weighted_flat_mesh_within_bound"] = all(
    float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) <= bd
    for a, b, bd in zip(jax.tree.leaves(wfgot), jax.tree.leaves(wwant),
                        bounds))

round_fn_m = steps_mod.make_fused_round_step(
    cfg, ccfg, mesh=mesh, aggregator=wagg, masked=True,
    param_specs=pspecs_part)
rbatch_m = {"tokens": jnp.zeros((2, K, 2, 4, 16), jnp.int32),
            "labels": jnp.ones((2, K, 2, 4, 16), jnp.int32)}
bmask = jnp.asarray(np.array([[True, True], [True, False]]))
with jax.set_mesh(mesh):
    averaged_m, _, aux_m = round_fn_m(stacked, (), rbatch_m, bmask,
                                      jnp.int32(0), W)
out["masked_round_losses_finite"] = bool(jnp.isfinite(aux_m["losses"]).all())
out["masked_round_slots_equal"] = max(
    float(jnp.abs(t[0] - t[1]).max())
    for t in jax.tree.leaves(averaged_m)) < 1e-4

# 4f) sub-int8 wire on the pod mesh: the bit-width-general codecs reduce
#     bitwise to the legacy int8 classes at bits=8, and the error-feedback
#     (stateful) paths — psum aggregate and the fused round step — run on
#     the mesh with each pod's residual resident on that pod
gen8 = api.FullAverage().make_aggregate_fn(
    api.FlatFusedIntN(bits=8, impl="ref"), mesh=mesh)
with jax.set_mesh(mesh):
    favg_gen = jax.jit(gen8)(new_stacked)
out["intn_bits8_pod_bit_identical"] = max(
    float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    for a, b in zip(jax.tree.leaves(favg_gen), jax.tree.leaves(favg))) == 0.0
lgen8 = api.FullAverage().make_aggregate_fn(
    api.LeafwiseIntN(bits=8, impl="ref"), mesh=mesh,
    param_specs=pspecs_part)
with jax.set_mesh(mesh):
    lavg_gen = jax.jit(lgen8)(new_stacked)
out["leafwise_bits8_pod_bit_identical"] = max(
    float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    for a, b in zip(jax.tree.leaves(lavg_gen), jax.tree.leaves(lavg))) == 0.0

ef_codec = api.FlatFusedIntN(bits=4, error_feedback=True, impl="ref")
res0 = ef_codec.init_state(new_stacked)
ef_mesh = api.FullAverage().make_aggregate_fn(ef_codec, mesh=mesh)
ef_host = api.FullAverage().make_aggregate_fn(ef_codec)
with jax.set_mesh(mesh):
    mixed_m, res_m = jax.jit(ef_mesh)(new_stacked, None, res0)
mixed_h, res_h = ef_host(new_stacked, None, res0)
out["ef_int4_pod_matches_host"] = max(
    float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    for a, b in zip(jax.tree.leaves(mixed_m), jax.tree.leaves(mixed_h))) < 1e-5
out["ef_int4_pod_residual_matches_host"] = float(
    jnp.abs(res_m - res_h).max()) < 1e-5
out["ef_int4_pod_residual_nonzero"] = float(jnp.abs(res_m).max()) > 0.0

round_fn_ef = steps_mod.make_fused_round_step(
    cfg, ccfg, mesh=mesh, codec="fused", codec_bits=4, error_feedback=True,
    param_specs=pspecs_part)
with jax.set_mesh(mesh):
    averaged_ef, _, aux_ef = round_fn_ef(stacked, (), res0, rbatch,
                                         jnp.int32(0))
out["ef_round_losses_finite"] = bool(jnp.isfinite(aux_ef["losses"]).all())
out["ef_round_slots_equal"] = max(
    float(jnp.abs(t[0] - t[1]).max())
    for t in jax.tree.leaves(averaged_ef)) < 1e-4
out["ef_round_residual_nonzero"] = (
    float(jnp.abs(aux_ef["residual"]).max()) > 0.0)

# 5) decode step lowers on the mesh
cache = tr.init_cache(cfg, 8, 16, jnp.float32)
csh = sp.named(mesh, sp.cache_specs(
    jax.tree.map(lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), cache),
    mesh, 8))
with jax.set_mesh(mesh):
    sfn = jax.jit(steps_mod.make_serve_step(cfg),
                  in_shardings=(psh, csh, NamedSharding(mesh, P()),
                                NamedSharding(mesh, P())))
    logits, _ = sfn(new_params, cache, jnp.zeros((8, 1), jnp.int32),
                    jnp.int32(0))
out["decode_finite"] = bool(jnp.isfinite(logits).all())
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_results():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError("no RESULT line:\n" + proc.stdout[-2000:])


def test_vanilla_step_on_mesh(mesh_results):
    assert mesh_results["vanilla_loss_finite"]


def test_colearn_replicas_independent(mesh_results):
    assert mesh_results["replicas_differ"]
    assert all(np.isfinite(l) for l in mesh_results["colearn_losses"])


def test_average_pjit_matches_shard_map(mesh_results):
    assert mesh_results["avg_match"]
    assert mesh_results["avg_is_mean"]


def test_flat_compressed_average_on_pod_mesh(mesh_results):
    assert mesh_results["flat_avg_within_bound"]
    assert mesh_results["flat_avg_slots_equal"]


def test_leafwise_compressed_average_on_pod_mesh(mesh_results):
    assert mesh_results["leafwise_avg_within_bound"]
    assert mesh_results["leafwise_avg_slots_equal"]


def test_weighted_aggregators_on_pod_mesh(mesh_results):
    assert mesh_results["partial_mesh_matches_host"]
    assert mesh_results["ring_mesh_matches_host"]


def test_graph_gossip_on_pod_mesh(mesh_results):
    assert mesh_results["graph_hypercube_sparse_path_engaged"]
    assert mesh_results["graph_hypercube_mesh_matches_host"]
    assert mesh_results["graph_grid2d_sparse_path_engaged"]
    assert mesh_results["graph_grid2d_mesh_matches_host"]


def test_d2_gossip_on_pod_mesh(mesh_results):
    assert mesh_results["d2_sparse_path_engaged"]
    assert mesh_results["d2_mesh_matches_host"]


def test_heterogeneity_scenario_on_pod_mesh(mesh_results):
    assert mesh_results["weighted_full_mesh_matches_host"]
    assert mesh_results["weighted_flat_mesh_within_bound"]
    assert mesh_results["masked_round_losses_finite"]
    assert mesh_results["masked_round_slots_equal"]


def test_fused_round_on_pod_mesh(mesh_results):
    assert mesh_results["fused_round_losses_finite"]
    assert mesh_results["fused_round_rel_finite"]
    assert mesh_results["fused_round_slots_equal"]


def test_sub_int8_wire_on_pod_mesh(mesh_results):
    assert mesh_results["intn_bits8_pod_bit_identical"]
    assert mesh_results["leafwise_bits8_pod_bit_identical"]
    assert mesh_results["ef_int4_pod_matches_host"]
    assert mesh_results["ef_int4_pod_residual_matches_host"]
    assert mesh_results["ef_int4_pod_residual_nonzero"]
    assert mesh_results["ef_round_losses_finite"]
    assert mesh_results["ef_round_slots_equal"]
    assert mesh_results["ef_round_residual_nonzero"]


def test_decode_on_mesh(mesh_results):
    assert mesh_results["decode_finite"]


import numpy as np  # noqa: E402  (used in fixtures above)
