"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
kernel tile it cannot lay out, a program larger than the chip's memory, a
collective it cannot place. Each test here compiles one program of the
round for ``v5e:2x2`` (one chip, or all four) and reads what the compiler
produced. Nothing runs, so no result or time comes from these tests.

The topology is described inside a fixture: the TPU library may be loaded
by one process at a time, so the call must happen only in the worker that
runs this file, and never while a module is imported.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import cut_depth, get_config
from repro.configs.base import CoLearnConfig
from repro.core import api, engine, flatbuf
from repro.kernels import ops
from repro.launch import steps
from repro.launch.mesh import auto_mesh
from repro.models import transformer as tr
from repro.optim.optimizers import get_optimizer

#: what the compiler lets one v5e program use (16 GiB less its reserve)
V5E_HBM = int(15.75 * 2 ** 30)
ARCH, N_LAYERS = "internlm2-1.8b", 2       # the one-chip smoke cell
K, BATCH, SEQ, STEPS = 2, 4, 512, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — skip reason
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return cut_depth(get_config(ARCH), N_LAYERS)


@pytest.fixture(scope="module")
def param_shapes(cfg):
    return jax.eval_shape(lambda k: tr.init_params(k, cfg, jnp.float32),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def _stacked(param_shapes, k, sharding_of):
    return jax.tree.map(
        lambda t: jax.ShapeDtypeStruct((k, *t.shape), t.dtype,
                                       sharding=sharding_of(t)),
        param_shapes)


# At the cell's full size the error-feedback pass needs 16.9 GB of the
# chip's 15.75 GB (the buffer and the residual, plus a relayout copy of
# each ahead of the kernel), so it is compiled on half the buffer.
@pytest.mark.parametrize("kernel,bits,share", [("quant_avg_dequant", 8, 1),
                                               ("quant_avg_dequant", 4, 1),
                                               ("quant_avg_dequant_ef", 4, 2)])
def test_wire_kernel_compiles_for_v5e(one_chip, param_shapes, kernel, bits,
                                      share):
    """The Eq. 2 wire kernel over the smoke cell's flat buffer (or
    ``1/share`` of it)."""
    layout = flatbuf.make_layout(_stacked(param_shapes, K, lambda t: None))
    buf = jax.ShapeDtypeStruct((K, layout.n_pad // share), jnp.float32,
                               sharding=one_chip)
    fn = functools.partial(getattr(ops, kernel), bits=bits, impl="pallas")
    args = (buf, buf) if kernel.endswith("_ef") else (buf,)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_round_fits_one_v5e(one_chip, cfg, param_shapes):
    """The full-width int8 round of the smoke cell on one chip, built as
    ``CoLearner``'s fused engine builds it (params and optimizer state
    donated): the Pallas kernel is in the program and the program fits."""
    codec = api.FlatFusedInt8(impl="pallas")
    schedule = api.CLR()

    def loss_fn(params, batch):
        x, y = batch
        return tr.loss_fn(params, cfg, {"tokens": x, "labels": y})

    fn = engine.make_fused_round(
        loss_fn, get_optimizer("sgd"), lr_fn=api.traced_body(schedule),
        aggregate_fn=api.FullAverage().make_aggregate_fn(codec))
    stacked = _stacked(param_shapes, K, lambda t: one_chip)
    batch = jax.ShapeDtypeStruct((1, K, STEPS, BATCH, SEQ), jnp.int32,
                                 sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    sched = {"kind": i32,
             "p": jax.ShapeDtypeStruct((api.N_SCHED_PARAMS,), jnp.float32,
                                       sharding=one_chip)}
    compiled = fn.lower(stacked, (), (batch, batch), i32, sched, i32,
                        None).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM
    assert mem.peak_memory_in_bytes < V5E_HBM


@pytest.mark.parametrize("codec", ["exact", "fused"])
def test_pod_round_compiles_on_v5e_2x2(topo, cfg, param_shapes, codec):
    """K=4, one participant per chip over the ``pod`` axis: the Eq. 2
    average crosses the chips as a collective."""
    n = len(topo.devices)
    mesh = auto_mesh((n,), ("pod",), topo.devices)
    specs = jax.tree.map(lambda t: P("pod", *([None] * t.ndim)),
                         param_shapes)
    ccfg = CoLearnConfig(n_participants=n, T0=1, max_rounds=1)
    # built outside the mesh: a described device cannot hold the eager
    # constants make_fused_round_step creates
    fn = jax.jit(steps.make_fused_round_step(
        cfg, ccfg, codec=codec, compress_impl="pallas", mesh=mesh,
        param_specs=specs))
    stacked = jax.tree.map(
        lambda t, s: jax.ShapeDtypeStruct((n, *t.shape), t.dtype,
                                          sharding=NamedSharding(mesh, s)),
        param_shapes, specs)
    batch = jax.ShapeDtypeStruct((1, n, STEPS, BATCH, SEQ), jnp.int32,
                                 sharding=NamedSharding(mesh, P(None, "pod")))
    ge0 = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        compiled = fn.lower(stacked, (), {"tokens": batch, "labels": batch},
                            ge0).compile()
    text = compiled.as_text()
    assert "all-reduce" in text or "collective-permute" in text
    if codec == "fused":
        assert "tpu_custom_call" in text
    assert compiled.memory_analysis().peak_memory_in_bytes < V5E_HBM


def test_described_devices_are_v5e(topo):
    kinds = {d.device_kind for d in topo.devices}
    assert len(topo.devices) == 4
    assert all("v5" in k.lower() for k in kinds), kinds
    assert np.unique([d.id for d in topo.devices]).size == 4
