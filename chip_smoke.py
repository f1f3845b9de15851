"""Bring-up check: the co-learning round on a TPU, at published widths.

Usage, on a TPU host with ``JAX_PLATFORMS`` unset:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the pod path, one participant per chip

One chip: two rounds of Algorithm 1 (local epochs, the Eq. 2 average, the
Eq. 4 check) through ``repro.launch.train.main`` for internlm2-1.8b at its
published widths with the depth cut to 2 layers, K=2, B=4, S=512, T0=1 and
2 steps per epoch; once with the exact wire and once with the flat int8
wire. It checks that the losses are finite, that every participant slot is
equal after each synced round, that the int8 round's compiled program holds
the Pallas kernel (``tpu_custom_call``), and that the kernel's Eq. 2 mean
agrees with ``kernels/ref.py`` on one buffer within the int8 bound.

Four chips: K=4, one participant per chip over the ``pod`` mesh axis,
through ``launch.steps.make_fused_round_step(mesh=..., param_specs=...)``
for both wires, compared with the same round built with ``mesh=None`` on
the same placed inputs (XLA infers the all-reduce there).

The timings printed are smoke timings of one run, not benchmarks. The last
line of the output is one JSON object naming the device; a failed check
raises, so the script exits non-zero and prints no such line, as it does
when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ARCH = "internlm2-1.8b"
N_LAYERS = 2
BATCH, SEQ, STEPS, ROUNDS = 4, 512, 2, 2
SEED = 0
#: f32 rounding between two differently partitioned programs
EXACT_TOL = 1e-5


def check(ok, what):
    if not ok:
        raise AssertionError(what)


def slot_spread(stacked):
    """Largest difference between any participant slot and slot 0."""
    import jax
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(t - t[:1])))
               for t in jax.tree.leaves(stacked))


def int8_bound(tree):
    """Per-leaf bound of the int8 wire's error on a mean: one quantum of
    the largest block scale, max|x| / 127."""
    import jax
    import jax.numpy as jnp
    return [float(jnp.max(jnp.abs(t))) / 127.0 + EXACT_TOL
            for t in jax.tree.leaves(tree)]


def round_text(learner, state, T):
    """Compiled text of the fused round executable the engine dispatched
    (full-average, ungated, static membership: the train.py defaults).
    It is lowered again from the same shapes, so the compile comes from the
    cache."""
    import jax
    import jax.numpy as jnp
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    K = learner.cfg.n_participants
    batch = jax.ShapeDtypeStruct((T, K, STEPS, BATCH, SEQ), jnp.int32,
                                 sharding=dev)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=dev)
    sched = learner.schedule.device_round_params(0)
    lowered = learner._runner._round.lower(
        state["params"], state["opt"], (batch, batch), i32, sched, i32, None)
    return lowered.compile().as_text()


def kernel_vs_ref(n=1 << 23):
    """The Pallas Eq. 2 wire mean against the jnp reference on one seeded
    (2, n) buffer, both against the exact mean."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    buf = jax.random.normal(jax.random.PRNGKey(SEED), (2, n), jnp.float32)
    pal = ops.quant_avg_dequant(buf, impl="pallas")
    oracle = ref.quant_avg_dequant_ref(buf)
    bound = float(jnp.max(jnp.abs(buf))) / 127.0
    d_ref = float(jnp.max(jnp.abs(pal - oracle)))
    d_exact = float(jnp.max(jnp.abs(pal - buf.mean(0))))
    print(f"kernel vs ref: n={n} max|pallas-ref|={d_ref:.3e} "
          f"max|pallas-exact|={d_exact:.3e} int8 bound={bound:.3e}",
          flush=True)
    check(d_ref <= bound and d_exact <= bound,
          "the Pallas wire mean leaves the int8 bound")


def one_chip():
    import jax
    import numpy as np
    from repro.launch import train

    dev = jax.devices()[0]
    for codec in ("exact", "fused"):
        rounds = []

        def on_round_end(learner, state, seconds, codec=codec, rounds=rounds):
            log = state["log"][-1]
            losses = [float(x) for x in log.local_losses]
            spread = slot_spread(state["params"])
            rounds.append(seconds)
            print(f"[{codec}] round {log.round}: T={log.T} losses={losses} "
                  f"rel_dw={log.rel_change} synced={log.synced} "
                  f"slot_spread={spread} round_s={seconds}", flush=True)
            check(np.isfinite(losses).all(), f"{codec}: non-finite loss")
            check(log.synced, f"{codec}: round {log.round} did not sync")
            check(spread == 0.0,
                  f"{codec}: participant slots differ after a synced round")
            if codec == "fused" and len(rounds) == ROUNDS:
                has_kernel = "tpu_custom_call" in round_text(learner, state,
                                                             log.T)
                print(f"[{codec}] compiled round holds tpu_custom_call: "
                      f"{has_kernel}", flush=True)
                check(has_kernel, "the int8 round runs no Pallas kernel")

        train.main(["--arch", ARCH, "--widths", "published",
                    "--n-layers", str(N_LAYERS), "--participants", "2",
                    "--batch-size", str(BATCH), "--seq-len", str(SEQ),
                    "--t0", "1", "--steps-per-epoch", str(STEPS),
                    "--rounds", str(ROUNDS), "--n-examples", "16",
                    "--codec", codec, "--seed", str(SEED)],
                   on_round_end=on_round_end)
        check(len(rounds) == ROUNDS, f"{codec}: {len(rounds)} rounds ran")
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"[{codec}] smoke timing, not a benchmark: round wall time "
              f"after warm-up {rounds[-1]} s; peak_bytes_in_use so far "
              f"{peak}", flush=True)
    kernel_vs_ref()


def four_chips():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import cut_depth, get_config
    from repro.configs.base import CoLearnConfig
    from repro.core import averaging
    from repro.data.synthetic import lm_examples
    from repro.launch import steps
    from repro.launch.mesh import make_sim_mesh
    from repro.models import transformer as tr

    K = 4
    mesh = make_sim_mesh((K,), ("pod",))
    cfg = cut_depth(get_config(ARCH), N_LAYERS)
    ccfg = CoLearnConfig(n_participants=K, T0=1, max_rounds=1)
    params = tr.init_params(jax.random.PRNGKey(SEED), cfg, jnp.float32)
    specs = jax.tree.map(lambda t: P("pod", *([None] * t.ndim)), params)
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    # every participant starts from the one model, each row on its chip
    stacked = jax.jit(lambda p: averaging.stack_participants(p, K),
                      out_shardings=shard)(params)
    del params
    x, y = lm_examples(SEED, K * STEPS * BATCH, SEQ, cfg.vocab_size)
    bsh = NamedSharding(mesh, P(None, "pod"))
    batches = {k: jax.device_put(a.reshape(1, K, STEPS, BATCH, SEQ), bsh)
               for k, a in (("tokens", x), ("labels", y))}
    for t in jax.tree.leaves((stacked, batches)):
        rows = t.addressable_shards
        check(len({s.device for s in rows}) == K,
              f"a {t.shape} leaf is not spread over the {K} chips")
        check(all(s.data.size * K == t.size for s in rows),
              f"a {t.shape} leaf is not split one participant per chip")
    print(f"placement: every leaf holds one participant on each of {K} "
          "chips", flush=True)

    ge0 = jnp.int32(0)
    with jax.set_mesh(mesh):
        for codec in ("exact", "fused"):
            outs = {}
            for path, kw in (("pod", {"mesh": mesh, "param_specs": specs}),
                             ("mesh=None", {"compress_impl": "ref"})):
                # the averaged rows stay one per chip: left to itself, XLA
                # replicates the broadcast average on the mesh=None path
                fn = jax.jit(steps.make_fused_round_step(
                    cfg, ccfg, codec=codec, **kw),
                    out_shardings=(shard, None, None))
                compiled = fn.lower(stacked, (), batches, ge0).compile()
                text = compiled.as_text()
                ops = {c: text.count(c) for c in
                       ("all-reduce", "collective-permute", "all-gather",
                        "tpu_custom_call")}
                averaged, _, aux = compiled(stacked, (), batches, ge0)
                losses = np.asarray(aux["losses"])
                print(f"[{codec} {path}] losses={losses.tolist()} "
                      f"rel={float(aux['rel'])} ops={ops}", flush=True)
                del aux
                check(np.isfinite(losses).all(), f"{codec} {path}: "
                      "non-finite loss")
                check(ops["all-reduce"] > 0,
                      f"{codec} {path}: no all-reduce over the pod axis")
                check(codec == "exact" or path != "pod"
                      or ops["tpu_custom_call"] > 0,
                      f"{codec} {path}: the int8 round runs no Pallas kernel")
                check(slot_spread(averaged) == 0.0,
                      f"{codec} {path}: participant slots differ")
                outs[path] = averaged
            diffs = [float(jnp.max(jnp.abs(a - b))) for a, b in
                     zip(jax.tree.leaves(outs["pod"]),
                         jax.tree.leaves(outs["mesh=None"]))]
            bounds = (int8_bound(outs["mesh=None"]) if codec == "fused"
                      else [EXACT_TOL] * len(diffs))
            print(f"[{codec}] pod vs mesh=None: max|diff|={max(diffs):.3e} "
                  f"(bound {max(bounds):.3e})", flush=True)
            check(all(d <= b for d, b in zip(diffs, bounds)),
                  f"{codec}: the pod round leaves the bound")
            del outs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    print(f"devices: {len(devices)} x {devices[0].device_kind}", flush=True)
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
