import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh ×
variant) against the production meshes.

Two phases per combination (see EXPERIMENTS.md §Dry-run for why):
  * PROOF — the full config, scan-over-layers lowering, per-layer remat,
    gradient accumulation: proves the production program compiles and fits
    (memory_analysis) on the target mesh.
  * PROFILE — XLA cost_analysis counts a scan body once (measured), so for
    accurate roofline terms we compile reduced-depth *unrolled* variants
    (segment repeats 1, then 1+1 per segment) and difference them: per-layer
    flops/bytes/collective-bytes × true layer counts + the outside-the-loop
    cost. Intra-layer chunk scans (attention) are corrected analytically
    (launch/analytic.py).

Usage:  python -m repro.launch.dryrun [--arch ID|all] [--shape NAME|all]
        [--mesh single|multi|both] [--out artifacts/dryrun] [--no-profile]
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.launch import analytic, steps as steps_mod
from repro.launch.mesh import make_production_mesh
from repro.sharding import specs as sp

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
               "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\w+\[[\d,]*\]\S*)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def _shape_bytes(s):
    total = 0
    for m in _SHAPE_RE.finditer(s):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text):
    """Per-device link-byte estimates (ring model) from post-SPMD HLO."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_s, op = m.group(1), m.group(2).replace("-start", "")
        nbytes = _shape_bytes(shape_s)
        g, stride, span = 1, 0, 0
        gm = _GROUPS_RE.search(line)
        if gm:
            ids = [int(x) for x in gm.group(1).split(",")]
            g = len(ids)
            stride = ids[1] - ids[0] if g > 1 else 0
            span = max(ids) - min(ids)
        else:
            im = _IOTA_RE.search(line)
            if im:
                # iota groups: arange(n).reshape(dims)[.transpose(perm)]
                # .reshape(G, S) — compute the true member span of group 0
                import numpy as np
                G, S = int(im.group(1)), int(im.group(2))
                dims = [int(d) for d in im.group(3).split(",")]
                ids = np.arange(int(np.prod(dims))).reshape(dims)
                if im.group(4):
                    ids = ids.transpose([int(p) for p in im.group(4).split(",")])
                row = ids.reshape(G, S)[0]
                g = S
                stride = int(row[1] - row[0]) if S > 1 else 0
                span = int(row.max() - row.min())
        if g <= 1:
            continue
        if op == "all-gather":
            link = nbytes * (g - 1) / g
        elif op == "reduce-scatter":
            link = nbytes * (g - 1)
        elif op == "all-reduce":
            link = 2 * nbytes * (g - 1) / g
        elif op == "all-to-all":
            link = nbytes * (g - 1) / g
        else:                                    # collective-permute
            link = nbytes
        out.append({"op": op, "link_bytes": link, "group": g,
                    "span": span})
    return out


def coll_summary(colls, multi_pod):
    by_op = {}
    for c in colls:
        by_op[c["op"]] = by_op.get(c["op"], 0.0) + c["link_bytes"]
    return {"n_ops": len(colls),
            "link_bytes": sum(c["link_bytes"] for c in colls),
            "cross_pod_link_bytes":
                sum(c["link_bytes"] for c in colls if c["span"] >= 256)
                if multi_pod else 0.0,
            "by_op": by_op}


def _microbatch(shape):
    if shape.kind != "train":
        return 1
    tokens = shape.global_batch * shape.seq_len
    m = max(1, tokens // (32 * 8192))            # ~8k tokens/device/microbatch
    while shape.global_batch % m:
        m -= 1
    return m


# ---------------------------------------------------------------------------
def build(cfg, shape, mesh, multi_pod, variant, lowering):
    """Returns (jitted_fn, abstract args)."""
    pshapes = steps_mod.params_shapes(cfg)
    K = mesh.shape.get("pod", 1)
    participant = (variant in ("train_colearn", "average", "round_colearn")
                   and multi_pod)

    if participant:
        pshapes = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct((K, *v.shape), v.dtype), pshapes)
    psh = sp.named(mesh, sp.param_specs(pshapes, cfg, mesh,
                                        participant=participant))

    if variant in ("train_vanilla", "train_colearn"):
        data = steps_mod.input_specs(cfg, shape,
                                     participants=K if participant else 0)
        bspecs = sp.named(mesh, sp.batch_specs(cfg, mesh, "train", participant))
        mb = _microbatch(shape)
        step = (steps_mod.make_colearn_train_step(cfg, lowering=lowering,
                                                  microbatch=mb)
                if participant else
                steps_mod.make_train_step(cfg, lowering=lowering,
                                          microbatch=mb))
        fn = jax.jit(step, in_shardings=(psh, bspecs),
                     out_shardings=(psh, NamedSharding(mesh, P())),
                     donate_argnums=(0,))
        return fn, (pshapes, data)

    if variant == "average":
        fn = jax.jit(steps_mod.make_average_step(),
                     in_shardings=(psh,), out_shardings=psh,
                     donate_argnums=(0,))
        return fn, (pshapes,)

    if variant == "round_colearn":
        # fused round engine on the pod mesh: T_dry-epoch scan + shard_map
        # Eq. 2 + on-device Eq. 4, compiled as ONE program. T_dry=2 and one
        # batch per epoch keep the compile bounded while still exercising
        # the epoch scan (the real T_i only changes scan trip count).
        from repro.configs.base import CoLearnConfig
        T_dry, n_b = 2, 1
        data = steps_mod.input_specs(cfg, shape, participants=K)
        data = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(
                (T_dry, v.shape[0], n_b, *v.shape[1:]), v.dtype), data)
        bspecs = sp.batch_specs(cfg, mesh, "train", participant=True)
        rspecs = jax.tree.map(lambda s: P(None, *s[:1], None, *s[1:]),
                              bspecs, is_leaf=lambda x: isinstance(x, P))
        rbsh = sp.named(mesh, rspecs)
        ccfg = CoLearnConfig(n_participants=K, T0=T_dry, max_rounds=1)
        round_fn = steps_mod.make_fused_round_step(
            cfg, ccfg, lowering=lowering, mesh=mesh,
            param_specs=sp.param_specs(pshapes, cfg, mesh, participant=True))
        fn = jax.jit(round_fn,
                     in_shardings=(psh, (), rbsh, NamedSharding(mesh, P())),
                     donate_argnums=(0,))
        return fn, (pshapes, (), data, jax.ShapeDtypeStruct((), jnp.int32))

    if variant == "prefill":
        data = steps_mod.input_specs(cfg, shape)
        bspecs = sp.named(mesh, sp.batch_specs(cfg, mesh, "train"))
        fn = jax.jit(steps_mod.make_prefill_step(cfg, lowering=lowering),
                     in_shardings=(psh, bspecs))
        return fn, (pshapes, data)

    # serve (decode)
    data = steps_mod.input_specs(cfg, shape)
    cspecs = sp.named(mesh, sp.cache_specs(data["cache"], mesh,
                                           shape.global_batch))
    dp_n = 512 if multi_pod else 256
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    b_spec = dp if shape.global_batch % (32 if multi_pod else 16) == 0 else None
    tok_sh = NamedSharding(mesh, P(b_spec, None))
    fn = jax.jit(steps_mod.make_serve_step(cfg, lowering=lowering),
                 in_shardings=(psh, cspecs, tok_sh, NamedSharding(mesh, P())),
                 donate_argnums=(1,))
    return fn, (pshapes, data["cache"], data["token"], data["pos"])


def _compile(cfg, shape, mesh, multi_pod, variant, lowering):
    fn, args = build(cfg, shape, mesh, multi_pod, variant, lowering)
    with jax.set_mesh(mesh):
        compiled = fn.lower(*args).compile()
    return compiled


def _costs(compiled, multi_pod):
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())
    cs = coll_summary(colls, multi_pod)
    return {"flops": ca.get("flops", 0.0),
            "bytes": ca.get("bytes accessed", 0.0),
            "link_bytes": cs["link_bytes"],
            "cross_pod_link_bytes": cs["cross_pod_link_bytes"],
            "by_op": cs["by_op"], "n_coll": cs["n_ops"]}


def _reduced(cfg, repeats):
    segs = tuple((pat, r) for (pat, _), r in zip(cfg.segments, repeats))
    n = sum(len(p) * r for p, r in segs)
    return cfg.with_(n_layers=n, segments=segs)


def profile_costs(cfg, shape, mesh, multi_pod, variant):
    """Depth-differenced per-layer costs extrapolated to full depth."""
    n_seg = len(cfg.segments)
    base_r = [1] * n_seg
    t0 = time.time()
    c_base = _costs(_compile(_reduced(cfg, base_r), shape, mesh, multi_pod,
                             variant, "unroll"), multi_pod)
    deltas = []
    for s in range(n_seg):
        r = list(base_r)
        r[s] += 1
        c_s = _costs(_compile(_reduced(cfg, r), shape, mesh, multi_pod,
                              variant, "unroll"), multi_pod)
        deltas.append({k: (c_s[k] - c_base[k]) if not isinstance(c_base[k], dict)
                       else {o: c_s[k].get(o, 0) - c_base[k].get(o, 0)
                             for o in set(c_base[k]) | set(c_s[k])}
                       for k in c_base})
    full = {}
    for k in ("flops", "bytes", "link_bytes", "cross_pod_link_bytes"):
        full[k] = c_base[k] + sum(
            max(d[k], 0.0) * (R - 1)
            for d, (_, R) in zip(deltas, cfg.segments))
    full["by_op"] = {
        o: c_base["by_op"].get(o, 0.0) + sum(
            max(d["by_op"].get(o, 0.0), 0.0) * (R - 1)
            for d, (_, R) in zip(deltas, cfg.segments))
        for o in set().union(c_base["by_op"],
                             *[d["by_op"] for d in deltas])}
    full["profile_s"] = round(time.time() - t0, 1)
    full["per_layer"] = deltas
    full["outside"] = c_base
    return full


def run_one(arch, shape_name, mesh_kind, variant, profile=True):
    multi_pod = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = INPUT_SHAPES[shape_name]
    cfg = steps_mod.config_for_shape(get_config(arch), shape)
    t0 = time.time()
    compiled = _compile(cfg, shape, mesh, multi_pod, variant, "scan")
    t1 = time.time()
    ma = compiled.memory_analysis()
    total_p, active_p = analytic.param_counts(cfg)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "variant": variant, "compile_s": round(t1 - t0, 1),
        "n_devices": int(len(mesh.devices.flat)),
        "microbatch": _microbatch(shape) if "train" in variant else 1,
        "params_total": int(total_p), "params_active": int(active_p),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_per_device": (ma.argument_size_in_bytes
                                      + ma.output_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      - ma.alias_size_in_bytes),
        },
        "scan_raw_cost": _costs(compiled, multi_pod),
        "analytic": {
            "model_flops": analytic.model_flops(cfg, shape, shape.kind)
            if variant not in ("average", "round_colearn") else 0.0,
            "scan_correction_flops":
                analytic.scan_corrections(cfg, shape, shape.kind)
                if variant not in ("average", "round_colearn") else 0.0,
        },
    }
    del compiled
    if profile and variant not in ("average", "round_colearn"):
        rec["profile"] = profile_costs(cfg, shape, mesh, multi_pod, variant)
    return rec


VARIANTS = {
    "train": {"single": ["train_vanilla"],
              "multi": ["train_vanilla", "train_colearn", "average",
                        "round_colearn"]},
    "prefill": {"single": ["prefill"], "multi": ["prefill"]},
    "decode": {"single": ["serve"], "multi": ["serve"]},
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-profile", action="store_true")
    ap.add_argument("--profile-meshes", default="single",
                    help="comma list of meshes to run the profile phase on")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    prof_meshes = set(args.profile_meshes.split(","))
    os.makedirs(args.out, exist_ok=True)

    n_ok = n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            kind = INPUT_SHAPES[shape_name].kind
            for mesh_kind in meshes:
                for variant in VARIANTS[kind][mesh_kind]:
                    tag = f"{arch}__{shape_name}__{mesh_kind}__{variant}"
                    path = os.path.join(args.out, tag + ".json")
                    if os.path.exists(path):
                        print(f"[skip cached] {tag}", flush=True)
                        n_ok += 1
                        continue
                    try:
                        rec = run_one(arch, shape_name, mesh_kind, variant,
                                      profile=(not args.no_profile and
                                               mesh_kind in prof_meshes))
                        with open(path, "w") as f:
                            json.dump(rec, f, indent=1)
                        pk = rec["memory"]["peak_bytes_per_device"] / 2 ** 30
                        fl = rec.get("profile", rec["scan_raw_cost"])["flops"]
                        print(f"[ok {rec['compile_s']:6.1f}s] {tag} "
                              f"flops/dev={fl:.3e} peak={pk:.2f}GiB",
                              flush=True)
                        n_ok += 1
                    except Exception as e:
                        n_fail += 1
                        with open(path + ".fail", "w") as f:
                            f.write(traceback.format_exc())
                        print(f"[FAIL] {tag}: {type(e).__name__}: {e}",
                              flush=True)
    print(f"dry-run done: {n_ok} ok, {n_fail} failed", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
