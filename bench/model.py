"""The benchmark's weights and its plain reference of the co-learning round.

Nothing here imports the program, and nothing here knows an
architecture: the configuration's module (``bench/arch/<model_type>.py``,
found by ``bench.spec.arch``) gives the parameter tree and the loss of
one batch. ``init_params`` makes a configuration's weights from a seed on
the device in one jitted call, from that tree. The reference runs the
module's loss under SGD at the round's rate (Eq. 3), and the Eq. 2
average of the participants, through the flat wire's blockwise
quantization where the traffic sets it. It computes in float32 with every
matrix product at ``HIGHEST`` precision (``dtype=bfloat16`` gives the
lower-precision control).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench.gen import seed_key

#: the flat wire's quantization block and tile rows (one scale a block of
#: one leaf; a leaf's blocks never hold another leaf's elements)
WIRE_BLOCK = 256
WIRE_ROWS = 8
QMAX = {8: 127.0, 4: 7.0}


def param_shapes(cfg):
    """The configuration's parameter tree as ``(shape, init)`` leaves, as
    its architecture module gives it (``bench.spec.arch``)."""
    return spec.arch(cfg).param_shapes(cfg)


def is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _init(key, cfg, dtype):
    leaves, treedef = jax.tree.flatten(param_shapes(cfg), is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, init) in zip(keys, leaves):
        if init == "ones":
            out.append(jnp.ones(shape, dtype))
        elif init == "zeros":
            out.append(jnp.zeros(shape, dtype))
        elif isinstance(init, int) and init > 0:
            w = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
            out.append((w * init ** -0.5).astype(dtype))
        else:
            raise ValueError(f"leaf {shape}: init {init!r} is no fan-in, "
                             "'ones' or 'zeros'")
    return jax.tree.unflatten(treedef, out)


def _init_fn(cfg, dtype, stack, out_shardings=None):
    def make(key):
        p = _init(key, cfg, dtype)
        if stack:
            p = jax.tree.map(lambda t: jnp.broadcast_to(t[None],
                                                        (stack, *t.shape)), p)
        return p
    return jax.jit(make, out_shardings=out_shardings)


@functools.lru_cache(maxsize=None)
def _init_cached(cfg_json, dtype_name, stack):
    import json
    return _init_fn(json.loads(cfg_json), jnp.dtype(dtype_name), stack)


def _cfg_json(cfg):
    import json
    return json.dumps(cfg, sort_keys=True)


def init_params(cfg, seed, dtype=jnp.float32, stack=0, out_shardings=None):
    """The configuration's weights from ``seed``, made on the device in one
    jitted call; ``stack=K`` gives K identical stacked rows (every
    participant starts from the one model), placed by ``out_shardings``."""
    if out_shardings is None:
        fn = _init_cached(_cfg_json(cfg), jnp.dtype(dtype).name, stack)
    else:
        fn = _init_fn(cfg, jnp.dtype(dtype), stack, out_shardings)
    return fn(seed_key(seed))


# ---------------------------------------------------------------------------
# one SGD step
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _step_fn(cfg_json, prec_name):
    import json
    cfg = json.loads(cfg_json)
    prec = getattr(jax.lax.Precision, prec_name)
    loss = spec.arch(cfg).loss

    def step(params, tokens, labels, lr):
        val, grads = jax.value_and_grad(loss)(params, tokens, labels, cfg,
                                              prec)
        lr = lr.astype(jax.tree.leaves(params)[0].dtype)
        return jax.tree.map(lambda p, g: p - lr * g, params, grads), val
    return jax.jit(step, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Eq. 3 and the wire
# ---------------------------------------------------------------------------
def epoch_lr(traffic, round_i, epoch_j, T_i, global_epoch):
    """The rate of local epoch ``epoch_j`` of round ``round_i``."""
    eta, r = traffic["eta0"], traffic["decay_rate"]
    kind = traffic["schedule"]
    if kind == "clr":
        return eta * r ** (epoch_j / T_i)
    if kind == "warmup_clr":
        ramp = min(1.0, (round_i + 1) / 3)
        return eta * ramp * r ** (epoch_j / T_i)
    if kind == "cosine":
        return 0.5 * eta * (1.0 + math.cos(math.pi * epoch_j / T_i))
    if kind == "elr":
        budget = max(global_epoch - epoch_j + T_i * max(
            traffic["max_rounds"] - round_i, 1), 1)
        return eta * r ** (global_epoch / budget)
    raise NotCovered(f"schedule {kind!r}")


class NotCovered(NotImplementedError):
    """A traffic option the reference does not implement."""


def _roundtrip(t, bits):
    """One participant's leaf through the flat wire: blockwise symmetric
    quantization, one scale per ``WIRE_BLOCK`` elements of the leaf."""
    flat = t.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    blocks = jnp.pad(flat, (0, (-n) % WIRE_BLOCK)).reshape(-1, WIRE_BLOCK)
    if bits == 1:
        scale = jnp.mean(jnp.abs(blocks), axis=1, keepdims=True)
        q = jnp.where(blocks > 0, 1.0, -1.0)
    else:
        amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
        scale = jnp.where(amax > 0, amax / QMAX[bits], 1.0)
        q = jnp.clip(jnp.round(blocks / scale), -QMAX[bits], QMAX[bits])
    return (q * scale).reshape(-1)[:n].reshape(t.shape)


@functools.lru_cache(maxsize=None)
def _upload_fn(bits, ef):
    """participant params (+ residual) -> what reaches the average."""
    if bits is None:
        return jax.jit(lambda p, e: (p, e))

    def up(p, e):
        y = p if e is None else jax.tree.map(jnp.add, p, e)
        dq = jax.tree.map(lambda t: _roundtrip(t, bits), y)
        new_e = None if e is None else jax.tree.map(jnp.subtract, y, dq)
        return dq, new_e
    return jax.jit(up)


def _check_covered(traffic):
    if traffic["codec"] not in ("exact", "fused"):
        raise NotCovered(f"codec {traffic['codec']!r}")
    if traffic["aggregator"] != "full":
        raise NotCovered(f"aggregator {traffic['aggregator']!r}")
    if traffic["sync_policy"] not in ("fle", "ile"):
        raise NotCovered(f"sync policy {traffic['sync_policy']!r}")
    if traffic["churn"] != "none":
        raise NotCovered(f"churn {traffic['churn']!r}")


def reference_rounds(cfg, traffic, seed, feed, n_rounds, *, dtype=jnp.float32,
                     devices=None, weights=None, fault=None):
    """Run the first ``n_rounds`` rounds from the seed's weights.

    ``feed(r, j)`` is epoch j of round r, a ``(tokens, labels)`` pair of
    ``(K, steps, B, S)`` arrays (``bench.gen.Feed``); the rounds' epoch
    counts follow the sync policy, as the reference runs it. Returns also
    ``epochs``, the epoch count of each round. Participant k runs on ``devices[k % len(devices)]``. Returns

    * ``losses`` ``(n_rounds, K)``: each participant's mean step loss;
    * ``norms[r]``: per leaf, in tree order, the ``(K,)`` norms of each
      participant row's change from the start after round r + 1;
    * ``spreads[r]``: the largest difference between a row and row 0;
    * ``epochs[r]``: the local epochs of round r.

    ``fault`` plants one of the faults the check must catch, for reading
    what it does: ``"half_batch"`` (each step's loss is the mean over the
    first half of its rows) or ``"no_exchange"`` (no Eq. 2: every row goes
    on from its own model).
    """
    _check_covered(traffic)
    if fault not in (None, "half_batch", "no_exchange"):
        raise ValueError(f"unknown fault {fault!r}")
    devices = devices or jax.devices()[:1]
    K = traffic["participants"]
    prec = "HIGHEST" if jnp.dtype(dtype) == jnp.float32 else "DEFAULT"
    step = _step_fn(_cfg_json(cfg), prec)
    bits = traffic["codec_bits"] if traffic["codec"] == "fused" else None
    upload = _upload_fn(bits, traffic["error_feedback"])
    w = (np.full(K, 1.0 / K) if weights is None
         else np.asarray(weights, np.float64) / np.sum(weights))
    own = [init_params(cfg, seed, dtype)] * K
    residual = [None] * K
    if traffic["error_feedback"]:
        residual = [jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32),
                                 own[0]) for _ in range(K)]
    T = traffic["t0"]
    ge = 0
    losses, norms, spreads, counts = [], [], [], []
    for r in range(n_rounds):
        epochs = [feed(r, j) for j in range(T)]
        counts.append(T)
        acc = None
        round_losses, outs = [None] * K, [None] * K
        old = own[0] if traffic["sync_policy"] == "ile" else None
        srcs, own = own, None
        # participant 0 last: the model it starts from is then used by no
        # one after it, and its first step reuses that buffer
        for k in reversed(range(K)):
            dev = devices[k % len(devices)]
            src, srcs[k] = srcs[k], None
            reused = any(o is src for o in srcs) or src is old
            p = jax.device_put(src, dev, may_alias=False if reused else None)
            del src
            ls = []
            for j, (tok, lab) in enumerate(epochs):
                lr = np.float32(epoch_lr(traffic, r, j, T, ge + j))
                for s in range(tok.shape[1]):
                    rows = (slice(0, tok.shape[2] // 2)
                            if fault == "half_batch" else slice(None))
                    p, l = step(p, jax.device_put(tok[k, s, rows], dev),
                                jax.device_put(lab[k, s, rows], dev),
                                jax.device_put(lr, dev))
                    ls.append(l)
            round_losses[k] = ls
            if fault == "no_exchange":
                outs[k] = p
                continue
            res = (None if residual[k] is None
                   else jax.device_put(residual[k], dev))
            dq, residual[k] = upload(p, res)
            del res
            del p
            dq = jax.device_put(dq, devices[0])
            acc = (_scaled(dq, np.float32(w[k])) if acc is None
                   else _accumulate(acc, dq, np.float32(w[k])))
            del dq
        round_losses = [float(np.mean(jax.device_get(ls)))
                        for ls in round_losses]
        if fault == "no_exchange":
            own = outs
        else:
            own = [jax.tree.map(lambda a: a.astype(dtype), acc)] * K
        del acc, outs
        rel = float(_rel_change(own[0], old)) if old is not None else None
        del old
        ge += T
        losses.append(round_losses)
        if all(o is own[0] for o in own):
            per_leaf = change_norms(cfg, seed, own[0])
            norms.append([np.full(K, float(x)) for x in per_leaf])
            spreads.append(0.0)
        else:
            per_row = [change_norms(cfg, seed, o) for o in own]
            norms.append([np.asarray([per_row[k][i] for k in range(K)])
                          for i in range(len(per_row[0]))])
            spreads.append(max(float(_max_abs_diff(
                jax.device_put(o, devices[0]), own[0])) for o in own))
        if traffic["sync_policy"] == "ile" and r > 0 and rel <= traffic[
                "epsilon"]:
            T *= 2
    return np.asarray(losses), norms, spreads, counts


@jax.jit
def _max_abs_diff(a, b):
    return jnp.max(jnp.stack([jnp.max(jnp.abs(x.astype(jnp.float32)
                                              - y.astype(jnp.float32)))
                              for x, y in zip(jax.tree.leaves(a),
                                              jax.tree.leaves(b))]))


@jax.jit
def _scaled(tree, w):
    return jax.tree.map(lambda t: t.astype(jnp.float32) * w, tree)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, tree, w):
    return jax.tree.map(lambda a, t: a + t.astype(jnp.float32) * w, acc, tree)


@jax.jit
def _rel_change(new, old):
    num = sum(jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2)
              for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)))
    den = sum(jnp.sum(b.astype(jnp.float32) ** 2)
              for b in jax.tree.leaves(old))
    return jnp.sqrt(num) / jnp.maximum(jnp.sqrt(den), 1e-12)


@functools.lru_cache(maxsize=None)
def _norms_fn(cfg_json, stacked):
    import json
    cfg = json.loads(cfg_json)

    def norms(params, key):
        start = _init(key, cfg, jnp.float32)
        out = []
        for t, s in zip(jax.tree.leaves(params), jax.tree.leaves(start)):
            d = t.astype(jnp.float32) - (s[None] if stacked else s)
            axes = tuple(range(1 if stacked else 0, d.ndim))
            out.append(jnp.sqrt(jnp.sum(d * d, axis=axes)))
        return out
    return jax.jit(norms)


def change_norms(cfg, seed, params, stacked=False):
    """Per leaf, ``||params - start||`` where ``start`` is the seed's
    weights, made again inside the call; with ``stacked`` one value a
    participant row. Returns a list of numpy arrays in tree order."""
    out = _norms_fn(_cfg_json(cfg), stacked)(params, seed_key(seed))
    return [np.asarray(x, np.float64) for x in jax.device_get(out)]


@jax.jit
def _row_spread(stacked):
    return jnp.max(jnp.stack([jnp.max(jnp.abs(t - t[:1]))
                              for t in jax.tree.leaves(stacked)]))


def row_spread(stacked):
    """Largest difference between any participant row and row 0."""
    return float(jax.device_get(_row_spread(stacked)))

