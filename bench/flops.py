"""The yardstick's arithmetic: model FLOPs a training token, the bytes of
the Eq. 2 pass, and the table of peaks (``bench/peaks.json``).

Model FLOPs count the matrix products of the forward and backward passes,
6 a parameter a token (2 forward, 4 backward), over the weights a token
passes through, plus the attention itself; the configuration's
architecture module (``bench/arch/<model_type>.py``) counts them.
Recomputation under remat is not counted.

The Eq. 2 pass reads K f32 rows of the flat wire buffer and writes one
averaged row, whatever implements it. The buffer holds every leaf from a
``WIRE_BLOCK``-aligned offset and is padded to whole ``WIRE_ROWS`` x
``WIRE_BLOCK`` tiles.
"""
from __future__ import annotations

import json
import math
import os

import jax

from bench import spec
from bench.model import WIRE_BLOCK, WIRE_ROWS, is_spec, param_shapes

PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class UnknownDevice(KeyError):
    """A device kind the peak table does not hold."""


def peaks(device_kind):
    """Peaks of one chip of ``device_kind``; an unknown kind raises."""
    with open(PEAKS_PATH) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in bench/peaks.json "
            f"(known: {sorted(table['devices'])})") from None


def matmul_params(cfg):
    """Weights that enter a matrix product once a token, for a module
    that counts them apart (the dense decoder's helper, which
    ``bench/tests/test_flops.py`` checks by hand). The harness reads
    ``train_flops_per_token`` only."""
    return spec.arch(cfg).matmul_params(cfg)


def train_flops_per_token(cfg, seq_len):
    """Model FLOPs of one training token (forward and backward)."""
    return spec.arch(cfg).train_flops_per_token(cfg, seq_len)


def param_count(cfg):
    """Elements a participant holds."""
    leaves = jax.tree.leaves(param_shapes(cfg), is_leaf=is_spec)
    return sum(math.prod(shape) for shape, _ in leaves)


def wire_n_pad(cfg, block=WIRE_BLOCK, rows=WIRE_ROWS):
    """Elements of one row of the flat wire buffer."""
    leaves = jax.tree.leaves(param_shapes(cfg), is_leaf=is_spec)
    n = sum(-(-math.prod(shape) // block) * block for shape, _ in leaves)
    tile = rows * block
    return -(-n // tile) * tile


def eq2_bytes(cfg, K):
    """HBM bytes of the Eq. 2 pass: K f32 rows read, one written."""
    return (K + 1) * wire_n_pad(cfg) * 4
