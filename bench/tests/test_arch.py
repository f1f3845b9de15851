"""A configuration brings its architecture as one file,
``bench/arch/<model_type>.py``: the harness finds it by name and needs no
edit, refuses a ``model_type`` that has no file, and makes every kind of
initial leaf the module may ask for."""
import importlib
import os
import sys
import uuid

import jax
import numpy as np
import pytest

from bench import drive, flops, gen, model, spec
from bench.tests import tiny


@pytest.fixture
def new_arch():
    """Write ``bench/arch/<new name>.py`` with the given source; the file
    and the module go again after the test."""
    made = []

    def write(source):
        name = "a" + uuid.uuid4().hex[:8]
        path = os.path.join(spec.BENCH_DIR, "arch", name + ".py")
        with open(path, "w") as f:
            f.write(source)
        made.append((name, path))
        # the import system may have listed the directory before the write
        importlib.invalidate_caches()
        return name
    yield write
    for name, path in made:
        os.remove(path)
        sys.modules.pop("bench.arch." + name, None)


def test_new_model_type_needs_no_harness_edit(new_arch):
    name = new_arch("from bench.arch.internlm2 import (  # noqa: F401\n"
                    "    loss, param_shapes, program_config,\n"
                    "    train_flops_per_token)\n")
    cfg = tiny.config(model_type=name)
    mod = spec.arch(cfg)
    assert mod.__name__ == "bench.arch." + name
    t = tiny.traffic()
    cell = drive.build(cfg, t, 7, jax.devices()[:1])
    assert isinstance(cell, drive.VmapCell)
    drive.free(cell)
    feed = gen.Feed(*drive.corpus(cfg, t, 7), t, 7)
    losses, norms, spreads, epochs = model.reference_rounds(cfg, t, 7, feed,
                                                            1)
    assert losses.shape == (1, 2) and np.isfinite(losses).all()
    assert epochs == [1] and spreads == [0.0]
    assert flops.train_flops_per_token(cfg, 16) == \
        flops.train_flops_per_token(tiny.config(), 16)


def test_unknown_model_type_names_the_file():
    with pytest.raises(spec.SpecError, match=r"bench/arch/no_such_arch\.py"):
        spec.arch(tiny.config(model_type="no_such_arch"))
    with pytest.raises(spec.SpecError):
        spec.arch(tiny.config(model_type="../internlm2"))


def test_zeros_leaf_is_made_and_counted(new_arch):
    name = new_arch(
        "from bench.arch import internlm2\n"
        "from bench.arch.internlm2 import (  # noqa: F401\n"
        "    loss, program_config, train_flops_per_token)\n\n\n"
        "def param_shapes(cfg):\n"
        "    tree = internlm2.param_shapes(cfg)\n"
        "    tree['router'] = {'bias': ((3, 5), 'zeros')}\n"
        "    return tree\n")
    cfg = tiny.config(model_type=name)
    p = model.init_params(cfg, 2 ** 31 + 3)
    assert p["router"]["bias"].shape == (3, 5)
    assert not np.any(np.asarray(p["router"]["bias"]))
    assert np.all(np.asarray(p["final_norm"]["g"]) == 1.0)
    assert flops.param_count(cfg) == flops.param_count(tiny.config()) + 15
