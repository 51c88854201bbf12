"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed next to the CPU backend, so the fused-update
kernels and the full-width personalization cohort compile here exactly as
the chip would compile them.  What it refuses (a block that breaks the
tiling rule, a program that does not fit 16 GB of HBM) fails here at no
chip time.  Nothing runs: these tests say nothing of results or times.

The topology is described inside the module fixture and never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels.fused_update import ops
from repro.models import api

ARCH = "mamba2-130m"
HBM_BYTES = 16e9                    # one v5e chip
STACK_BUDGET = 2 << 30              # bytes of one stacked-delta operand


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compile cache off: an entry
    written for a chip could not be read back on this host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def leaves():
    """mamba2-130m's parameter leaves as (name, shape), full width."""
    shapes = jax.eval_shape(
        lambda: api.init_params(get_config(ARCH), jax.random.PRNGKey(0)))
    return [(jax.tree_util.keystr(p), leaf.shape)
            for p, leaf in jax.tree_util.tree_leaves_with_path(shapes)]


@pytest.fixture
def on_tpu(monkeypatch):
    # ops dispatches on jax.default_backend(), which is the CPU here:
    # steer it to the chip's branch (the compiled Pallas kernel)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel,m", [("apply_scaled", 1),
                                      ("apply_rows", 3),
                                      ("apply_rows", 128),
                                      ("apply_rows_q", 4),
                                      ("apply_rows_q", 64)])
def test_fused_update_compiles_for_v5e(kernel, m, one_chip, leaves, on_tpu):
    """Each fused_update entry compiles to a Mosaic kernel at real leaf
    shapes.  A leaf joins an M-row call when its ``[M, *leaf]`` operand
    fits STACK_BUDGET, as in ``chip_smoke.py``."""
    itemsize = 1 if kernel == "apply_rows_q" else 4
    sel = [(n, s) for n, s in leaves
           if m * int(np.prod(s)) * itemsize <= STACK_BUDGET]
    w = {n: _struct(s, jnp.float32, one_chip) for n, s in sel}
    row = _struct((m,), jnp.float32, one_chip)
    if kernel == "apply_scaled":
        fn = lambda w, d: ops.apply_delta_tree(w, d, 0.5)  # noqa: E731
        args = (w, w)
    elif kernel == "apply_rows":
        fn = ops.apply_rows_tree
        args = (w, {n: _struct((m,) + s, jnp.float32, one_chip)
                    for n, s in sel}, row)
    else:
        fn = ops.apply_rows_q_tree
        args = (w, {n: _struct((m,) + s, jnp.int8, one_chip)
                    for n, s in sel},
                {n: row for n, _ in sel}, row)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= len(sel)


def test_personalization_cohort_fits_one_v5e(one_chip):
    """The full-width 4-user mode-C cohort call (``vmap``, the TPU path)
    compiles for one chip and fits its HBM."""
    from repro.fl import CohortEngine
    from repro.launch import serve
    from repro.serving.batcher import personalize_strategy

    cfg = get_config(ARCH)
    args = serve.build_parser().parse_args(["--arch", ARCH])
    pcfg = serve.personalization_config(args)
    loss = lambda p, b: api.loss_fn(cfg, p, b)        # noqa: E731
    engine = CohortEngine(pcfg, loss, cohort_impl="vmap",
                          strategy=personalize_strategy(pcfg, loss, "C"))
    params = jax.tree.map(
        lambda x: _struct(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: api.init_params(cfg,
                                               jax.random.PRNGKey(0))))
    length = serve._personalize_len(cfg, 128)
    batch = {k: _struct((4, 1, length), jnp.int32, one_chip)
             for k in ("tokens", "labels")}
    compiled = engine._jit_cohort.lower(params, batch).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total
