"""Launch-layer tests: microbatching equivalence, the train steps, input
specs, and the serve CLI at smoke scale."""
import socket

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_shape, reduce_for_smoke
from repro.launch.specs import (decode_specs, params_struct,
                                prefill_batch_specs, train_batch_specs)
from repro.launch.steps import make_loss, make_train_step, microbatched
from repro.models import api


def test_microbatched_grad_equals_full_grad():
    cfg = reduce_for_smoke(get_config("minitron-8b"))
    key = jax.random.PRNGKey(0)
    params = api.init_params(cfg, key)
    toks = jax.random.randint(key, (4, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    loss_full = make_loss(cfg, 1)
    loss_mb = make_loss(cfg, 4)
    g1 = jax.grad(loss_full)(params, batch)
    g2 = jax.grad(loss_mb)(params, batch)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-3)


def test_train_step_applies_server_update():
    from repro.core import PersAFLConfig
    cfg = reduce_for_smoke(get_config("codeqwen1.5-7b"))
    key = jax.random.PRNGKey(1)
    params = api.init_params(cfg, key)
    pcfg = PersAFLConfig(option="A", q_local=2, eta=0.01, beta=1.0)
    step = make_train_step(cfg, pcfg, n_microbatches=1)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    new_params, metrics = step(params, params, batch)
    # server moved in the -delta direction: w_new = w - beta*delta
    moved = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(new_params)))
    assert moved > 0
    # staleness decoupling: delta computed at stale params, applied to server
    stale = jax.tree.map(lambda x: x + 0.01 if x.ndim >= 2 else x, params)
    new2, _ = step(params, stale, batch)
    diff = sum(float(jnp.sum(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(new_params),
                               jax.tree.leaves(new2)))
    assert diff > 0  # different download point -> different delta


def test_cohort_step_equals_pjit_on_one_device():
    """The FedBuff cohort shard_map round degenerates to the paper-faithful
    step when the cohort has one member (1-device mesh)."""
    from repro.core import PersAFLConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_cohort_train_step
    cfg = reduce_for_smoke(get_config("mamba2-130m"))
    pcfg = PersAFLConfig(option="A", q_local=2, eta=0.01)
    key = jax.random.PRNGKey(0)
    params = api.init_params(cfg, key)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    mesh = make_host_mesh()
    with mesh:
        p1, _ = jax.jit(make_train_step(cfg, pcfg, 1))(params, params, batch)
        p2, _ = jax.jit(make_cohort_train_step(cfg, pcfg, mesh, 1))(
            params, params, batch)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_input_specs_shapes():
    cfg = get_config("internvl2-76b")
    shape = get_shape("train_4k")
    b = train_batch_specs(cfg, shape)
    assert b["tokens"].shape == (256, 4096 - 1024)
    assert b["visual"].shape == (256, 1024, 8192)
    p = prefill_batch_specs(cfg, get_shape("prefill_32k"))
    assert "labels" not in p
    wcfg = get_config("whisper-large-v3")
    wb = train_batch_specs(wcfg, shape)
    assert wb["frames"].shape == (256, 1500, 1280)


def test_decode_specs_cache_struct():
    cfg = reduce_for_smoke(get_config("gemma2-2b"))
    p_struct = params_struct(cfg, cast=False)
    cache, tok, pos = decode_specs(cfg, get_shape("decode_32k"), p_struct)
    k = cache["layers"]["k"]
    assert k.shape[0] == cfg.n_layers and k.shape[2] == 32768
    assert tok.shape == (128, 1) and pos.shape == ()


@pytest.mark.parametrize("flags", [
    ("--personalize", "--requests", "4", "--tokens", "8"),
    ("--listen", "0", "--serve-seconds", "2"),
], ids=["personalize", "listen"])
def test_serve_cli_smoke(run_cli, flags):
    """``python -m repro.launch.serve`` at smoke width: a server-driven
    personalized decode, and a boot of the socket front-end that serves
    for two seconds and shuts down cleanly."""
    run_cli(["-m", "repro.launch.serve", "--arch", "mamba2-130m", "--smoke",
             *flags], timeout=70)


def test_serve_cli_boots_jax_distributed_on_a_2d_mesh(run_cli):
    """The single-process ``jax.distributed`` bring-up (``--coordinator``,
    ``--num-processes``, ``--process-id``) on eight forced host devices,
    serving on the 2x4 ``("cohort", "model")`` mesh."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    out = run_cli(["-m", "repro.launch.serve", "--arch", "mamba2-130m",
                   "--smoke", "--model-axis", "4", "--listen", "0",
                   "--serve-seconds", "2", "--coordinator",
                   f"127.0.0.1:{port}", "--num-processes", "1",
                   "--process-id", "0"], timeout=45, devices=8)
    assert "2-D mesh: cohort=2 × model=4 over 8 devices" in out
