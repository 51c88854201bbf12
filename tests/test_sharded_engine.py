"""Sharded cohort execution: ``cohort_impl="shard_map"`` must be a layout
transform, not a semantics change.

The in-process tests run on however many devices the suite sees (1 on a
stock CPU runner; 8 in the CI job that sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — the shard_map
path must agree with the single-device vmap path either way.  The
subprocess test forces the 8-virtual-device split regardless of the parent
environment, so the multi-device psum path can't rot on 1-device runners.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PersAFLConfig, apply_buffered_rows, init_server_state
from repro.fl import CohortEngine, DelayModel, FLRun, buffered
from repro.kernels.fused_update.ops import apply_rows_tree


def quad_loss(w, batch):
    r = batch["a"] @ w["w"] - batch["y"]
    return 0.5 * jnp.mean(r ** 2)


def _client_batches(seed, q3=6, m=8, d=5):
    rng = np.random.RandomState(seed)
    return {"a": jnp.asarray(rng.randn(q3, m, d).astype(np.float32)),
            "y": jnp.asarray(rng.randn(q3, m).astype(np.float32))}


def _pcfg(option):
    return PersAFLConfig(option=option, q_local=2, eta=0.05, alpha=0.05,
                         lam=20.0, inner_steps=5, inner_eta=0.02,
                         maml_mode="full")


@pytest.mark.parametrize("option", ["A", "B", "C"])
def test_shard_map_cohort_matches_vmap(option):
    params = {"w": jnp.arange(1.0, 6.0) * 0.1}
    batch_list = [_client_batches(seed) for seed in range(32)]
    e_ref = CohortEngine(_pcfg(option), quad_loss, cohort_impl="vmap")
    e_sh = CohortEngine(_pcfg(option), quad_loss, cohort_impl="shard_map")
    ref = list(e_ref.update_cohort(params, batch_list))
    got = list(e_sh.update_cohort(params, batch_list))
    assert len(got) == 32
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(g["w"]), np.asarray(r["w"]),
                                   rtol=1e-5, atol=1e-5)


def test_shard_map_mean_single_psum_matches_vmap():
    """Masked mean inside the sharded region (one psum per leaf) ==
    unsharded masked mean, non-divisible cohort (padding masked)."""
    params = {"w": jnp.arange(1.0, 6.0) * 0.1}
    batch_list = [_client_batches(seed) for seed in range(13)]
    e_ref = CohortEngine(_pcfg("A"), quad_loss, cohort_impl="vmap")
    e_sh = CohortEngine(_pcfg("A"), quad_loss, cohort_impl="shard_map")
    ref = e_ref.update_cohort_mean(params, batch_list)
    got = e_sh.update_cohort_mean(params, batch_list)
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(ref["w"]),
                               rtol=1e-5, atol=1e-6)


def test_shard_map_bank_feeds_apply_rows():
    """A sharded DeltaBank is consumable by the fused stacked apply."""
    params = {"w": jnp.arange(1.0, 6.0) * 0.1}
    batch_list = [_client_batches(seed) for seed in range(8)]
    engine = CohortEngine(_pcfg("A"), quad_loss, cohort_impl="shard_map")
    bank = engine.update_cohort(params, batch_list)
    weights = np.zeros(bank.capacity, np.float32)
    weights[:8] = 1.0 / 8
    # the apply donates its params tree: hand it a copy, keep ``params``
    out = apply_rows_tree(jax.tree.map(jnp.copy, params), bank.stacked,
                          weights)
    rows = list(CohortEngine(_pcfg("A"), quad_loss,
                             cohort_impl="vmap").update_cohort(params,
                                                               batch_list))
    mean = jax.tree.map(lambda *xs: sum(xs) / len(xs), *rows)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(params["w"] - mean["w"]),
                               rtol=1e-5, atol=1e-6)


def test_shard_map_buffered_simulator_end_to_end():
    """The buffered scheduler runs unchanged on a sharded engine and still
    never materializes deltas to the host."""
    rng = np.random.RandomState(0)
    from repro.data.federated import ClientData
    clients = []
    for _ in range(8):
        x = rng.randn(64, 5).astype(np.float32)
        y = rng.randint(0, 4, 64).astype(np.int32)
        clients.append(ClientData(train_x=x, train_y=y, test_x=x[:8],
                                  test_y=y[:8], classes=(0, 1, 2, 3)))

    def loss(p, b):
        logits = b["images"] @ p["w"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(jax.nn.one_hot(b["labels"], 4) * logp, -1))

    params = {"w": jnp.zeros((5, 4))}
    pcfg = PersAFLConfig(option="A", q_local=2, eta=0.05, buffer_size=4)
    sim = FLRun(clients=clients, loss_fn=loss,
                init_params=params, pcfg=pcfg,
                delays=DelayModel(len(clients), seed=1),
                strategy="persafl", schedule=buffered(),
                batch_size=8, seed=0, cohort_impl="shard_map")
    sim.run(max_rounds=8)
    assert sim.engine.stats["host_materializations"] == 0
    assert int(sim.final_stats["server_rounds"]) >= 8
    for leaf in jax.tree.leaves(sim.state["params"]):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_shard_map_stateful_strategy_matches_vmap():
    """PR 4: stacked client state (SCAFFOLD control variates) threads
    through the sharded cohort exactly like the vmap path — deltas AND
    updated per-client states agree."""
    from repro.fl import strategy
    params = {"w": jnp.arange(1.0, 6.0) * 0.1}
    batch_list = [_client_batches(seed) for seed in range(8)]
    pcfg = _pcfg("A")
    banks = {}
    for impl in ("vmap", "shard_map"):
        strat = strategy("scaffold").bind(pcfg, quad_loss)
        eng = CohortEngine(pcfg, quad_loss, cohort_impl=impl,
                           strategy=strat)
        cstates = [strat.dispatch_state(strat.init_client_state(params))
                   for _ in batch_list]
        banks[impl] = eng.update_cohort(params, batch_list,
                                        cstate_list=cstates)
    for i in range(8):
        np.testing.assert_allclose(
            np.asarray(banks["shard_map"].client_state(i)["w"]),
            np.asarray(banks["vmap"].client_state(i)["w"]),
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(banks["shard_map"][i]["w"]),
                                   np.asarray(banks["vmap"][i]["w"]),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("k", [5, 13, 17])
def test_padding_waste_matches_vmap_at_non_pow2_cohorts(k):
    """Bucket accounting parity: at non-pow2 cohort sizes the shard_map
    bucket (device-count multiple) coincides with the vmap pow2 bucket
    whenever pow2(k) ≥ n_devices, so ``padding_waste`` must match; below
    that the sharded bucket is exactly the device count."""
    params = {"w": jnp.arange(1.0, 6.0) * 0.1}
    batch_list = [_client_batches(seed) for seed in range(k)]
    e_ref = CohortEngine(_pcfg("A"), quad_loss, cohort_impl="vmap")
    e_sh = CohortEngine(_pcfg("A"), quad_loss, cohort_impl="shard_map")
    e_ref.update_cohort(params, batch_list)
    e_sh.update_cohort(params, batch_list)
    pow2 = 1 << (k - 1).bit_length()
    assert e_ref.stats["padding_waste"] == pow2 - k
    if pow2 >= e_sh._ndev:
        assert e_sh.stats["padding_waste"] == e_ref.stats["padding_waste"]
    else:
        assert e_sh.stats["padding_waste"] == e_sh._ndev - k


def test_sharded_buffered_flush_keeps_deltas_on_device():
    """A buffered flush consumed straight from a sharded bank does zero
    host materializations — and materializing a row afterwards counts."""
    params = {"w": jnp.arange(1.0, 6.0) * 0.1}
    batch_list = [_client_batches(seed) for seed in range(6)]
    engine = CohortEngine(_pcfg("A"), quad_loss, cohort_impl="shard_map")
    state = init_server_state(jax.tree.map(jnp.array, params))
    bank = engine.update_cohort(state["params"], batch_list)
    weights = np.zeros(bank.capacity, np.float32)
    weights[:6] = 0.5 / 6
    state = apply_buffered_rows(state, bank.stacked, weights, 6,
                                staleness_max=0)
    jax.block_until_ready(jax.tree.leaves(state["params"])[0])
    assert engine.stats["host_materializations"] == 0
    bank.row(0)
    assert engine.stats["host_materializations"] == 1


_SUBPROC = textwrap.dedent("""
    import jax, numpy as np, jax.numpy as jnp
    assert jax.device_count() == 8, jax.device_count()
    from repro.core import PersAFLConfig
    from repro.fl import CohortEngine

    def quad_loss(w, batch):
        r = batch["a"] @ w["w"] - batch["y"]
        return 0.5 * jnp.mean(r ** 2)

    def batches(seed):
        rng = np.random.RandomState(seed)
        return {"a": jnp.asarray(rng.randn(6, 8, 5).astype(np.float32)),
                "y": jnp.asarray(rng.randn(6, 8).astype(np.float32))}

    pcfg = PersAFLConfig(option="A", q_local=2, eta=0.05)
    params = {"w": jnp.arange(1.0, 6.0) * 0.1}
    bl = [batches(s) for s in range(32)]
    e_sh = CohortEngine(pcfg, quad_loss, cohort_impl="shard_map")
    assert e_sh._ndev == 8
    bank = e_sh.update_cohort(params, bl)
    assert bank.capacity == 32 and bank.capacity % 8 == 0
    ref = list(CohortEngine(pcfg, quad_loss,
                            cohort_impl="vmap").update_cohort(params, bl))
    for r, g in zip(ref, bank):
        np.testing.assert_allclose(np.asarray(g["w"]), np.asarray(r["w"]),
                                   rtol=1e-5, atol=1e-5)
    # non-pow2 cohort on a real 8-way split: device-multiple bucket ==
    # pow2 bucket, so padding accounting matches the vmap path
    e13 = CohortEngine(pcfg, quad_loss, cohort_impl="shard_map")
    e13.update_cohort(params, bl[:13])
    ev13 = CohortEngine(pcfg, quad_loss, cohort_impl="vmap")
    ev13.update_cohort(params, bl[:13])
    assert e13.stats["padding_waste"] == ev13.stats["padding_waste"] == 3
    print("SHARDED8-OK")
""")


def test_shard_map_8_virtual_devices_subprocess():
    """Force an 8-way host-device split and pin shard_map == vmap there."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=540)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SHARDED8-OK" in res.stdout
