"""End-to-end behaviour tests: the paper's system over the discrete-event
simulator + data pipeline + optimizers + checkpointing working together."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import MNIST_CNN
from repro.core import PersAFLConfig
from repro.data import make_federated_dataset, sample_batches
from repro.fl import DelayModel, FLRun, immediate, make_personalized_eval, \
    sync_barrier
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn


@pytest.fixture(scope="module")
def fed():
    clients = make_federated_dataset("mnist", n_clients=6,
                                     classes_per_client=3, seed=0)
    params = init_cnn(MNIST_CNN, jax.random.PRNGKey(0))
    loss = lambda p, b: cnn_loss(MNIST_CNN, p, b, train=False)
    acc = lambda p, b: cnn_accuracy(MNIST_CNN, p, b)
    return clients, params, loss, acc


def test_partition_heterogeneity(fed):
    clients, *_ = fed
    for c in clients:
        assert set(np.unique(c.train_y)).issubset(set(c.classes))
        assert len(c.classes) == 3
        assert c.n_train > 0 and len(c.test_y) > 0
    sizes = [c.n_train for c in clients]
    assert max(sizes) > min(sizes)  # unbalanced


def test_sample_batches_fixed_shape(fed):
    clients, *_ = fed
    rng = np.random.RandomState(0)
    for c in clients:
        b = sample_batches(c, rng, 6, 16)
        assert b["images"].shape[:2] == (6, 16)
        assert b["labels"].shape == (6, 16)


def test_async_persafl_improves_accuracy(fed):
    clients, params, loss, acc = fed
    ev = make_personalized_eval(loss, acc, clients, ft_steps=1, ft_lr=0.01)
    acc0 = ev(params)
    pcfg = PersAFLConfig(option="C", q_local=5, eta=0.01, lam=25.0,
                         inner_steps=5, inner_eta=0.02)
    sim = FLRun(clients=clients, loss_fn=loss, init_params=params,
                pcfg=pcfg, delays=DelayModel(len(clients)),
                strategy="persafl", schedule=immediate(),
                batch_size=16, seed=0)
    hist = sim.run(max_rounds=60, eval_every=60, eval_fn=ev)
    assert hist.acc, "no eval recorded"
    assert hist.acc[-1] > acc0 + 0.1, (acc0, hist.acc)
    # staleness is recorded and non-negative
    assert all(s >= 0 for s in hist.staleness)
    assert int(sim.final_stats["server_rounds"]) == 60


def test_async_concurrency_exceeds_sync(fed):
    """Paper Figure 2a: async active-client ratio >> sync."""
    clients, params, loss, acc = fed
    pcfg = PersAFLConfig(option="A", q_local=2, eta=0.02)
    asim = FLRun(clients=clients, loss_fn=loss, init_params=params,
                 pcfg=pcfg, delays=DelayModel(len(clients)),
                 strategy="persafl", schedule=immediate(),
                 batch_size=8, seed=0)
    ah = asim.run(max_rounds=30)
    ssim = FLRun(clients=clients, loss_fn=loss, init_params=params,
                 pcfg=pcfg, delays=DelayModel(len(clients)),
                 strategy="fedavg", schedule=sync_barrier(3), batch_size=8,
                 seed=0)
    sh = ssim.run(max_rounds=6)
    a_ratio = float(np.mean(ah.active_ratio))
    s_ratio = float(np.mean(sh.active_ratio))
    assert a_ratio > s_ratio + 0.2, (a_ratio, s_ratio)
    assert a_ratio > 0.5


@pytest.mark.parametrize("algo", ["fedavg", "perfedavg", "pfedme", "fedprox",
                                  "scaffold"])
def test_sync_baselines_run(fed, algo):
    clients, params, loss, acc = fed
    pcfg = PersAFLConfig(option="A", q_local=2, eta=0.01, alpha=0.01,
                         lam=25.0, inner_steps=3, inner_eta=0.02,
                         maml_mode="full")
    sim = FLRun(clients=clients, loss_fn=loss, init_params=params,
                pcfg=pcfg, delays=DelayModel(len(clients)),
                strategy=algo, schedule=sync_barrier(3), batch_size=8, seed=0)
    ev = make_personalized_eval(loss, acc, clients, ft_steps=1, ft_lr=0.02)
    hist = sim.run(max_rounds=4, eval_every=4, eval_fn=ev)
    assert hist.acc and np.isfinite(hist.acc[-1])


def test_staleness_grows_with_delay_spread(fed):
    """Assumption 1 diagnostics: wider delay spread -> larger max staleness."""
    clients, params, loss, _ = fed
    pcfg = PersAFLConfig(option="A", q_local=2, eta=0.01)

    def run(spread):
        dm = DelayModel(len(clients), seed=1,
                        down_range=(1.0, 1.0 + spread),
                        up_factor_range=(4.0, 4.0 + spread))
        sim = FLRun(clients=clients, loss_fn=loss,
                    init_params=params, pcfg=pcfg, delays=dm,
                    strategy="persafl", schedule=immediate(),
                    batch_size=8, seed=0)
        h = sim.run(max_rounds=40)
        return max(h.staleness)

    assert run(12.0) >= run(0.0)


def test_checkpoint_server_state_roundtrip(fed, tmp_path):
    from repro.checkpoint import load_server_state, save_server_state
    from repro.core import init_server_state
    clients, params, loss, _ = fed
    state = init_server_state(params)
    path = str(tmp_path / "state")
    save_server_state(path, state, meta={"note": "test"})
    back = load_server_state(path)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_optimizers_descend():
    from repro.optim import adam, momentum, sgd, apply_updates
    w = {"w": jnp.ones(4) * 5.0}
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for opt in (sgd(0.1), momentum(0.05), adam(0.3)):
        params = w
        state = opt.init(params)
        for _ in range(50):
            g = jax.grad(loss)(params)
            upd, state = opt.update(g, state, params)
            params = apply_updates(params, upd)
        assert float(loss(params)) < 0.1 * float(loss(w))


def test_staleness_sweep_example_runs_on_the_current_api(run_example):
    """``examples/staleness_sweep.py`` (the paper's Table 1 staleness
    study) at its smoke size, with deprecations raised from the example
    or from ``repro`` fatal."""
    run_example("staleness_sweep.py", timeout=240)
