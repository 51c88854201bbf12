"""Unit tests for the PersA-FL core (Algorithms 1 & 2, Options A/B/C)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PersAFLConfig, apply_buffered, apply_update,
                        client_update, init_server_state, maml_grad, me_grad,
                        personalize_me, solve_prox, split_batches_for_option)
from repro.core.server import staleness_stats


def quad_loss(w, batch):
    """f(w) = 0.5 ||A w - y||^2 / m  (smooth, known gradients)."""
    r = batch["a"] @ w["w"] - batch["y"]
    return 0.5 * jnp.mean(r ** 2)


@pytest.fixture(scope="module")
def quad():
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (64, 8))
    xstar = jnp.arange(1.0, 9.0)
    return A, A @ xstar, xstar


def _batches(quad, q, seed=0):
    A, y, _ = quad
    idx = np.random.RandomState(seed).choice(64, q * 8).reshape(q, 8)
    return {"a": A[idx], "y": y[idx]}


def test_option_a_delta_telescopes(quad):
    """Δ from client_update == w0 - wQ of the naive Algorithm-2 loop."""
    pcfg = PersAFLConfig(option="A", q_local=4, eta=0.05)
    params = {"w": jnp.zeros(8)}
    batches = _batches(quad, 4)
    delta, _ = client_update(pcfg, quad_loss, params, batches)
    w = params
    for qi in range(4):
        b = jax.tree.map(lambda x: x[qi], batches)
        g = jax.grad(quad_loss)(w, b)
        w = jax.tree.map(lambda ww, gg: ww - pcfg.eta * gg, w, g)
    np.testing.assert_allclose(np.asarray(delta["w"]),
                               np.asarray(params["w"] - w["w"]), rtol=1e-5)


def test_maml_grad_matches_analytic_quadratic(quad):
    """For quadratic f, ∇F(w) = (I-αH) ∇f(w-α∇f(w)) exactly."""
    A, y, _ = quad
    batch = {"a": A, "y": y}
    w = {"w": jnp.ones(8) * 0.5}
    alpha = 0.1
    H = A.T @ A / 64
    g_w = H @ w["w"] - A.T @ y / 64
    adapted = w["w"] - alpha * g_w
    g_ad = H @ adapted - A.T @ y / 64
    expected = (jnp.eye(8) - alpha * H) @ g_ad
    got = maml_grad(quad_loss, w, batch, batch, batch, alpha, mode="full")
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(expected),
                               rtol=1e-4)


def test_maml_variants_approximate_full(quad):
    A, y, _ = quad
    batch = {"a": A, "y": y}
    w = {"w": jnp.ones(8) * 0.3}
    full = maml_grad(quad_loss, w, batch, batch, batch, 0.05, mode="full")
    fo = maml_grad(quad_loss, w, batch, batch, batch, 0.05, mode="fo")
    hf = maml_grad(quad_loss, w, batch, batch, batch, 0.05, mode="hf")
    full_v, fo_v, hf_v = (np.asarray(x["w"]) for x in (full, fo, hf))
    # hf (central difference of a quadratic) is exact up to fp error
    np.testing.assert_allclose(hf_v, full_v, rtol=1e-2, atol=1e-4)
    # fo drops the curvature term: close but not equal
    assert np.linalg.norm(fo_v - full_v) < 0.1 * np.linalg.norm(full_v) + 1e-3
    assert np.linalg.norm(fo_v - full_v) > 0


def test_me_prox_matches_closed_form(quad):
    """θ̂(w) = (H + λI)^{-1} (λ w + A^T y / m) for the quadratic."""
    A, y, _ = quad
    batch = {"a": A, "y": y}
    w = {"w": jnp.zeros(8)}
    lam = 20.0
    H = A.T @ A / 64
    theta_hat = jnp.linalg.solve(H + lam * jnp.eye(8),
                                 lam * w["w"] + A.T @ y / 64)
    theta, nu = solve_prox(quad_loss, w, batch, lam, inner_eta=0.04,
                           inner_steps=300)
    np.testing.assert_allclose(np.asarray(theta["w"]), np.asarray(theta_hat),
                               rtol=1e-3, atol=1e-3)
    assert float(nu) < 1e-2


def test_me_grad_is_lambda_scaled_displacement(quad):
    A, y, _ = quad
    batch = {"a": A, "y": y}
    w = {"w": jnp.ones(8)}
    lam = 25.0
    g, nu = me_grad(quad_loss, w, batch, lam, inner_eta=0.03, inner_steps=200)
    theta, _ = solve_prox(quad_loss, w, batch, lam, inner_eta=0.03,
                          inner_steps=200)
    np.testing.assert_allclose(np.asarray(g["w"]),
                               lam * np.asarray(w["w"] - theta["w"]),
                               rtol=1e-5)


def test_me_nu_decreases_with_inner_steps(quad):
    A, y, _ = quad
    batch = {"a": A, "y": y}
    w = {"w": jnp.ones(8)}
    nus = []
    for k in (1, 5, 25, 100):
        _, nu = me_grad(quad_loss, w, batch, 30.0, inner_eta=0.02,
                        inner_steps=k)
        nus.append(float(nu))
    assert nus == sorted(nus, reverse=True)
    assert nus[-1] < 0.05 * nus[0]  # geometric: (λ−L)-strong convexity


def test_server_apply_and_staleness():
    state = init_server_state({"w": jnp.zeros(4)})
    delta = {"w": jnp.ones(4)}
    state = apply_update(state, delta, beta=0.5, staleness=3)
    state = apply_update(state, delta, beta=0.5, staleness=1)
    np.testing.assert_allclose(np.asarray(state["params"]["w"]), -1.0)
    stats = staleness_stats(state)
    assert int(stats["server_rounds"]) == 2
    assert int(stats["max_staleness"]) == 3
    assert float(stats["mean_staleness"]) == 2.0


def test_buffered_apply_matches_mean_of_singles():
    params = {"w": jnp.zeros(4)}
    d1, d2 = {"w": jnp.ones(4)}, {"w": 3 * jnp.ones(4)}
    s_buf = apply_buffered(init_server_state(params),
                           {"w": d1["w"] + d2["w"]},
                           jnp.asarray(2), beta=1.0, staleness_max=2)
    np.testing.assert_allclose(np.asarray(s_buf["params"]["w"]), -2.0)
    assert int(s_buf["t"]) == 2


def test_buffered_apply_accounts_staleness_sum():
    """Regression: t advances by M per flush, so the buffer's Σ τ must enter
    staleness_sum or mean_staleness under-reports for buffered runs."""
    state = init_server_state({"w": jnp.zeros(4)})
    state = apply_buffered(state, {"w": jnp.ones(4)}, jnp.asarray(3),
                           beta=1.0, staleness_max=4, staleness_sum=2 + 4 + 0)
    state = apply_buffered(state, {"w": jnp.ones(4)}, jnp.asarray(3),
                           beta=1.0, staleness_max=2, staleness_sum=1 + 2 + 0)
    stats = staleness_stats(state)
    assert int(stats["server_rounds"]) == 6
    assert int(stats["max_staleness"]) == 4
    assert float(stats["mean_staleness"]) == pytest.approx(9 / 6)


def test_apply_buffered_rows_matches_summed_apply():
    """The stacked-buffer overload == apply_buffered on the summed deltas,
    with β/M+damping folded into the row weights and padding rows masked."""
    from repro.core import apply_buffered_rows
    params = {"w": jnp.zeros(4)}
    stack = {"w": jnp.stack([jnp.ones(4), 3 * jnp.ones(4),
                             999.0 * jnp.ones(4)])}   # row 2 = padding
    weights = jnp.asarray([0.5, 0.5, 0.0])            # β/M with β=1, M=2
    s_rows = apply_buffered_rows(init_server_state(params), stack, weights,
                                 jnp.asarray(2), staleness_max=2,
                                 staleness_sum=3.0)
    # the row apply donated ``params``: the reference starts from fresh zeros
    s_ref = apply_buffered(init_server_state({"w": jnp.zeros(4)}),
                           {"w": jnp.ones(4) + 3 * jnp.ones(4)},
                           jnp.asarray(2), beta=1.0, staleness_max=2,
                           staleness_sum=3.0)
    np.testing.assert_allclose(np.asarray(s_rows["params"]["w"]),
                               np.asarray(s_ref["params"]["w"]), rtol=1e-6)
    assert int(s_rows["t"]) == int(s_ref["t"]) == 2
    stats = staleness_stats(s_rows)
    assert int(stats["max_staleness"]) == 2
    assert float(stats["mean_staleness"]) == pytest.approx(1.5)


def test_apply_update_staleness_damping():
    """a>0 discounts the server step by (1+tau)^-a (FedAsync-style)."""
    state = init_server_state({"w": jnp.zeros(2)})
    state = apply_update(state, {"w": jnp.ones(2)}, beta=1.0, staleness=3,
                         damping=1.0)
    np.testing.assert_allclose(np.asarray(state["params"]["w"]), -0.25,
                               rtol=1e-6)


def test_split_batches_layout():
    b3q = {"x": jnp.arange(12).reshape(6, 2)}
    a = split_batches_for_option("A", b3q)
    assert a["x"].shape == (2, 2)
    b = split_batches_for_option("B", b3q)
    assert set(b) == {"d", "dp", "dpp"}
    np.testing.assert_array_equal(np.asarray(b["dpp"]["x"]),
                                  np.arange(8, 12).reshape(2, 2))


@pytest.mark.parametrize("option", ["A", "B", "C"])
def test_all_options_descend_on_quadratic(quad, option):
    A, y, xstar = quad
    pcfg = PersAFLConfig(option=option, q_local=5, eta=0.05, alpha=0.05,
                         lam=20.0, inner_steps=30, inner_eta=0.02,
                         maml_mode="full")
    state = init_server_state({"w": jnp.zeros(8)})
    for t in range(60):
        b3q = _batches(quad, 15, seed=t)
        batches = split_batches_for_option(option, b3q)
        delta, _ = client_update(pcfg, quad_loss, state["params"], batches)
        state = apply_update(state, delta, pcfg.beta, staleness=0)
    err = float(jnp.linalg.norm(state["params"]["w"] - xstar))
    assert err < 0.5, f"option {option} err={err}"


def test_personalize_me_moves_toward_local_optimum(quad):
    A, y, _ = quad
    batch = {"a": A, "y": y}
    w = {"w": jnp.zeros(8)}
    theta = personalize_me(quad_loss, w, batch, lam=10.0, inner_eta=0.03,
                           inner_steps=100)
    assert quad_loss(theta, batch) < quad_loss(w, batch)
