"""Property-based tests (hypothesis) on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# no reason= kwarg: that importorskip parameter needs pytest>=8.2, and the
# dev floor is 7.0 — hypothesis itself comes from requirements-dev.txt
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (PersAFLConfig, apply_update, client_update,
                        init_server_state, solve_prox)
from repro.models.moe import expert_capacity, moe_forward
from repro.configs import get_config, reduce_for_smoke

SET = settings(max_examples=20, deadline=None)


def quad_loss(w, batch):
    r = batch["a"] @ w["w"] - batch["y"]
    return 0.5 * jnp.mean(r ** 2)


@st.composite
def quadratic(draw):
    d = draw(st.integers(2, 6))
    m = draw(st.integers(8, 24))
    seed = draw(st.integers(0, 2 ** 16))
    rng = np.random.RandomState(seed)
    A = rng.randn(m, d).astype(np.float32)
    y = rng.randn(m).astype(np.float32)
    return jnp.asarray(A), jnp.asarray(y)


@SET
@given(quadratic(), st.floats(8.0, 64.0))
def test_prox_contraction_toward_w_as_lambda_grows(q, lam):
    """Lemma-6 regime: as λ→∞, θ̃(w) → w (‖θ−w‖ ≤ ‖∇f(w)‖/(λ−L))."""
    A, y = q
    batch = {"a": A, "y": y}
    w = {"w": jnp.zeros(A.shape[1])}
    t_small, _ = solve_prox(quad_loss, w, batch, lam, 1.0 / (4 * lam), 200)
    t_big, _ = solve_prox(quad_loss, w, batch, 4 * lam, 1.0 / (16 * lam), 200)
    d_small = float(jnp.linalg.norm(t_small["w"] - w["w"]))
    d_big = float(jnp.linalg.norm(t_big["w"] - w["w"]))
    assert d_big <= d_small + 1e-5


@SET
@given(quadratic(), st.integers(1, 6), st.floats(0.001, 0.05))
def test_delta_scales_linearly_with_eta_first_order(q, q_local, eta):
    """For Option A, Δ(η)/η → Σ∇f as η→0 (telescoping consistency)."""
    A, y = q
    batches = {"a": jnp.stack([A] * q_local), "y": jnp.stack([y] * q_local)}
    w = {"w": jnp.ones(A.shape[1])}
    d1, _ = client_update(PersAFLConfig(option="A", q_local=q_local, eta=eta),
                          quad_loss, w, batches)
    d2, _ = client_update(PersAFLConfig(option="A", q_local=q_local,
                                        eta=eta / 2), quad_loss, w, batches)
    # halving eta at least halves the delta norm (up to curvature terms)
    n1 = float(jnp.linalg.norm(d1["w"]))
    n2 = float(jnp.linalg.norm(d2["w"]))
    assert n2 <= 0.75 * n1 + 1e-6


@SET
@given(st.integers(0, 10), st.integers(0, 10), st.floats(0.1, 2.0))
def test_server_counter_and_staleness_accounting(s1, s2, beta):
    state = init_server_state({"w": jnp.zeros(3)})
    state = apply_update(state, {"w": jnp.ones(3)}, beta, s1)
    state = apply_update(state, {"w": jnp.ones(3)}, beta, s2)
    assert int(state["t"]) == 2
    assert int(state["staleness_max"]) == max(s1, s2)
    np.testing.assert_allclose(np.asarray(state["params"]["w"]),
                               -2 * beta, rtol=1e-6)


@SET
@given(st.integers(2, 64), st.integers(2, 8), st.integers(1, 4),
       st.floats(1.0, 2.0))
def test_expert_capacity_bounds(tokens, experts, topk, cf):
    from repro.configs.base import MoEConfig
    mo = MoEConfig(n_experts=experts, top_k=min(topk, experts),
                   expert_d_ff=8, capacity_factor=cf)
    C = expert_capacity(tokens, mo)
    assert C >= mo.top_k
    assert C * experts >= tokens * mo.top_k  # can host all assignments at cf>=1


@SET
@given(st.integers(0, 2 ** 16))
def test_moe_forward_finite_and_bounded(seed):
    cfg = reduce_for_smoke(get_config("granite-moe-1b-a400m"))
    key = jax.random.PRNGKey(seed)
    from repro.models.moe import init_moe
    p = init_moe(key, cfg)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    out, aux = moe_forward(cfg, p, x)
    assert out.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(aux) >= 0.0


@SET
@given(st.integers(0, 2 ** 16), st.integers(1, 3))
def test_checkpoint_roundtrip(seed, depth):
    from repro.checkpoint import load_pytree, save_pytree
    import tempfile, os
    rng = np.random.RandomState(seed)

    def build(d):
        if d == 0:
            return rng.randn(*rng.randint(1, 4, size=2)).astype(np.float32)
        return {f"k{i}": build(d - 1) for i in range(2)} if rng.rand() < 0.7 \
            else [build(d - 1), build(d - 1)]

    tree = {"root": build(depth), "scalar": np.float32(rng.randn())}
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "ckpt")
        save_pytree(path, tree)
        back = load_pytree(path)
    flat1 = jax.tree.leaves(tree)
    flat2 = jax.tree.leaves(back)
    assert len(flat1) == len(flat2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- admission_weights invariants (serving-ring apply math) -----------------

@st.composite
def admissions(draw):
    """Random (capacity, [(row, tau), ...]) with duplicates allowed."""
    capacity = draw(st.integers(1, 16))
    rows = draw(st.lists(
        st.tuples(st.integers(0, capacity - 1), st.integers(0, 6)),
        min_size=1, max_size=12))
    return capacity, rows


@SET
@given(admissions(), st.floats(0.1, 2.0), st.floats(0.0, 2.0))
def test_admission_weights_accumulate_per_admission(adm, beta, damping):
    """w == Σ over admissions of β/count·(1+τ)^{-damping} onto each slot —
    duplicates ACCUMULATE (the w[idx] = wt overwrite bug's invariant)."""
    from repro.core import admission_weights
    capacity, rows = adm
    count = len(rows)
    w = admission_weights(capacity, rows, beta=beta, count=count,
                          damping=damping)
    expect = np.zeros(capacity, np.float64)
    for idx, tau in rows:
        expect[idx] += beta / count * (1.0 + tau) ** (-damping)
    np.testing.assert_allclose(w, expect.astype(np.float32), rtol=1e-5)


@SET
@given(admissions(), st.floats(0.1, 2.0), st.integers(0, 3))
def test_admission_weights_tau_max_zeroes_stale_rows(adm, beta, tau_max):
    """Rows past the bound contribute exactly zero; within the bound the
    total weight never exceeds β (bounded-staleness admission)."""
    from repro.core import admission_weights
    capacity, rows = adm
    count = len(rows)
    w = admission_weights(capacity, rows, beta=beta, count=count,
                          tau_max=tau_max)
    only_stale = [i for i in range(capacity)
                  if all(t > tau_max for r, t in rows if r == i)]
    assert all(w[i] == 0.0 for i in only_stale)
    # damping <= 1 per row and #admitted <= count => sum(w) <= beta
    assert float(np.sum(w)) <= beta + 1e-5


@SET
@given(st.integers(0, 2 ** 16), st.integers(2, 4), st.floats(0.1, 1.5),
       st.floats(0.0, 1.0))
def test_ring_advance_composes_like_sequential_oracle(seed, windows, beta,
                                                      damping):
    """DeltaRing.advance over several windows == a numpy step-by-step
    oracle applying the same admitted/capped/duplicate/stale row mix."""
    from repro.core import init_server_state
    from repro.fl.engine import DeltaBank
    from repro.serving import DeltaRing

    rng = np.random.RandomState(seed)
    d = 3
    params = {"w": jnp.asarray(rng.randn(d).astype(np.float32))}
    ring = DeltaRing(params, windows=windows, user_cap=2)
    state = init_server_state(params)
    oracle = np.asarray(params["w"], np.float64)

    for _ in range(3):
        k = rng.randint(1, 4)
        stack = rng.randn(k, d).astype(np.float32)
        bank = DeltaBank(stacked={"w": jnp.asarray(stack)}, k=k)
        ring.retain(bank)
        # admissions: random rows, random staleness, one duplicate
        reqs = [(rng.randint(0, k), int(rng.randint(0, windows + 1)))
                for _ in range(rng.randint(1, 4))]
        reqs.append(reqs[0])               # duplicate slot, same user
        verdicts = [ring.admit_row(f"u{i % 2}", bank, r, t)
                    for i, (r, t) in enumerate(reqs)]
        admitted = [(r, t) for (r, t), v in zip(reqs, verdicts)
                    if v == "admitted"]
        state = ring.advance(state, beta=beta, damping=damping)
        m = len(admitted)
        for r, t in admitted:
            oracle -= (beta / m * (1.0 + t) ** (-damping)
                       * stack[r].astype(np.float64))
    np.testing.assert_allclose(np.asarray(state.params["w"]), oracle,
                               rtol=1e-4, atol=1e-5)


@SET
@given(st.integers(0, 2 ** 16), st.integers(1, 48))
def test_flash_attention_property_random_shapes(seed, s_mult):
    """Kernel == oracle on randomly drawn (block-aligned) shapes."""
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    from repro.kernels.flash_attention.ref import attention_ref
    rng = np.random.RandomState(seed)
    S = 32 * (1 + seed % 4)
    Hkv = int(rng.choice([1, 2]))
    Hq = Hkv * int(rng.choice([1, 2, 4]))
    hd = int(rng.choice([16, 32]))
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, S, Hq, hd))
    k = jax.random.normal(ks[1], (1, S, Hkv, hd))
    v = jax.random.normal(ks[2], (1, S, Hkv, hd))
    out = flash_attention_fwd(q, k, v, block_q=32, block_k=32, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                               rtol=1e-4)


@st.composite
def robust_case(draw):
    """Random admission set: norms (some non-finite), rows, knobs."""
    cap = draw(st.integers(2, 24))
    k = draw(st.integers(1, cap))
    seed = draw(st.integers(0, 2 ** 16))
    rng = np.random.RandomState(seed)
    norms = np.abs(rng.randn(cap)) * 10.0 ** rng.randint(-1, 3, cap)
    bad = rng.rand(cap) < 0.2
    norms[bad] = rng.choice([np.nan, np.inf], bad.sum())
    idxs = rng.choice(cap, k, replace=False)
    rows = [(int(i), int(rng.randint(0, 4))) for i in idxs]
    beta = draw(st.floats(0.1, 2.0))
    return cap, rows, norms, beta


@SET
@given(robust_case(), st.floats(0.0, 1.5))
def test_robust_clip_weights_vs_oracle(case, damping):
    """Clip == plain admission_weights with a per-row norm cap: every
    weight equals β/count·(1+τ)^-a scaled by min(1, c/norm), zero on
    non-finite rows, and keep mirrors finiteness."""
    from repro.core import robust_admission_weights
    cap, rows, norms, beta = case
    w, keep, info = robust_admission_weights(
        cap, rows, norms, beta=beta, count=len(rows), damping=damping,
        method="clip")
    np.testing.assert_array_equal(keep, np.isfinite(norms))
    finite = [(i, t) for i, t in rows if np.isfinite(norms[i])]
    assert info["nonfinite"] == len(rows) - len(finite)
    oracle = np.zeros(cap)
    if finite:
        c = info["clip_norm"]
        assert c == pytest.approx(
            2.0 * np.median([norms[i] for i, _ in finite]))
        for i, t in finite:
            wt = beta / len(rows) * (1.0 + t) ** (-damping)
            if norms[i] > c and norms[i] > 0.0:
                wt *= c / norms[i]
            oracle[i] += wt
    np.testing.assert_allclose(w, oracle, rtol=1e-5, atol=1e-12)
    # clipping never increases any admission's contribution norm
    contrib = w * np.where(np.isfinite(norms), norms, 0.0)
    if finite and info["clip_norm"] > 0:
        assert contrib.max() <= beta / len(rows) * info["clip_norm"] \
            * max((1.0 + t) ** (-damping) for _, t in finite) * (1 + 1e-6)


@SET
@given(robust_case(), st.floats(0.05, 0.45))
def test_robust_trim_weights_vs_oracle(case, trim_frac):
    """Trim == numpy trimmed mean over the finite admissions: the norm
    tails get weight 0, survivors split β evenly, ≥1 survives."""
    from repro.core import robust_admission_weights
    cap, rows, norms, beta = case
    w, keep, info = robust_admission_weights(
        cap, rows, norms, beta=beta, count=len(rows), method="trim",
        trim_frac=trim_frac)
    finite = [(i, t) for i, t in rows if np.isfinite(norms[i])]
    if not finite:
        assert not w.any()
        return
    k = len(finite)
    cut = int(np.ceil(trim_frac * k))
    if 2 * cut >= k:
        cut = (k - 1) // 2
    order = np.argsort([norms[i] for i, _ in finite], kind="stable")
    survivors = [finite[j][0] for j in order[cut: k - cut]]
    assert len(survivors) >= 1
    assert info["trimmed"] == k - len(survivors)
    oracle = np.zeros(cap)
    for i in survivors:
        oracle[i] += beta / len(survivors)
    np.testing.assert_allclose(w, oracle, rtol=1e-6, atol=1e-12)
    # total admitted mass is exactly β (a trimmed MEAN, not a down-scale)
    assert w.sum() == pytest.approx(beta, rel=1e-5)
