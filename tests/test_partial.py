"""Partial-model personalization (head-only deltas end-to-end).

SubsetSpec spellings/transforms, pruned-form closure under the npz codec,
subset deltas from the personalize strategy (backbone frozen), subset
window applies (backbone bit-parity), the PersonalizationServer serving
subset heads with shrunken ring residency, transport subset negotiation,
the sharded cohort path on subset-shaped deltas, and subset-restricted
personalized evaluation.
"""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PersAFLConfig, SubsetSpec, merge_subset
from repro.core.moreau import solve_prox
from repro.core.subset import leaf_paths, subset_like
from repro.serving import PersonalizationServer
from repro.serving.transport import (AsyncTransportClient, TransportError,
                                     TransportServer, decode_pytree,
                                     encode_pytree)


def loss(p, b):
    logits = b["x"] @ p["w"] + p["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(jax.nn.one_hot(b["y"], 4) * logp, -1))


def user_batch(seed, n=8, d=5):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(n, d).astype(np.float32),
            "y": rng.randint(0, 4, n).astype(np.int32)}


def _params(seed=0, d=5):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(0.1 * rng.randn(d, 4).astype(np.float32)),
            "b": jnp.zeros((4,))}


def _pcfg(**kw):
    base = dict(option="C", lam=20.0, inner_steps=5, inner_eta=0.05,
                alpha=0.1, beta=0.5)
    base.update(kw)
    return PersAFLConfig(**base)


def _close(a, b, **kw):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=kw.get("rtol", 1e-5),
                                   atol=kw.get("atol", 1e-6))


def _cnn_tree():
    """fig2-CNN-shaped nested tree: conv stack + two FC layers."""
    rng = np.random.RandomState(3)
    layer = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    return {"conv": [{"w": layer(3, 3, 1, 4), "b": layer(4)},
                     {"w": layer(3, 3, 4, 8), "b": layer(8)}],
            "fc": [{"w": layer(32, 16), "b": layer(16)},
                   {"w": layer(16, 10), "b": layer(10)}]}


# -- SubsetSpec spellings and transforms ------------------------------------

def test_resolve_accepts_every_spelling():
    tree = _cnn_tree()
    want = SubsetSpec(("fc/#1",))
    assert SubsetSpec.resolve("fc/#1", tree) == want
    assert SubsetSpec.resolve(("fc/#1",), tree) == want
    assert SubsetSpec.resolve(["fc/#1"], tree) == want
    assert SubsetSpec.resolve(want, tree) is want
    assert SubsetSpec.resolve(None) is None
    # pytree bool mask spelling resolves to the matched leaf paths
    mask = jax.tree.map(lambda _: False, tree)
    mask["fc"][1] = {"w": True, "b": True}
    got = SubsetSpec.resolve(mask, tree)
    assert set(got.prefixes) == {"fc/#1/b", "fc/#1/w"}
    assert got.validate(tree) == want.validate(tree)


def test_resolve_rejects_typos_and_empty():
    tree = _cnn_tree()
    with pytest.raises(ValueError, match="matches no param leaf"):
        SubsetSpec.resolve("fc/#7", tree)
    with pytest.raises(ValueError, match="no leaves"):
        SubsetSpec.resolve("", tree)
    with pytest.raises(TypeError):
        SubsetSpec.resolve(42)


def test_extract_merge_mask_roundtrip():
    tree = _cnn_tree()
    spec = SubsetSpec.resolve("fc/#1")
    sub = spec.extract(tree)
    # pruned form: conv dropped entirely, fc keeps a gap-None for slot 0
    assert set(sub) == {"fc"}
    assert sub["fc"][0] is None and set(sub["fc"][1]) == {"b", "w"}
    assert leaf_paths(sub) == ("fc/#1/b", "fc/#1/w")
    # merge restores the original bit-for-bit
    merged = merge_subset(tree, sub)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(merged)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # a modified subset lands ONLY on its own leaves
    sub2 = jax.tree.map(lambda x: x + 1.0, sub)
    merged2 = merge_subset(tree, sub2)
    assert np.array_equal(np.asarray(merged2["conv"][0]["w"]),
                          np.asarray(tree["conv"][0]["w"]))
    assert np.allclose(np.asarray(merged2["fc"][1]["w"]),
                       np.asarray(tree["fc"][1]["w"]) + 1.0)
    # mask mirrors the full structure with Python bools
    mask = spec.mask(tree)
    assert mask["fc"][1] == {"w": True, "b": True}
    assert mask["fc"][0] == {"w": False, "b": False}
    # subset_like re-arranges full-tree leaves into the pruned structure
    like = subset_like(tree, sub)
    assert jax.tree_util.tree_structure(like) \
        == jax.tree_util.tree_structure(sub)


def test_pruned_form_closed_under_npz_codec():
    """decode(encode(extract(t))) must have extract(t)'s exact treedef —
    the property that lets bank rows, checkpoints and wire frames share
    one structure (gap-preserving list rebuild in checkpoint.store)."""
    tree = _cnn_tree()
    for prefixes in ("fc/#1", "conv/#0/b,fc/#1/w", "fc"):
        sub = SubsetSpec.resolve(prefixes).extract(tree)
        back = decode_pytree(encode_pytree(sub))
        assert jax.tree_util.tree_structure(back) \
            == jax.tree_util.tree_structure(sub), prefixes
        for a, b in zip(jax.tree.leaves(sub), jax.tree.leaves(back)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_descriptor_roundtrip():
    tree = _cnn_tree()
    spec = SubsetSpec.resolve("fc/#1")
    desc = spec.descriptor(tree)
    assert desc == ["fc/#1/b", "fc/#1/w"]
    spec2 = SubsetSpec.from_descriptor(desc)
    assert spec2.validate(tree) == spec.validate(tree)
    # descriptors survive JSON (the checkpoint meta / wire header path)
    import json
    assert SubsetSpec.resolve(json.loads(json.dumps(desc)), tree) \
        .validate(tree) == spec.validate(tree)


# -- strategy: subset deltas against a frozen backbone ----------------------

def test_mode_b_subset_delta_is_alpha_grad_of_subset():
    from repro.serving.batcher import personalize_strategy
    params = _params()
    pcfg = _pcfg()
    batch = user_batch(0)
    strat = personalize_strategy(pcfg, loss, "B", personal_subset=("b",))
    delta, _, _ = strat.local_update(params, batch, None)
    assert set(delta) == {"b"}                        # pruned: no backbone
    g = jax.grad(lambda b_sub, bt: loss(merge_subset(params, b_sub), bt))(
        {"b": params["b"]}, batch)
    _close(delta, jax.tree.map(lambda x: pcfg.alpha * x, g))


def test_mode_c_subset_delta_is_prox_gap_with_frozen_backbone():
    from repro.serving.batcher import personalize_strategy
    params = _params()
    pcfg = _pcfg()
    batch = user_batch(1)
    strat = personalize_strategy(pcfg, loss, "C", personal_subset=("b",))
    delta, _, _ = strat.local_update(params, batch, None)
    theta, _ = solve_prox(
        lambda s, bt: loss(merge_subset(params, s), bt),
        {"b": params["b"]}, batch, pcfg.lam, pcfg.inner_eta,
        pcfg.inner_steps)
    _close(delta, {"b": params["b"] - theta["b"]})


# -- subset window apply: backbone bit-parity -------------------------------

def test_subset_apply_rows_freezes_backbone_bitwise():
    from repro.core import apply_admitted_rows, init_server_state
    params = _params()
    state = init_server_state(params)
    rng = np.random.RandomState(5)
    stack = {"b": jnp.asarray(rng.randn(4, 4).astype(np.float32))}
    weights = jnp.asarray([0.25, 0.25, 0.0, 0.0])
    new = apply_admitted_rows(state, stack, weights, 2, staleness_max=0)
    # backbone leaf: BIT-identical, not approximately equal
    assert np.array_equal(np.asarray(new.params["w"]),
                          np.asarray(params["w"]))
    expect_b = np.asarray(params["b"]) \
        - 0.25 * (np.asarray(stack["b"][0]) + np.asarray(stack["b"][1]))
    np.testing.assert_allclose(np.asarray(new.params["b"]), expect_b,
                               rtol=1e-6, atol=1e-7)
    assert int(new["t"]) == 2


# -- PersonalizationServer end-to-end ---------------------------------------

def test_server_serves_subset_heads_end_to_end():
    params = _params()
    pcfg = _pcfg()
    srv = PersonalizationServer(params, loss, pcfg,
                                personal_subset=("b",), windows=3)
    full = PersonalizationServer(params, loss, pcfg, windows=3)
    w0 = np.asarray(params["w"])

    tickets = [srv.submit(f"u{i}", user_batch(i)) for i in range(4)]
    srv.flush()
    for i, t in enumerate(tickets):
        head = srv.poll(t)
        assert set(head) == {"b"}                     # subset pytree
        theta, _ = solve_prox(
            lambda s, bt: loss(merge_subset(params, s), bt),
            {"b": params["b"]}, user_batch(i), pcfg.lam, pcfg.inner_eta,
            pcfg.inner_steps)
        _close(head, theta)
    # stacked heads carry the subset structure too
    stacked = srv.stacked_heads([t.user for t in tickets])
    assert set(stacked) == {"b"} and stacked["b"].shape[0] == 4

    # ring residency: a subset row is head-sized, and the full-model
    # server's row is strictly larger
    for i in range(4):
        full.submit(f"u{i}", user_batch(i))
    full.flush()
    assert srv.stats["ring_row_bytes"] == 4 * 4       # b: f32[4]
    assert srv.stats["ring_bytes_per_user"] == 2 * srv.ring.row_nbytes
    assert full.stats["ring_row_bytes"] == 4 * (5 * 4 + 4)
    assert full.stats["ring_bytes_per_user"] \
        > srv.stats["ring_bytes_per_user"]

    # several window advances: subset applies move b, never touch w
    for k in range(3):
        srv.submit("fresh", user_batch(10 + k))
        srv.advance_window()
        assert np.array_equal(np.asarray(srv.params["w"]), w0)  # bitwise
    assert not np.array_equal(np.asarray(srv.params["b"]),
                              np.asarray(params["b"]))
    assert srv.stats["host_materializations"] == 0


def test_server_subset_straggler_uses_merged_snapshot():
    """A straggler's cohort runs against snapshot(stamp) — in subset mode
    that is merge(backbone, stored subset), and since subset applies never
    move the backbone the recombination is exact."""
    pcfg = _pcfg(staleness_damping=0.5)
    params = _params()
    srv = PersonalizationServer(params, loss, pcfg,
                                personal_subset=("b",), windows=3)
    srv.submit("a", user_batch(1))
    srv.flush()
    srv.submit("late", user_batch(2))                 # stamped window 0
    srv.advance_window(flush=False)
    params1 = jax.tree.map(np.asarray, srv.params)
    t_late = srv.submit("late2", user_batch(3))       # fresh in window 1
    srv.advance_window()                              # drains both
    assert srv.stats["ring_stragglers"] == 1
    # the straggler's head solves against the ORIGINAL window-0 params
    theta0, _ = solve_prox(
        lambda s, bt: loss(merge_subset(params, s), bt),
        {"b": params["b"]}, user_batch(2), pcfg.lam, pcfg.inner_eta,
        pcfg.inner_steps)
    _close(srv.head("late"), theta0)
    # and the fresh one against window-1 params
    theta1, _ = solve_prox(
        lambda s, bt: loss(merge_subset(
            jax.tree.map(jnp.asarray, params1), s), bt),
        {"b": jnp.asarray(params1["b"])}, user_batch(3), pcfg.lam,
        pcfg.inner_eta, pcfg.inner_steps)
    _close(srv.poll(t_late), theta1)


def test_server_subset_save_restore_roundtrip(tmp_path):
    pcfg = _pcfg()
    srv = PersonalizationServer(_params(), loss, pcfg,
                                personal_subset=("b",), windows=3)
    users = [f"u{i}" for i in range(3)]
    for w in range(2):
        for i, u in enumerate(users):
            srv.submit(u, user_batch(10 * w + i))
        srv.advance_window()
    heads_before = {u: jax.tree.map(np.asarray, srv.head(u))
                    for u in users}
    path = str(tmp_path / "subset_state")
    srv.save(path)

    srv2 = PersonalizationServer.restore(path, loss, pcfg)
    # the subset survives the round trip (resolved descriptor form)
    assert srv2.personal_subset is not None
    assert srv2.personal_subset.validate(srv2.params) \
        == srv.personal_subset.validate(srv.params)
    _close(srv2.params, srv.params)
    # subset snapshots round-trip with their pruned structure
    for w in srv.ring._snapshots:
        assert jax.tree_util.tree_structure(srv2.ring.subset_snapshot(w)) \
            == jax.tree_util.tree_structure(srv.ring.subset_snapshot(w))
        _close(srv2.ring.snapshot(w), srv.ring.snapshot(w))
    for u in users:
        got = srv2.head(u)
        assert set(got) == {"b"}
        _close(got, heads_before[u])
    # the restored server keeps serving subset heads
    t = srv2.submit("fresh", user_batch(42))
    srv2.advance_window()
    assert t.status == "done"
    assert np.array_equal(np.asarray(srv2.params["w"]),
                          np.asarray(srv.params["w"]))


# -- transport subset negotiation -------------------------------------------

def test_transport_subset_negotiation_and_heads():
    params = _params()
    pcfg = _pcfg()

    ref = PersonalizationServer(params, loss, pcfg,
                                personal_subset=("b",), max_pending=64)
    t_ref = ref.submit("u0", user_batch(0))
    ref.flush()
    expected = jax.tree.map(np.asarray, ref.poll(t_ref))

    async def go():
        srv = PersonalizationServer(params, loss, pcfg,
                                    personal_subset=("b",), max_pending=64)
        ts = await TransportServer(srv, flush_ms=60_000.0).start()
        c = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        # a client that does NOT declare subset_ok gets a typed ERR on
        # every head-carrying op (old clients must not silently treat a
        # partial pytree as the full model)
        for hdr in ({"op": "SUBMIT", "user": "x", "mode": "C"},
                    {"op": "POLL", "ticket": 0},
                    {"op": "HEAD", "user": "x"}):
            with pytest.raises(TransportError) as ei:
                await c._rpc(hdr, encode_pytree(user_batch(0))
                             if hdr["op"] == "SUBMIT" else b"")
            assert ei.value.code == "subset_unsupported"
        # the subset-aware client path: served heads are subset pytrees
        # and the reply header stamps the resolved leaf descriptor
        tid = await c.submit("u0", user_batch(0))
        await c.flush()
        head = await c.poll(tid, wait_ms=10_000)
        assert c.last_subset == ["b"]
        again = await c.head("u0")
        stats = await c.stats()
        await c.close()
        await ts.stop()
        return head, again, stats

    head, again, stats = asyncio.run(go())
    assert set(head) == {"b"}
    for got in (head, again):
        for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(got)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert stats["host_materializations"] == 0
    # a client can reconstruct its full personalized model from the
    # descriptor + shared backbone
    merged = merge_subset(params, head)
    assert np.array_equal(np.asarray(merged["w"]), np.asarray(params["w"]))
    assert np.array_equal(np.asarray(merged["b"]), np.asarray(head["b"]))


def test_transport_full_model_server_ignores_subset_negotiation():
    """A full-model server never refuses: subset_ok is forward-compatible
    and the reply carries no subset key."""
    async def go():
        srv = PersonalizationServer(_params(), loss, _pcfg(),
                                    max_pending=64)
        ts = await TransportServer(srv, flush_ms=60_000.0).start()
        c = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        # no subset_ok: still fine against a full-model server
        h, _ = await c._rpc({"op": "SUBMIT", "user": "u", "mode": "C"},
                            encode_pytree(user_batch(0)))
        assert h["op"] == "OK"
        await c.flush()
        head = await c.poll(int(h["ticket"]), wait_ms=10_000)
        assert set(head) == {"b", "w"}
        assert c.last_subset is None
        await c.close()
        await ts.stop()

    asyncio.run(go())


# -- sharded cohort path on subset deltas -----------------------------------

def test_shard_map_cohort_handles_subset_deltas():
    """The stateless shard_map cohort body must carry pruned subset
    outputs (pytree-prefix out_specs) and agree with the vmap path."""
    from repro.fl.engine import CohortEngine
    from repro.serving.batcher import personalize_strategy
    params = _params()
    pcfg = _pcfg()
    batches = [user_batch(i) for i in range(8)]
    e_ref = CohortEngine(pcfg, loss, cohort_impl="vmap",
                         strategy=personalize_strategy(
                             pcfg, loss, "C", personal_subset=("b",)))
    e_sh = CohortEngine(pcfg, loss, cohort_impl="shard_map",
                        strategy=personalize_strategy(
                            pcfg, loss, "C", personal_subset=("b",)))
    ref = e_ref.update_cohort(params, batches)
    got = e_sh.update_cohort(params, batches)
    assert set(got.stacked) == {"b"}
    np.testing.assert_allclose(np.asarray(got.stacked["b"]),
                               np.asarray(ref.stacked["b"]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("cohort_impl", ["shard_map"])
def test_server_subset_sharded_serving(cohort_impl):
    """Subset serving over the sharded cohort path (exercised with 8
    virtual devices in the CI partial-smoke job; degenerates to a
    1-device mesh elsewhere)."""
    params = _params()
    pcfg = _pcfg()
    srv = PersonalizationServer(params, loss, pcfg,
                                cohort_impl=cohort_impl,
                                personal_subset=("b",))
    tickets = [srv.submit(f"u{i}", user_batch(i)) for i in range(5)]
    srv.flush()
    for i, t in enumerate(tickets):
        theta, _ = solve_prox(
            lambda s, bt: loss(merge_subset(params, s), bt),
            {"b": params["b"]}, user_batch(i), pcfg.lam, pcfg.inner_eta,
            pcfg.inner_steps)
        _close(srv.poll(t), theta)
    srv.advance_window()
    assert np.array_equal(np.asarray(srv.params["w"]),
                          np.asarray(params["w"]))
    assert srv.stats["host_materializations"] == 0


def test_head_only_rows_are_20x_smaller_than_full_rows():
    """At 32 features a bias-only row is 33x smaller than a full-model
    row (132 floats against 4), so a head-only ring holds at least 20x
    the users in the same device memory."""
    params = _params(d=32)
    per_user = {}
    for name, subset in (("full", None), ("head_only", ("b",))):
        srv = PersonalizationServer(params, loss, _pcfg(),
                                    personal_subset=subset)
        for i in range(4):
            srv.submit(f"u{i}", user_batch(i, d=32))
        srv.flush()
        per_user[name] = srv.stats["ring_bytes_per_user"]
    assert per_user["full"] >= 20 * per_user["head_only"], per_user


def test_head_only_personalization_tracks_full_model_accuracy():
    """On the fig2 MNIST configuration (10 clients, 5 classes each), after
    20 rounds of persafl option C, fine-tuning only the last FC layer
    personalizes to within 0.1 accuracy of fine-tuning the whole model:
    the head carries the personalization."""
    from repro.configs.paper_models import MNIST_CNN
    from repro.data.federated import make_federated_dataset
    from repro.fl import DelayModel, FLRun, immediate, strategy
    from repro.fl.evaluate import make_personalized_eval
    from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn

    clients = make_federated_dataset("mnist", n_clients=10,
                                     classes_per_client=5, seed=0)
    cnn_l = lambda p, b: cnn_loss(MNIST_CNN, p, b, train=False)
    cnn_a = lambda p, b: cnn_accuracy(MNIST_CNN, p, b)
    pcfg = PersAFLConfig(option="C", q_local=5, lam=25.0, inner_steps=5,
                         inner_eta=0.02, beta=1.0, eta=0.002)
    sim = FLRun(clients=clients, loss_fn=cnn_l,
                init_params=init_cnn(MNIST_CNN, jax.random.PRNGKey(0)),
                pcfg=pcfg, delays=DelayModel(len(clients), seed=1),
                strategy=strategy("persafl", option="C"),
                schedule=immediate(), batch_size=16, seed=0)
    sim.run(max_rounds=20)
    ev_full = make_personalized_eval(cnn_l, cnn_a, clients, ft_steps=1,
                                     ft_lr=0.01)
    ev_head = make_personalized_eval(cnn_l, cnn_a, clients, ft_steps=1,
                                     ft_lr=0.01, personal_subset="fc/#1")
    a_full = float(ev_full(sim.state.params))
    a_head = float(ev_head(sim.state.params))
    assert abs(a_full - a_head) <= 0.1, (a_full, a_head)


# -- personalized evaluation over a subset ----------------------------------

def test_personalized_eval_subset_freezes_backbone():
    from repro.data.federated import make_federated_dataset
    from repro.fl.evaluate import make_personalized_eval

    clients = make_federated_dataset("mnist", n_clients=4,
                                     classes_per_client=2, seed=0)

    def mnist_loss(p, b):
        x = b["images"].reshape(b["images"].shape[0], -1)
        logits = x @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(
            jax.nn.one_hot(b["labels"], 10) * logp, -1))

    def acc(p, b):
        x = b["images"].reshape(b["images"].shape[0], -1)
        return jnp.mean((jnp.argmax(x @ p["w"] + p["b"], -1)
                         == b["labels"]).astype(jnp.float32))

    rng = np.random.RandomState(0)
    dim = int(np.prod(clients[0].train_x.shape[1:]))
    params = {"w": jnp.asarray(0.01 * rng.randn(dim, 10)
                               .astype(np.float32)),
              "b": jnp.zeros((10,))}
    ev_full = make_personalized_eval(mnist_loss, acc, clients, ft_steps=2,
                                     ft_lr=0.05, seed=0)
    ev_head = make_personalized_eval(mnist_loss, acc, clients, ft_steps=2,
                                     ft_lr=0.05, seed=0,
                                     personal_subset=("b",))
    a_full, a_head = ev_full(params), ev_head(params)
    assert 0.0 <= a_head <= 1.0 and 0.0 <= a_full <= 1.0
    # an all-leaves subset IS full-model fine-tuning
    ev_all = make_personalized_eval(mnist_loss, acc, clients, ft_steps=2,
                                    ft_lr=0.05, seed=0,
                                    personal_subset=("b", "w"))
    assert abs(ev_all(params) - a_full) < 1e-6
    # typo'd subsets fail loudly at evaluate time
    ev_typo = make_personalized_eval(mnist_loss, acc, clients,
                                     personal_subset=("nope",))
    with pytest.raises(ValueError, match="matches no param leaf"):
        ev_typo(params)
