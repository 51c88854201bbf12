"""Quantized delta banking + compressed wire (int8 codec, error feedback).

The apply_rows_q kernel against its jnp oracle (pow2/non-pow2 cohorts,
all-padding rows, bf16 weights), the oracle against dequant-then-apply,
quantizer error bounds, the error-feedback recurrence keeping the running
quantized sum near the fp32 sum over many windows (hypothesis when
available, a seeded sweep otherwise), the wire codec (int8 bodies
self-describing and smaller, non-float dtypes exact), the npz dtype
regression (bf16 through encode/decode AND save/load_pytree), quantized
serving end-to-end (lazy heads ≈ fp32 twin, residency ≥ 3.5x smaller,
stragglers, zero host materializations), transport codec negotiation, and
bit-exact save/restore of quantized snapshots + residuals.
"""
import asyncio
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import load_pytree, save_pytree
from repro.core import PersAFLConfig, init_server_state
from repro.core.quant import (QuantStack, QuantTree, QuantizedBank,
                              QuantizedHeads, dequantize_stack,
                              dequantize_tree, ef_quantize_stack,
                              fp32_row_nbytes, quantize_stack,
                              quantize_tree)
from repro.core.server import apply_admitted_rows
from repro.kernels.fused_update.kernel import apply_rows_q
from repro.kernels.fused_update.ops import apply_rows_q_tree
from repro.kernels.fused_update.ref import apply_rows_q_ref, apply_rows_ref
from repro.serving import PersonalizationServer
from repro.serving.transport import (AsyncTransportClient, TransportServer,
                                     decode_pytree, encode_pytree)


def _quant_leaves(stack):
    qs = quantize_stack(stack)
    return (jax.tree.leaves(qs.q)[0], jax.tree.leaves(qs.scales)[0])


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,shape", [(1, (33,)), (3, (128, 7)),
                                     (5, (1000,)), (8, (64, 64)),
                                     (32, (257,))])
def test_apply_rows_q_matches_oracle(m, shape):
    rng = np.random.RandomState(m)
    w = jnp.asarray(rng.randn(*shape).astype(np.float32))
    stack = jnp.asarray(0.01 * rng.randn(m, *shape).astype(np.float32))
    q, sc = _quant_leaves(stack)
    weights = jnp.asarray(rng.rand(m).astype(np.float32))
    got = apply_rows_q(w, q, sc, weights, interpret=True)
    want = apply_rows_q_ref(w, q, sc, weights)
    assert got.dtype == w.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=0)


def test_apply_rows_q_bf16_params():
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(96).astype(np.float32)).astype(jnp.bfloat16)
    stack = jnp.asarray(0.01 * rng.randn(4, 96).astype(np.float32))
    q, sc = _quant_leaves(stack)
    weights = jnp.asarray(rng.rand(4).astype(np.float32))
    got = apply_rows_q(w, q, sc, weights, interpret=True)
    want = apply_rows_q_ref(w, q, sc, weights)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-2)


def test_apply_rows_q_all_padding_rows_identity():
    """Zero weights on zero rows (the pow2 bucket padding) leave w as-is."""
    w = jnp.arange(50, dtype=jnp.float32)
    q = jnp.zeros((4, 50), jnp.int8)
    sc = jnp.zeros((4,), jnp.float32)
    weights = jnp.zeros((4,), jnp.float32)
    got = apply_rows_q(w, q, sc, weights, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(w))


def test_apply_rows_q_ref_is_dequant_then_apply():
    """The quantized oracle == dequantize + the fp32 rows oracle."""
    rng = np.random.RandomState(1)
    w = jnp.asarray(rng.randn(64).astype(np.float32))
    stack = jnp.asarray(0.02 * rng.randn(6, 64).astype(np.float32))
    qs = quantize_stack(stack)
    q, sc = jax.tree.leaves(qs.q)[0], jax.tree.leaves(qs.scales)[0]
    weights = jnp.asarray(rng.rand(6).astype(np.float32))
    got = apply_rows_q_ref(w, q, sc, weights)
    deq = jax.tree.leaves(dequantize_stack(qs))[0]
    want = apply_rows_ref(w, deq, weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_apply_rows_q_tree_modes_agree():
    rng = np.random.RandomState(2)
    w = {"a": jnp.asarray(rng.randn(40).astype(np.float32)),
         "b": jnp.asarray(rng.randn(8, 5).astype(np.float32))}
    stack = jax.tree.map(
        lambda x: jnp.asarray(0.01 * rng.randn(3, *x.shape)
                              .astype(np.float32)), w)
    qs = quantize_stack(stack)
    weights = jnp.asarray(rng.rand(3).astype(np.float32))
    # the apply donates its params tree: each mode gets its own copy
    got_k = apply_rows_q_tree(jax.tree.map(jnp.copy, w), qs.q, qs.scales,
                              weights, mode="kernel")
    got_r = apply_rows_q_tree(jax.tree.map(jnp.copy, w), qs.q, qs.scales,
                              weights, mode="ref")
    for a, b in zip(jax.tree.leaves(got_k), jax.tree.leaves(got_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_apply_admitted_rows_dispatches_quant_stack():
    """A QuantStack delta bank applies without materializing fp32 rows and
    matches the fp32 apply of the dequantized stack."""
    rng = np.random.RandomState(3)
    params = {"w": jnp.asarray(rng.randn(30, 4).astype(np.float32))}
    stack = {"w": jnp.asarray(0.05 * rng.randn(4, 30, 4)
                              .astype(np.float32))}
    qs = quantize_stack(stack)
    weights = jnp.asarray([0.2, 0.3, 0.0, 0.1], jnp.float32)
    s_q = apply_admitted_rows(init_server_state(params), qs, weights, 3, 1)
    s_f = apply_admitted_rows(init_server_state(params),
                              dequantize_stack(qs), weights, 3, 1)
    np.testing.assert_allclose(np.asarray(s_q.params["w"]),
                               np.asarray(s_f.params["w"]), atol=1e-6)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >1 device for a sharded stack")
def test_apply_admitted_rows_quant_sharded_stack():
    """A QuantStack whose leaves span devices takes the ref path (Pallas
    interpret can't trace through shard_map) and matches single-device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    rng = np.random.RandomState(4)
    ndev = jax.device_count()
    params = {"w": jnp.asarray(rng.randn(64).astype(np.float32))}
    stack = {"w": jnp.asarray(0.05 * rng.randn(ndev, 64)
                              .astype(np.float32))}
    qs = quantize_stack(stack)
    mesh = Mesh(np.array(jax.devices()), ("cohort",))
    sharded = QuantStack(
        q=jax.device_put(qs.q, NamedSharding(mesh, P("cohort"))),
        scales=jax.device_put(qs.scales, NamedSharding(mesh, P("cohort"))))
    weights = jnp.asarray(rng.rand(ndev).astype(np.float32))
    s_sh = apply_admitted_rows(init_server_state(params), sharded,
                               weights, ndev, 0)
    s_1d = apply_admitted_rows(init_server_state(params), qs,
                               weights, ndev, 0)
    np.testing.assert_allclose(np.asarray(s_sh.params["w"]),
                               np.asarray(s_1d.params["w"]), atol=1e-6)


# ---------------------------------------------------------------------------
# quantizer + error feedback
# ---------------------------------------------------------------------------

def test_quantize_stack_error_bound_and_zero_rows_exact():
    rng = np.random.RandomState(5)
    stack = {"x": jnp.asarray(
        np.concatenate([rng.randn(3, 17), np.zeros((2, 17))])
        .astype(np.float32))}
    qs = quantize_stack(stack)
    assert jax.tree.leaves(qs.q)[0].dtype == jnp.int8
    deq = jax.tree.leaves(dequantize_stack(qs))[0]
    x = np.asarray(stack["x"])
    for i in range(3):   # symmetric absmax: error ≤ scale/2 per element
        bound = np.max(np.abs(x[i])) / 127.0 * 0.500001
        assert np.max(np.abs(np.asarray(deq)[i] - x[i])) <= bound
    np.testing.assert_array_equal(np.asarray(deq)[3:], x[3:])  # zeros exact


def test_quantize_tree_roundtrip_bound():
    rng = np.random.RandomState(6)
    tree = {"w": jnp.asarray(rng.randn(9, 3).astype(np.float32)),
            "b": jnp.zeros((3,), jnp.float32)}
    qt = quantize_tree(tree)
    assert isinstance(qt, QuantTree)
    deq = dequantize_tree(qt)
    err = float(jnp.max(jnp.abs(deq["w"] - tree["w"])))
    assert err <= float(jnp.max(jnp.abs(tree["w"]))) / 127.0 * 0.500001
    np.testing.assert_array_equal(np.asarray(deq["b"]),
                                  np.asarray(tree["b"]))


def _ef_drift(seed: int, windows: int, n: int) -> float:
    """Max |Σ dequant(quant_EF(delta)) − Σ delta| after ``windows`` EF
    steps, relative to the quantization step of one window."""
    rng = np.random.RandomState(seed)
    exact = np.zeros(n, np.float32)
    applied = np.zeros(n, np.float32)
    residual = None
    step = 0.0
    for _ in range(windows):
        raw = {"x": jnp.asarray(0.1 * rng.randn(1, n).astype(np.float32))}
        qs, res_q = ef_quantize_stack(raw, residual)
        residual = dequantize_stack(res_q)  # stored int8, fed back as fp32
        deq = np.asarray(jax.tree.leaves(dequantize_stack(qs))[0][0])
        exact += np.asarray(raw["x"])[0]
        applied += deq
        step = max(step, float(np.max(np.abs(np.asarray(raw["x"])))) / 127)
    return float(np.max(np.abs(applied - exact))) / max(step, 1e-12)


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2 ** 16), st.integers(4, 24),
           st.integers(8, 200))
    def test_ef_running_sum_stays_bounded(seed, windows, n):
        # without EF the worst case drifts ~windows/2 steps; WITH EF the
        # carried residual keeps the total within ~2 steps regardless of
        # window count (1 step current error + quantized-residual dust)
        assert _ef_drift(seed, windows, n) <= 2.0
except ImportError:     # hypothesis is a dev extra — seeded sweep fallback
    @pytest.mark.parametrize("seed,windows,n",
                             [(0, 4, 8), (1, 12, 64), (2, 24, 200),
                              (3, 16, 33), (4, 20, 128)])
    def test_ef_running_sum_stays_bounded(seed, windows, n):
        assert _ef_drift(seed, windows, n) <= 2.0


def test_ef_beats_plain_quantization_over_windows():
    """The point of the residual: cumulative EF error stays ~flat while
    plain re-quantization error can accumulate with window count."""
    rng = np.random.RandomState(7)
    n, windows = 64, 32
    bias = 0.004 * rng.randn(n).astype(np.float32)  # sub-step per-window
    exact = np.zeros(n, np.float32)
    plain = np.zeros(n, np.float32)
    ef = np.zeros(n, np.float32)
    residual = None
    for _ in range(windows):
        raw = (bias + 0.001 * rng.randn(n).astype(np.float32)) \
            .astype(np.float32)
        # force a coarse shared scale: one large element dominates absmax
        row = np.concatenate([raw, [1.0]]).astype(np.float32)[None]
        tree = {"x": jnp.asarray(row)}
        exact += raw
        deq_p = np.asarray(jax.tree.leaves(
            dequantize_stack(quantize_stack(tree)))[0][0][:n])
        plain += deq_p
        qs, res_q = ef_quantize_stack(tree, residual)
        residual = dequantize_stack(res_q)
        ef += np.asarray(jax.tree.leaves(
            dequantize_stack(qs))[0][0][:n])
    err_plain = float(np.max(np.abs(plain - exact)))
    err_ef = float(np.max(np.abs(ef - exact)))
    # sub-step deltas vanish without EF (quantize to 0 every window)
    assert err_plain > 5 * err_ef


def test_quantized_bank_handles():
    rng = np.random.RandomState(8)
    stack = {"w": jnp.asarray(rng.randn(4, 6).astype(np.float32))}
    qs = quantize_stack(stack)
    bank = QuantizedBank(qs, k=3)
    assert bank.capacity == 4 and len(bank) == 3
    rows = bank.rows(jnp.asarray([0, 2], jnp.int32))
    deq = dequantize_stack(qs)
    np.testing.assert_allclose(np.asarray(rows["w"][1]),
                               np.asarray(deq["w"][2]), atol=1e-7)
    assert fp32_row_nbytes(qs) == 6 * 4
    snap = {"w": jnp.asarray(rng.randn(6).astype(np.float32))}
    heads = QuantizedHeads(snap, bank)
    head0 = heads.row(0)
    np.testing.assert_allclose(
        np.asarray(head0["w"]),
        np.asarray(snap["w"] - deq["w"][0]), atol=1e-6)


# ---------------------------------------------------------------------------
# wire codec + npz dtype regression (satellite bugfix)
# ---------------------------------------------------------------------------

def test_wire_codec_int8_roundtrip_and_size():
    rng = np.random.RandomState(9)
    tree = {"x": rng.randn(32, 64).astype(np.float32),
            "y": rng.randint(0, 10, 32).astype(np.int32)}
    b32 = encode_pytree(tree)
    b8 = encode_pytree(tree, codec="int8")
    assert len(b8) < len(b32) / 2
    dec32 = decode_pytree(b32)
    np.testing.assert_array_equal(dec32["x"], tree["x"])  # fp32 bit-exact
    dec8 = decode_pytree(b8)
    np.testing.assert_array_equal(dec8["y"], tree["y"])   # ints exact
    assert dec8["y"].dtype == np.int32
    bound = np.max(np.abs(tree["x"])) / 127.0 * 0.500001
    assert np.max(np.abs(dec8["x"] - tree["x"])) <= bound


def test_wire_codec_rejects_unknown():
    with pytest.raises(ValueError):
        encode_pytree({"x": np.zeros(3, np.float32)}, codec="int4")


def test_npz_roundtrip_preserves_nonfloat_dtypes():
    """Regression (pre-fix failing): ml_dtypes leaves came back as raw
    void records (dtype ``|V2``) from npz; int8/uint8 must stay exact."""
    import ml_dtypes
    rng = np.random.RandomState(10)
    tree = {"i8": rng.randint(-127, 127, (5, 3)).astype(np.int8),
            "u8": rng.randint(0, 255, (4,)).astype(np.uint8),
            "bf16": rng.randn(6).astype(ml_dtypes.bfloat16),
            "f32": rng.randn(2, 2).astype(np.float32)}
    for codec in ("fp32", "int8"):
        dec = decode_pytree(encode_pytree(tree, codec=codec))
        for key in ("i8", "u8", "bf16"):
            assert dec[key].dtype == tree[key].dtype, (codec, key)
            np.testing.assert_array_equal(
                dec[key].view(np.uint8), tree[key].view(np.uint8))


def test_save_pytree_preserves_nonfloat_dtypes(tmp_path):
    import ml_dtypes
    rng = np.random.RandomState(11)
    tree = {"q": rng.randint(-127, 127, (3, 4)).astype(np.int8),
            "h": rng.randn(5).astype(ml_dtypes.bfloat16),
            "s": np.float32(0.25)}
    path = os.path.join(tmp_path, "ck")
    save_pytree(path, tree)
    back = load_pytree(path)
    assert back["q"].dtype == np.int8
    np.testing.assert_array_equal(back["q"], tree["q"])
    assert back["h"].dtype == tree["h"].dtype
    np.testing.assert_array_equal(back["h"].view(np.uint16),
                                  tree["h"].view(np.uint16))


# ---------------------------------------------------------------------------
# quantized serving end-to-end
# ---------------------------------------------------------------------------

def _loss(p, b):
    logits = b["x"] @ p["w"] + p["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(jax.nn.one_hot(b["y"], 4) * logp, -1))


def _params(seed=0, d=40):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(0.1 * rng.randn(d, 4).astype(np.float32)),
            "b": jnp.zeros((4,), jnp.float32)}


def _batch(seed, d=40, n=8):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(n, d).astype(np.float32),
            "y": rng.randint(0, 4, n).astype(np.int32)}


_PCFG = PersAFLConfig(option="C", lam=20.0, inner_steps=5,
                      inner_eta=0.05, beta=0.5)


def _drive(delta_dtype, windows=3, users=6):
    srv = PersonalizationServer(_params(), _loss, _PCFG, modes=("C",),
                                windows=4, max_pending=64,
                                delta_dtype=delta_dtype)
    heads = {}
    for w in range(windows):
        tickets = [srv.submit(f"u{i}", _batch(100 * w + i))
                   for i in range(users)]
        srv.flush()
        for i, t in enumerate(tickets):
            heads[f"u{i}"] = srv.poll(t)
        srv.advance_window()
    return srv, heads


def test_quant_serving_matches_fp32_twin():
    s32, h32 = _drive("fp32")
    s8, h8 = _drive("int8")
    for user in h32:
        for key in h32[user]:
            np.testing.assert_allclose(np.asarray(h8[user][key]),
                                       np.asarray(h32[user][key]),
                                       atol=0.05)
    assert s8.stats["host_materializations"] == 0
    assert s8.stats["delta_codec"] == "int8"
    assert s32.stats["delta_codec"] == "fp32"
    assert s32.stats["ring_bytes_saved_per_user"] == 0
    # global params track the fp32 server (EF keeps noise a residual)
    for key in s32.params:
        np.testing.assert_allclose(np.asarray(s8.params[key]),
                                   np.asarray(s32.params[key]), atol=5e-3)


def test_quant_serving_residency_ratio():
    s8, _ = _drive("int8")
    st = s8.stats
    assert st["ring_bytes_per_user"] * 3.5 <= st["ring_bytes_per_user_fp32"]
    assert st["ring_bytes_saved_per_user"] == (
        st["ring_bytes_per_user_fp32"] - st["ring_bytes_per_user"])


def test_quant_serving_head_and_stacked_heads():
    s8, h8 = _drive("int8")
    again = s8.head("u0")
    for key in again:
        np.testing.assert_array_equal(np.asarray(again[key]),
                                      np.asarray(h8["u0"][key]))
    stacked = s8.stacked_heads(["u0", "u1"])
    for key in stacked:
        np.testing.assert_array_equal(np.asarray(stacked[key][0]),
                                      np.asarray(h8["u0"][key]))


def test_quant_serving_straggler_window_boundary():
    srv = PersonalizationServer(_params(), _loss, _PCFG, modes=("C",),
                                windows=4, delta_dtype="int8")
    t1 = srv.submit("s1", _batch(1))
    srv.advance_window(flush=False)          # t1 becomes a straggler
    t2 = srv.submit("s2", _batch(2))
    srv.flush()
    assert srv.poll(t1) is not None and srv.poll(t2) is not None
    srv.advance_window()
    assert srv.stats["ring_stragglers"] == 1
    assert srv.stats["host_materializations"] == 0


def test_quant_serving_snapshot_demotion():
    srv, _ = _drive("int8", windows=3)
    snaps = srv.ring._snapshots
    current = srv.ring.current
    assert not isinstance(snaps[current], QuantTree)   # fresh stays fp32
    assert any(isinstance(s, QuantTree) for w, s in snaps.items()
               if w < current)


def test_quant_save_restore_bit_exact(tmp_path):
    srv, heads = _drive("int8")
    path = os.path.join(tmp_path, "ck")
    srv.save(path)
    back = PersonalizationServer.restore(path, _loss, _PCFG, modes=("C",))
    assert back.delta_dtype == "int8"
    for user in heads:
        a, b = srv.head(user), back.head(user)
        for key in a:
            np.testing.assert_array_equal(np.asarray(a[key]),
                                          np.asarray(b[key]))
    # residual codes survive bit-exactly (the EF recurrence continues)
    assert list(srv._residuals) == list(back._residuals)
    for user in srv._residuals:
        b1, r1 = srv._residuals[user]
        b2, r2 = back._residuals[user]
        for qa, qb in zip(
                jax.tree.leaves(jax.tree.map(lambda x: x[r1],
                                             b1.stacked.q)),
                jax.tree.leaves(jax.tree.map(lambda x: x[r2],
                                             b2.stacked.q))):
            np.testing.assert_array_equal(np.asarray(qa), np.asarray(qb))
    # demoted snapshots keep their int8 codes + scales exactly
    for w, snap in srv.ring._snapshots.items():
        snap2 = back.ring._snapshots[w]
        assert isinstance(snap2, QuantTree) == isinstance(snap, QuantTree)
        for la, lb in zip(jax.tree.leaves(snap), jax.tree.leaves(snap2)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # and the restored server keeps serving
    t = back.submit("u0", _batch(999))
    back.flush()
    assert back.poll(t) is not None


def test_delta_dtype_validated():
    with pytest.raises(ValueError):
        PersonalizationServer(_params(), _loss, _PCFG, modes=("C",),
                              delta_dtype="fp16")


# ---------------------------------------------------------------------------
# transport codec negotiation
# ---------------------------------------------------------------------------

def test_transport_codec_negotiation():
    async def run():
        srv = PersonalizationServer(_params(), _loss, _PCFG, modes=("C",),
                                    max_pending=2, delta_dtype="int8")
        ts = await TransportServer(srv, flush_ms=20.0).start()
        cq = await AsyncTransportClient("127.0.0.1", ts.port,
                                        codec="int8").connect()
        cf = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        tq = await cq.submit("uq", _batch(1))
        tf = await cf.submit("uf", _batch(2))
        hq = await cq.poll(tq, wait_ms=30_000)
        hf = await cf.poll(tf, wait_ms=30_000)
        assert hq is not None and hf is not None
        assert cq.last_codec == "int8"      # negotiated
        assert cf.last_codec == "fp32"      # legacy client: fp32 fallback
        np.testing.assert_allclose(hq["w"], hf["w"], atol=0.05)
        await cq.head("uq")
        assert cq.last_codec == "int8"
        await cf.head("uf")
        assert cf.last_codec == "fp32"
        stats = await cq.stats()
        assert stats["delta_codec"] == "int8"
        assert stats["wire_codec"] == "int8"
        assert stats["host_materializations"] == 0
        await cq.close()
        await cf.close()
        await ts.stop()
    asyncio.run(run())


def test_transport_fp32_server_never_sends_int8():
    async def run():
        srv = PersonalizationServer(_params(), _loss, _PCFG, modes=("C",),
                                    max_pending=1)
        ts = await TransportServer(srv, flush_ms=20.0).start()
        c = await AsyncTransportClient("127.0.0.1", ts.port,
                                       codec="int8").connect()
        head = await c.poll(await c.submit("u", _batch(3)),
                            wait_ms=30_000)
        assert head is not None
        assert c.last_codec == "fp32"   # server-side codec wins
        await c.close()
        await ts.stop()
    asyncio.run(run())
