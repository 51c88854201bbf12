"""Socket transport front-end: wire codec round-trips, the
flush-timer-driven submit→poll→head path (bit-for-bit vs the in-process
server), explicit backpressure (BUSY at server/connection/user scope), a
second-OS-process integration drive, and clean shutdown."""
import asyncio
import os
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PersAFLConfig
from repro.serving import PersonalizationServer
from repro.serving.transport import (AsyncTransportClient, TransportBusy,
                                     TransportError, TransportServer,
                                     decode_pytree, encode_pytree,
                                     pack_frame, split_frame)


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


def _server(**kw):
    kw.setdefault("max_pending", 64)
    return PersonalizationServer(_params(), loss, _pcfg(), **kw)


def _bitwise_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y)


# -- codec -----------------------------------------------------------------

def test_pytree_codec_roundtrip():
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "nested": {"b": np.asarray([1, 2], np.int32),
                       "c": [np.float64(3.5) * np.ones((2,)),
                             np.zeros((1, 2), np.float16)]}}
    out = decode_pytree(encode_pytree(tree))
    _bitwise_equal(tree, out)
    # jax leaves encode identically to their host values (f64 narrows to
    # f32 at jnp.asarray time, before the codec ever sees it)
    jtree = jax.tree.map(jnp.asarray, tree)
    out2 = decode_pytree(encode_pytree(jtree))
    _bitwise_equal(jax.tree.map(np.asarray, jtree), out2)


def test_frame_roundtrip():
    header = {"op": "SUBMIT", "user": "u0", "mode": "C"}
    body = b"\x00\x01binary\xff"
    framed = pack_frame(header, body)
    # strip the outer length prefix, as the stream reader does
    import struct
    (n,) = struct.unpack("!I", framed[:4])
    assert n == len(framed) - 4
    h, b = split_frame(framed[4:])
    assert h == header and b == body


# -- request path over the socket ------------------------------------------

def test_round_trip_flush_timer_head_bitwise():
    """submit → (deadline flush timer) → poll → head, equal bit-for-bit
    to the head the in-process surface serves for the same request."""
    ref = _server()
    t_ref = ref.submit("u0", user_batch(0))
    ref.flush()
    expected = ref.poll(t_ref)

    async def go():
        srv = _server()
        ts = await TransportServer(srv, flush_ms=150.0).start()
        c = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        tid = await c.submit("u0", user_batch(0))
        # below max_pending: nothing has flushed yet — still queued
        assert await c.poll(tid) is None
        head = await c.poll(tid, wait_ms=30_000)   # flush timer fires
        assert head is not None
        assert ts.stats["timer_flushes"] == 1
        again = await c.head("u0")
        stats = await c.stats()
        await c.close()
        await ts.stop()
        return head, again, stats

    head, again, stats = asyncio.run(go())
    _bitwise_equal(head, expected)
    _bitwise_equal(again, expected)
    assert stats["host_materializations"] == 0
    assert stats["cohort_calls"] == 1


def test_full_queue_flushes_synchronously_not_by_timer():
    async def go():
        srv = _server(max_pending=3)
        ts = await TransportServer(srv, flush_ms=60_000.0).start()
        c = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        tids = [await c.submit(f"u{i}", user_batch(i)) for i in range(3)]
        # the 3rd submit filled the queue: served without any timer
        heads = [await c.poll(t, wait_ms=1_000) for t in tids]
        assert all(h is not None for h in heads)
        assert ts.stats["timer_flushes"] == 0
        await c.close()
        await ts.stop()

    asyncio.run(go())


def test_concurrent_connections_coalesce_into_one_cohort_call():
    """Sixteen connections, one user each, submit at once: their requests
    share one cohort call, the wire keeps the micro-batching win, and
    every head equals the in-process server's bit for bit."""
    conns = 16
    ref = _server(max_pending=conns)
    tickets = [ref.submit(f"u{i}", user_batch(i)) for i in range(conns)]
    expected = [ref.poll(t) for t in tickets]

    async def go():
        srv = _server(max_pending=conns)
        ts = await TransportServer(srv, flush_ms=60_000.0,
                                   max_inflight=4 * conns).start()
        clients = [await AsyncTransportClient("127.0.0.1", ts.port)
                   .connect() for _ in range(conns)]
        tids = await asyncio.gather(*(
            c.submit(f"u{i}", user_batch(i))
            for i, c in enumerate(clients)))
        heads = await asyncio.gather(*(
            c.poll(t, wait_ms=30_000) for c, t in zip(clients, tids)))
        stats = await clients[0].stats()
        for c in clients:
            await c.close()
        await ts.stop()
        return heads, stats, dict(ts.stats)

    heads, stats, tstats = asyncio.run(go())
    for got, want in zip(heads, expected):
        _bitwise_equal(got, want)
    assert stats["cohort_calls"] == 1
    assert tstats["timer_flushes"] == 0
    assert stats["host_materializations"] == 0


def test_loopback_selftest_cli(run_cli):
    """``python -m repro.serving.transport``: concurrent clients through
    submit/poll/head over loopback, zero host materializations, clean
    shutdown."""
    out = run_cli(["-m", "repro.serving.transport", "--clients", "6",
                   "--rounds", "3"], timeout=40)
    assert "host_materializations=0,ok" in out


def test_refusals_surface_typed_errors():
    """A request beyond tau_max polls back as code="dropped" over the
    wire (and a fairness refusal as code="capped")."""
    async def go():
        srv = _server(windows=2)                 # tau_max = 1
        ts = await TransportServer(srv, flush_ms=60_000.0).start()
        c = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        tid = await c.submit("slow", user_batch(0))
        await c.advance(flush=False)
        await c.advance(flush=False)             # tau = 2 > tau_max
        await c.flush()
        with pytest.raises(TransportError) as ei:
            await c.poll(tid)
        assert ei.value.code == "dropped"

        srv2 = _server(user_cap=1)
        srv2.batcher.user_cap = None             # ring is the authority
        ts2 = await TransportServer(srv2, flush_ms=60_000.0,
                                    max_inflight=8).start()
        c2 = await AsyncTransportClient("127.0.0.1", ts2.port).connect()
        # the transport's door check spans its own connections, so the
        # over-cap row must come from traffic it cannot see: in-process
        # submits sharing the same server (pre-filter drift)
        t_local = srv2.submit("heavy", user_batch(1))
        t2 = await c2.submit("heavy", user_batch(2))
        await c2.flush()
        assert t_local.status == "done"
        with pytest.raises(TransportError) as ei:
            await c2.poll(t2)
        assert ei.value.code == "capped"
        for cl in (c, c2):
            await cl.close()
        await ts.stop()
        await ts2.stop()

    asyncio.run(go())


# -- backpressure ----------------------------------------------------------

def test_backpressure_busy_server_scope():
    async def go():
        srv = _server()
        ts = await TransportServer(srv, flush_ms=60_000.0,
                                   max_inflight=2).start()
        c = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        t0 = await c.submit("u0", user_batch(0))
        t1 = await c.submit("u1", user_batch(1))
        with pytest.raises(TransportBusy) as ei:
            await c.submit("u2", user_batch(2))
        assert ei.value.scope == "server"
        assert ts.stats["busy"] == 1
        # terminal polls free the slots: the queue drains and refills
        await c.flush()
        assert (await c.poll(t0, wait_ms=1_000)) is not None
        assert (await c.poll(t1, wait_ms=1_000)) is not None
        await c.submit("u2", user_batch(2))      # accepted now
        await c.close()
        await ts.stop()

    asyncio.run(go())


def test_backpressure_busy_connection_and_user_scopes():
    async def go():
        srv = _server(user_cap=1)
        ts = await TransportServer(srv, flush_ms=60_000.0,
                                   conn_inflight=2).start()
        c1 = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        c2 = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        # per-user door check (honors user_cap before burning a slot) —
        # and it spans connections: the same user on ANOTHER socket is
        # refused too
        await c1.submit("shared", user_batch(0))
        with pytest.raises(TransportBusy) as ei:
            await c1.submit("shared", user_batch(1))
        assert ei.value.scope == "user"
        with pytest.raises(TransportBusy) as ei:
            await c2.submit("shared", user_batch(1))
        assert ei.value.scope == "user"
        # ...and counts rows the ring already ADMITTED this window
        await c1.flush()
        with pytest.raises(TransportBusy) as ei:
            await c2.submit("shared", user_batch(2))
        assert ei.value.scope == "user"
        # per-connection bound; the other connection is unaffected
        await c1.submit("other", user_batch(2))
        with pytest.raises(TransportBusy) as ei:
            await c1.submit("third", user_batch(3))
        assert ei.value.scope == "connection"
        await c2.submit("fourth", user_batch(4))
        await c1.close()
        await c2.close()
        await ts.stop()

    asyncio.run(go())


def test_dead_connection_releases_inflight_slots():
    async def go():
        srv = _server()
        ts = await TransportServer(srv, flush_ms=60_000.0,
                                   max_inflight=2).start()
        c1 = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        await c1.submit("u0", user_batch(0))
        await c1.submit("u1", user_batch(1))
        await c1.close()                          # frees both slots
        await asyncio.sleep(0.05)
        c2 = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        await c2.submit("u2", user_batch(2))      # no BUSY
        await c2.close()
        await ts.stop()

    asyncio.run(go())


# -- protocol robustness ---------------------------------------------------

def test_unknown_ops_and_tickets_are_typed_errors():
    async def go():
        srv = _server()
        ts = await TransportServer(srv).start()
        c = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        with pytest.raises(TransportError) as ei:
            await c._rpc({"op": "NOPE"})
        assert ei.value.code == "unknown_op"
        with pytest.raises(TransportError) as ei:
            await c.poll(12345)
        assert ei.value.code == "unknown_ticket"
        with pytest.raises(TransportError) as ei:
            await c.head("nobody")
        assert ei.value.code == "unknown_user"
        with pytest.raises(TransportError) as ei:
            await c.submit("u", user_batch(0), mode="Z")
        assert ei.value.code == "bad_mode"
        # an undecodable npz body is a bad_request for THAT frame only:
        # no flush ran, other queued tickets are untouched and serve once
        t_ok = await c.submit("fine", user_batch(1))
        with pytest.raises(TransportError) as ei:
            await c._rpc({"op": "SUBMIT", "user": "u", "mode": "C"},
                         b"not-an-npz")
        assert ei.value.code == "bad_request"
        assert ts.stats["failed_flushes"] == 0
        await c.flush()
        assert (await c.poll(t_ok, wait_ms=1_000)) is not None
        await c.close()
        await ts.stop()

    asyncio.run(go())


def test_poisoned_batch_fails_typed_and_server_survives():
    """A malformed batch (wrong keys/shapes — remote clients send
    arbitrary pytrees) must not kill the event loop or strand tickets:
    the failed flush group polls back as server_error and the NEXT
    well-formed request is served normally."""
    async def go():
        srv = _server(max_pending=2)
        ts = await TransportServer(srv, flush_ms=60_000.0).start()
        c = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        t_ok = await c.submit("good", user_batch(0))
        # second submit fills the queue -> auto-flush with the poison in
        bad = {"wrong_key": np.zeros((3, 3), np.float32)}
        with pytest.raises(TransportError) as ei:
            await c.submit("evil", bad)
        assert ei.value.code == "server_error"
        # the good ticket was in the poisoned drain: typed failure
        with pytest.raises(TransportError) as ei:
            await c.poll(t_ok, wait_ms=1_000)
        assert ei.value.code == "server_error"
        assert ts.stats["failed_flushes"] == 1
        # the server is still alive and serving
        t2 = await c.submit("good", user_batch(1))
        await c.flush()
        assert (await c.poll(t2, wait_ms=1_000)) is not None
        await c.close()
        await ts.stop()

    asyncio.run(go())


def test_clean_shutdown_closes_connections():
    """stop() must return promptly even with a handler parked in a long
    POLL wait — the task is cancelled, not stranded."""
    async def go():
        srv = _server()
        ts = await TransportServer(srv, flush_ms=60_000.0).start()
        c = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        assert (await c.stats())["window"] == 0
        # park a second connection in a 60s POLL wait
        c2 = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        tid = await c2.submit("u", user_batch(0))
        waiter = asyncio.ensure_future(c2.poll(tid, wait_ms=60_000))
        await asyncio.sleep(0.05)
        t0 = asyncio.get_running_loop().time()
        await ts.stop()
        assert asyncio.get_running_loop().time() - t0 < 2.0
        with pytest.raises((ConnectionError, OSError)):
            await c.stats()
        with pytest.raises((ConnectionError, OSError,
                            TransportError)):
            await waiter
        await c.close()
        await c2.close()

    asyncio.run(go())


# -- second OS process -----------------------------------------------------

CLIENT_SCRIPT = textwrap.dedent("""
    import sys
    import numpy as np
    from repro.serving.transport import TransportClient

    port, out = int(sys.argv[1]), sys.argv[2]
    rng = np.random.RandomState(7)
    batch = {"x": rng.randn(8, 5).astype(np.float32),
             "y": rng.randint(0, 4, 8).astype(np.int32)}
    c = TransportClient("127.0.0.1", port, timeout=120.0)
    tid = c.submit("remote-user", batch)
    head = c.poll(tid, wait_ms=60_000)
    assert head is not None, "poll timed out"
    again = c.head("remote-user")
    for a, b in zip(head.values(), again.values()):
        assert np.array_equal(a, b)
    stats = c.stats()
    assert stats["host_materializations"] == 0, stats
    np.savez(out, **head)
    c.close()
""")


def test_second_process_personalizes_over_the_socket(tmp_path):
    """A separate OS process submits a batch and fetches its personalized
    head over the socket; the head equals the in-process result
    bit-for-bit."""
    script = tmp_path / "client.py"
    script.write_text(CLIENT_SCRIPT)
    out = tmp_path / "head.npz"
    rng = np.random.RandomState(7)
    batch = {"x": rng.randn(8, 5).astype(np.float32),
             "y": rng.randint(0, 4, 8).astype(np.int32)}
    ref = _server()
    t_ref = ref.submit("remote-user", batch)
    ref.flush()
    expected = jax.tree.map(np.asarray, ref.poll(t_ref))

    async def go():
        srv = _server()
        ts = await TransportServer(srv, flush_ms=50.0).start()
        src = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "src"))
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (src, env.get("PYTHONPATH", "")) if p)
        proc = await asyncio.create_subprocess_exec(
            sys.executable, str(script), str(ts.port), str(out), env=env,
            stderr=asyncio.subprocess.PIPE)
        try:
            _, err = await asyncio.wait_for(proc.communicate(),
                                            timeout=240)
        finally:
            if proc.returncode is None:
                proc.kill()
        assert proc.returncode == 0, err.decode()[-2000:]
        stats = dict(srv.stats)
        await ts.stop()
        return stats

    stats = asyncio.run(go())
    assert stats["host_materializations"] == 0
    with np.load(out) as z:
        got = {k: z[k] for k in z.files}
    _bitwise_equal(got, expected)
