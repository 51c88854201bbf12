"""Asyncio socket front-end: the network transport for
:class:`repro.serving.PersonalizationServer`.

Until this module, no request could reach the serving stack from outside
the Python process — submit/flush/poll were in-process method calls.
:class:`TransportServer` makes the server network-addressable while keeping
every micro-batching property intact: concurrent connections' SUBMIT frames
land in the same :class:`repro.serving.batcher.MicroBatcher` queue and
coalesce into the same pow2-bucketed cohort calls, so the transport never
forfeits the batched-personalization win.

Wire protocol (version 1) — length-prefixed JSON + binary frames::

    frame  := u32 len(rest) | u32 len(header) | header | body
    header := UTF-8 JSON object; "op" selects the operation
    body   := npz-encoded pytree (numpy ``savez`` of the checkpoint
              store's ``path/to/leaf`` flat layout) or empty

All u32 are big-endian.  Client → server ops and their replies:

    SUBMIT {user, mode, subset_ok?, codec_ok?}  + npz(batch)
                                       → OK {ticket, window}
                                         | BUSY {scope, open}
    POLL   {ticket, wait_ms?, subset_ok?}
                                       → OK {status:"queued"}
                                         | OK {status:"done", subset?,
                                               codec} + npz(head)
                                         | ERR {code: dropped|capped|
                                                evicted|superseded, error}
    HEAD   {user, subset_ok?, codec_ok?} → OK {subset?, codec} + npz(head)
                                         | ERR unknown_user
    STATS  {}                          → OK {stats: {...}, subset?}
    FLUSH  {}                          → OK {served}
    ADVANCE{}                          → OK {window}

Subset negotiation: when the fronted server personalizes a
``personal_subset`` only, head bodies are *subset pytrees* (pruned
structure; merge over the global backbone with
``repro.core.merge_subset``).  A v1 client that does not declare
``subset_ok: true`` on SUBMIT/POLL/HEAD gets a typed
``ERR subset_unsupported`` instead of a silently-partial pytree; replies
that carry a subset body stamp the resolved leaf paths in the header's
``subset`` key (both clients record it as ``.last_subset``).

Codec negotiation (compressed wire): npz bodies may carry float leaves as
symmetric-absmax **int8 codes + one f32 scale per leaf** — the scale rides
in the same flat layout under a ``__q8s__:<key>`` marker, so an int8 body
is self-describing and ``decode_pytree`` dequantizes transparently.  The
negotiation mirrors the subset handshake but FALLS BACK instead of
refusing: a quantized-banking server (``delta_dtype="int8"``, or an
explicit ``wire_codec=``) sends int8 head bodies only to clients that
declared ``codec_ok: true`` at SUBMIT (HEAD negotiates per request);
non-declaring clients get plain fp32 bodies — a precision downgrade is
never silent, and replies stamp the body's actual codec in the header's
``codec`` key (clients record ``.last_codec``).  Uplink SUBMIT bodies are
the client's choice: constructing a client with ``codec="int8"`` encodes
its batches quantized (the server decodes either form).  At the ``quant``
bench's serve config both directions shrink ≥ 3.5x.

Deadline-driven flushing: a SUBMIT that fills the underlying server's
``max_pending`` queue flushes synchronously (the micro-batch path); a
partial queue is flushed by a ``flush_ms`` timer armed at the first queued
request — so latency is bounded by ``max(flush_ms, cohort call)`` even at
low request rates.  ``window_ms`` optionally drives ``advance_window`` on a
wall-clock timer (the aggregation-window boundary of the serving rules).

Backpressure is explicit, never unbounded growth: ``max_inflight`` bounds
the server-wide open tickets (submitted, not yet terminally polled),
``conn_inflight`` bounds one connection's, and with the server's
``user_cap`` fairness bound set, a user's queued submissions per window are
refused at the door — each refusal is a ``BUSY`` frame naming its scope
(``server`` / ``connection`` / ``user``), and clients raise
:class:`TransportBusy` so callers can back off and retry.

Clients: :class:`TransportClient` is the blocking library (any second OS
process: ``submit``/``poll``/``head``/``stats``), :class:`AsyncTransportClient`
the asyncio twin (the load generator drives N of them concurrently).
Frames on one connection are handled in order; issue one RPC at a time per
connection and open more connections for concurrency.

Quickstart (see also ``launch/serve.py --listen PORT``)::

    # process 1
    srv = PersonalizationServer(params, loss, pcfg)
    ts = TransportServer(srv, port=7777, flush_ms=10.0)
    asyncio.run(ts.serve_forever())

    # process 2
    c = TransportClient("127.0.0.1", 7777)
    head = c.poll(c.submit("user-a", batch, mode="C"), wait_ms=5_000)

``python -m repro.serving.transport`` runs a loopback selftest (tiny
logistic workload, concurrent clients, zero-host-materialization check,
clean shutdown) — the CI ``transport-smoke`` job's entry point.
"""
from __future__ import annotations

import asyncio
import io
import json
import socket
import struct
from typing import Dict, Optional, Tuple

import numpy as np

from repro.checkpoint.store import (flatten_pytree, pack_dtypes,
                                    unflatten_pytree, unpack_dtypes)

PROTOCOL_VERSION = 1
WIRE_CODECS = ("fp32", "int8")
# per-leaf wire-quantization scale marker: like the checkpoint store's
# ``__dt__:`` markers, the ``:`` keeps it disjoint from every data key
Q8_KEY_PREFIX = "__q8s__:"
_U32 = struct.Struct("!I")
# reject absurd frames instead of buffering our way to OOM
MAX_FRAME_BYTES = 1 << 28


class ProtocolError(RuntimeError):
    """Malformed frame or header (framing, not application, errors)."""


class TransportError(RuntimeError):
    """Application-level ERR reply surfaced client-side.

    ``code`` mirrors the server's refusal cause: ``dropped`` (staleness
    past tau_max), ``capped`` (per-window fairness cap), ``superseded``
    (the ticket's ring window retired before it was polled), ``evicted``
    (LRU head-cache pressure on a handle-less ticket),
    ``subset_unsupported`` (the server serves personal-subset heads and
    the client did not declare ``subset_ok``), ``unknown_user`` /
    ``unknown_ticket`` / ``bad_request``.
    """

    def __init__(self, code: str, message: str):
        super().__init__(f"{code}: {message}")
        self.code = code


class TransportBusy(TransportError):
    """BUSY reply: the server refused to queue more work.  ``scope`` says
    which bound tripped (``server`` / ``connection`` / ``user``) — back
    off and retry, nothing was queued."""

    def __init__(self, scope: str, open_tickets: int):
        super().__init__(
            "busy", f"backpressure at scope={scope!r} "
                    f"(open={open_tickets}); retry later")
        self.scope = scope
        self.open_tickets = open_tickets


# ---------------------------------------------------------------------------
# codec: npz pytrees + length-prefixed frames
# ---------------------------------------------------------------------------

def encode_pytree(tree, codec: str = "fp32") -> bytes:
    """Pytree → npz bytes in the checkpoint store's flat key layout.
    ``np.asarray`` on each leaf moves device arrays to the host — the wire
    is a host boundary by definition (this is NOT a DeltaBank
    materialization; the ``host_materializations`` stat stays untouched).

    Non-float dtypes (int8/uint8/bf16/...) round-trip EXACTLY in every
    codec: ml_dtypes leaves travel as bit patterns + ``__dt__:`` markers
    (``pack_dtypes``), integer leaves natively.  ``codec="int8"``
    additionally rewrites each f32/f64 leaf as int8 codes + one f32 scale
    under ``__q8s__:<key>`` (symmetric absmax — the delta-banking codec
    reused on the wire); the body stays self-describing, so the decoder
    needs no negotiated state.
    """
    if codec not in WIRE_CODECS:
        raise ValueError(f"codec must be one of {WIRE_CODECS}, "
                         f"got {codec!r}")
    flat = flatten_pytree(tree)
    if codec == "int8":
        out = {}
        for key, val in flat.items():
            arr = np.asarray(val)
            if arr.dtype.kind == "f" and arr.dtype.itemsize >= 4:
                x = arr.astype(np.float32)
                scale = np.float32(np.max(np.abs(x)) / 127.0
                                   if x.size else 0.0)
                safe = scale if scale > 0 else np.float32(1.0)
                out[key] = np.clip(np.round(x / safe),
                                   -127, 127).astype(np.int8)
                out[Q8_KEY_PREFIX + key] = scale
            else:
                out[key] = arr
        flat = out
    buf = io.BytesIO()
    np.savez(buf, **pack_dtypes(flat))
    return buf.getvalue()


def decode_pytree(data: bytes):
    """npz bytes → pytree (dicts/lists of numpy arrays).  Self-describing
    inverse of :func:`encode_pytree`: ``__dt__:`` markers restore exact
    non-native dtypes, ``__q8s__:`` markers dequantize int8 leaves."""
    with np.load(io.BytesIO(data)) as z:
        flat = unpack_dtypes({k: z[k] for k in z.files})
    scales = {k[len(Q8_KEY_PREFIX):]: flat[k] for k in flat
              if k.startswith(Q8_KEY_PREFIX)}
    if scales:
        flat = {k: v for k, v in flat.items()
                if not k.startswith(Q8_KEY_PREFIX)}
        for key, scale in scales.items():
            flat[key] = flat[key].astype(np.float32) * np.float32(scale)
    return unflatten_pytree(flat)


def pack_frame(header: Dict, body: bytes = b"") -> bytes:
    hdr = json.dumps(header, separators=(",", ":")).encode()
    return (_U32.pack(4 + len(hdr) + len(body)) + _U32.pack(len(hdr))
            + hdr + body)


def split_frame(payload: bytes) -> Tuple[Dict, bytes]:
    if len(payload) < 4:
        raise ProtocolError("truncated frame")
    (hlen,) = _U32.unpack_from(payload)
    if 4 + hlen > len(payload):
        raise ProtocolError("header length exceeds frame")
    try:
        header = json.loads(payload[4:4 + hlen])
    except ValueError as e:
        raise ProtocolError(f"bad header JSON: {e}") from None
    if not isinstance(header, dict):
        raise ProtocolError("header must be a JSON object")
    return header, payload[4 + hlen:]


async def read_frame(reader: asyncio.StreamReader
                     ) -> Optional[Tuple[Dict, bytes]]:
    """One frame off an asyncio stream; None on clean EOF."""
    try:
        raw = await reader.readexactly(4)
    except (asyncio.IncompleteReadError, ConnectionResetError):
        return None
    (n,) = _U32.unpack(raw)
    if n < 4 or n > MAX_FRAME_BYTES:
        raise ProtocolError(f"frame length {n} out of bounds")
    try:
        payload = await reader.readexactly(n)
    except (asyncio.IncompleteReadError, ConnectionResetError):
        return None
    return split_frame(payload)


def _no_nagle(sock_like) -> None:
    """Frames are small request/reply pairs: Nagle + delayed ACK would add
    ~40ms per RPC on loopback, drowning the micro-batch win."""
    try:
        sock_like.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    except (OSError, AttributeError):
        pass


def _jsonable(stats: Dict) -> Dict:
    out = {}
    for k, v in stats.items():
        if isinstance(v, str):
            out[k] = v          # e.g. delta_codec
        elif isinstance(v, (float, np.floating)):
            out[k] = float(v)
        elif isinstance(v, (int, np.integer)):
            out[k] = int(v)
    return out


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

class _Record:
    """One open ticket: the server-side Ticket plus the event POLL waits
    on (set when a flush turns the ticket terminal), for served tickets
    the pre-encoded npz reply body, and for tickets lost to a poisoned
    flush the failure message (see ``TransportServer._resolve`` /
    ``_safe_call``)."""

    __slots__ = ("ticket", "event", "user", "encoded", "failed", "codec")

    def __init__(self, ticket, user, codec: str = "fp32"):
        self.ticket = ticket
        self.event = asyncio.Event()
        self.user = user
        self.encoded: Optional[bytes] = None
        self.failed: Optional[str] = None
        # reply-body codec negotiated at SUBMIT: "int8" only when the
        # server runs a quantized wire AND this client declared codec_ok
        self.codec = codec


class _Conn:
    __slots__ = ("records", "next_tid")

    def __init__(self):
        self.records: Dict[int, _Record] = {}
        self.next_tid = 0


class TransportServer:
    """Bridges concurrent socket connections into one
    :class:`PersonalizationServer`'s submit/flush/poll surface.

    Parameters
    ----------
    server        : the PersonalizationServer being fronted
    host, port    : bind address (``port=0`` = ephemeral; ``self.port``
                    holds the bound port after :meth:`start`)
    flush_ms      : deadline flush — a partial queue older than this is
                    flushed by timer (a full ``max_pending`` queue flushes
                    synchronously inside submit, as in-process)
    window_ms     : optional wall-clock aggregation-window timer driving
                    ``advance_window`` (None = windows advance only via
                    ADVANCE frames or the owning process)
    max_inflight  : server-wide bound on open tickets → ``BUSY server``
    conn_inflight : per-connection bound on open tickets → ``BUSY
                    connection``
    per-user      : with the fronted server's ``user_cap`` set, a user's
                    *queued* submissions in the current window are bounded
                    by it → ``BUSY user`` (cheaper than burning a queue
                    slot on a request the ring would refuse as "capped")

    Everything runs on one event loop; cohort compute blocks it for the
    duration of a flush, which is exactly the micro-batch amortization the
    serving stack is built around.
    """

    def __init__(self, server, *, host: str = "127.0.0.1", port: int = 0,
                 flush_ms: float = 10.0, window_ms: Optional[float] = None,
                 max_inflight: int = 256, conn_inflight: int = 64,
                 wire_codec: Optional[str] = None):
        self.server = server
        self.host = host
        spec = getattr(server, "personal_subset", None)
        # resolved once: the leaf paths stamped into subset reply headers
        # and matched against clients' subset_ok declarations
        self._subset_desc = spec.descriptor(server.params) \
            if spec is not None else None
        # the wire codec follows the fronted server's banking codec unless
        # overridden; int8 bodies still reach only codec_ok clients
        if wire_codec is None:
            wire_codec = getattr(server, "delta_dtype", "fp32")
        if wire_codec not in WIRE_CODECS:
            raise ValueError(f"wire_codec must be one of {WIRE_CODECS}, "
                             f"got {wire_codec!r}")
        self.wire_codec = wire_codec
        self.requested_port = port
        self.flush_ms = flush_ms
        self.window_ms = window_ms
        self.max_inflight = max_inflight
        self.conn_inflight = conn_inflight
        self.port: Optional[int] = None
        self._srv: Optional[asyncio.AbstractServer] = None
        self._conns: set = set()
        self._writers: set = set()
        self._tasks: set = set()
        self._inflight = 0
        self._flush_handle = None
        self._window_handle = None
        self.stats = {"connections": 0, "frames": 0, "busy": 0,
                      "timer_flushes": 0, "window_advances": 0,
                      "failed_flushes": 0}

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> "TransportServer":
        self._srv = await asyncio.start_server(self._handle, self.host,
                                               self.requested_port)
        self.port = self._srv.sockets[0].getsockname()[1]
        if self.window_ms is not None:
            self._window_handle = asyncio.get_running_loop().call_later(
                self.window_ms / 1e3, self._on_window_timer)
        return self

    async def stop(self) -> None:
        """Clean shutdown: stop listening, drop connections, cancel
        timers.  Queued-but-unflushed requests stay in the fronted
        server's queue (its owner may still flush them)."""
        for h in (self._flush_handle, self._window_handle):
            if h is not None:
                h.cancel()
        self._flush_handle = self._window_handle = None
        if self._srv is not None:
            self._srv.close()
        for w in list(self._writers):
            w.close()
        # a handler parked in a long POLL wait is not woken by its writer
        # closing — cancel outright so shutdown never strands a task
        for t in list(self._tasks):
            t.cancel()
        if self._tasks:
            await asyncio.gather(*list(self._tasks),
                                 return_exceptions=True)
        if self._srv is not None:
            # last: on Python >= 3.12 wait_closed also waits for every
            # open connection, so it would hang on a parked handler
            await self._srv.wait_closed()

    async def serve_forever(self, *, announce: bool = False) -> None:
        if self._srv is None:
            await self.start()
        if announce:
            print(f"transport: listening on {self.host}:{self.port} "
                  f"(wire protocol v{PROTOCOL_VERSION})", flush=True)
        try:
            await self._srv.serve_forever()
        finally:
            await self.stop()

    # -- timers ------------------------------------------------------------

    def _sync_flush_timer(self) -> None:
        """The deadline belongs to the oldest queued request: armed when
        the queue goes non-empty, cancelled the moment a flush empties it
        (a stale timer would fire mid-next-batch and split its cohort)."""
        if len(self.server.batcher):
            if self._flush_handle is None:
                self._flush_handle = asyncio.get_running_loop().call_later(
                    self.flush_ms / 1e3, self._on_flush_timer)
        elif self._flush_handle is not None:
            self._flush_handle.cancel()
            self._flush_handle = None

    def _on_flush_timer(self) -> None:
        self._flush_handle = None
        self.stats["timer_flushes"] += 1
        self._safe_call(self.server.flush)
        self._resolve()

    def _on_window_timer(self) -> None:
        self.stats["window_advances"] += 1
        self._safe_call(self.server.advance_window)
        self._resolve()
        self._sync_flush_timer()
        self._window_handle = asyncio.get_running_loop().call_later(
            self.window_ms / 1e3, self._on_window_timer)

    def _safe_call(self, fn) -> Tuple[Optional[object], Optional[str]]:
        """Run a flush/advance without letting one poisoned batch kill
        the event loop: a cohort call raising (bad shapes, missing keys —
        remote clients send arbitrary pytrees) has already consumed the
        drained queue, so every still-queued ticket's batch is gone.
        Those tickets fail with the cause; the server keeps serving."""
        try:
            return fn(), None
        except Exception as e:      # noqa: BLE001 — remote input boundary
            msg = f"{type(e).__name__}: {e}"
            self._fail_queued(msg)
            return None, msg

    def _fail_queued(self, msg: str) -> None:
        self.stats["failed_flushes"] += 1
        for conn in self._conns:
            for rec in conn.records.values():
                if rec.ticket.status == "queued" \
                        and not rec.event.is_set():
                    rec.failed = msg
                    rec.event.set()

    def _resolve(self) -> None:
        """Wake every POLL waiter whose ticket a flush just turned
        terminal, and micro-batch the response path: the heads this flush
        served are encoded from ONE stacked gather + ONE host transfer
        (per-ticket npz slicing in numpy) instead of two eager gather
        dispatches and a device sync per POLL — the wire must not forfeit
        the batching the cohort call just won.

        Refused tickets (dropped/capped) — and handle-less done tickets,
        which the per-POLL fallback resolves — carry no body here.  The
        gather is PER TICKET HANDLE, not per user: each record's head
        comes from its own ticket's (bank, row), grouped by bank into one
        ``jnp.take`` + one transfer each (steady state: one bank per
        flush), so an older ticket's body is never aliased to the user's
        newest head.  (An executor-thread variant of the blocking
        ``device_get`` was measured and rejected: on CPU the PJRT
        client serializes with the loop thread's dispatches and the hop
        costs more than it overlaps.)"""
        done = []
        horizon = self.server.window - self.server.ring.windows + 1
        for conn in self._conns:
            for rec in conn.records.values():
                if rec.ticket.status != "queued" and not rec.event.is_set():
                    # retired-window tickets are NOT encoded: their poll
                    # must report superseded, not a stale body
                    if rec.ticket.status == "done" \
                            and rec.ticket.head is not None \
                            and rec.ticket.window >= horizon:
                        done.append(rec)
                    else:
                        rec.event.set()
        if not done:
            return
        import jax
        import jax.numpy as jnp
        groups: Dict[int, Tuple[object, list]] = {}
        for rec in done:
            bank, row = rec.ticket.head
            groups.setdefault(id(bank), (bank, []))[1].append((rec, row))
        for bank, pairs in groups.values():
            rows = jnp.asarray([r for _, r in pairs], jnp.int32)
            # quantized banking serves LAZY head handles (no .stacked):
            # .rows() is the fused snapshot − scale·q gather — still one
            # device gather + one transfer for the whole group
            if hasattr(bank, "rows"):
                gathered = bank.rows(rows)
            else:
                gathered = jax.tree.map(
                    lambda x: jnp.take(x, rows, axis=0), bank.stacked)
            host = jax.device_get(gathered)
            for i, (rec, _) in enumerate(pairs):
                rec.encoded = encode_pytree(
                    jax.tree.map(lambda x: x[i], host), codec=rec.codec)
                rec.event.set()

    # -- connection handling -----------------------------------------------

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        conn = _Conn()
        _no_nagle(writer.get_extra_info("socket"))
        self._conns.add(conn)
        self._writers.add(writer)
        self._tasks.add(asyncio.current_task())
        self.stats["connections"] += 1
        try:
            while True:
                frame = await read_frame(reader)
                if frame is None:
                    break
                self.stats["frames"] += 1
                header, body = frame
                try:
                    reply, rbody = await self._dispatch(conn, header, body)
                except (ProtocolError, KeyError, TypeError,
                        ValueError) as e:
                    reply, rbody = {"op": "ERR", "code": "bad_request",
                                    "error": str(e)}, b""
                writer.write(pack_frame(reply, rbody))
                await writer.drain()
        except (ConnectionResetError, BrokenPipeError, ProtocolError):
            pass
        finally:
            # a dead connection releases its open tickets (backpressure
            # slots must not leak); the server-side work still completes
            self._inflight -= len(conn.records)
            conn.records.clear()
            self._conns.discard(conn)
            self._writers.discard(writer)
            self._tasks.discard(asyncio.current_task())
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError, OSError):
                pass

    async def _dispatch(self, conn: _Conn, header: Dict,
                        body: bytes) -> Tuple[Dict, bytes]:
        op = header.get("op")
        if op == "SUBMIT":
            return self._op_submit(conn, header, body)
        if op == "POLL":
            return await self._op_poll(conn, header)
        if op == "HEAD":
            return self._op_head(header)
        if op == "STATS":
            return self._op_stats()
        if op == "FLUSH":
            served, err = self._safe_call(self.server.flush)
            self._resolve()
            self._sync_flush_timer()
            if err is not None:
                return {"op": "ERR", "code": "flush_failed",
                        "error": err}, b""
            return {"op": "OK", "served": served}, b""
        if op == "ADVANCE":
            # flush=false models a window boundary firing while requests
            # are still queued: they become stragglers, recomputed against
            # their stamped snapshot at the next flush
            _, err = self._safe_call(lambda: self.server.advance_window(
                flush=bool(header.get("flush", True))))
            self._resolve()
            self._sync_flush_timer()
            if err is not None:
                return {"op": "ERR", "code": "flush_failed",
                        "error": err}, b""
            return {"op": "OK", "window": self.server.window}, b""
        return {"op": "ERR", "code": "unknown_op",
                "error": f"unknown op {op!r}"}, b""

    def _subset_refusal(self, header: Dict) -> Optional[Tuple[Dict, bytes]]:
        """Typed ERR for pre-subset clients against a subset server: a head
        body would be a *partial* pytree — a client that has not declared
        ``subset_ok`` would silently treat it as the full model."""
        if self._subset_desc is not None and not header.get("subset_ok"):
            return {"op": "ERR", "code": "subset_unsupported",
                    "error": "server personalizes a param subset "
                             f"(subset={self._subset_desc}); declare "
                             "subset_ok and merge heads with "
                             "repro.core.merge_subset"}, b""
        return None

    def _op_submit(self, conn: _Conn, header: Dict,
                   body: bytes) -> Tuple[Dict, bytes]:
        refusal = self._subset_refusal(header)
        if refusal is not None:
            return refusal
        user = header["user"]
        mode = header.get("mode", "C")
        busy_scope = None
        if self._inflight >= self.max_inflight:
            busy_scope = "server"
        elif len(conn.records) >= self.conn_inflight:
            busy_scope = "connection"
        else:
            cap = self.server.ring.user_cap
            if cap is not None:
                # the user's consumed window budget = rows the ring
                # already admitted + submissions queued on ANY connection
                # (one user may fan out over several) — refusing here is
                # cheaper than burning a queue slot and a cohort row on a
                # request the ring would refuse as "capped"
                window = self.server.window
                used = self.server.ring.admitted_rows(user)
                for cn in self._conns:
                    used += sum(1 for r in cn.records.values()
                                if r.user == user
                                and r.ticket.status == "queued"
                                and r.ticket.stamp == window)
                if used >= cap:
                    busy_scope = "user"
        if busy_scope is not None:
            self.stats["busy"] += 1
            return {"op": "BUSY", "scope": busy_scope,
                    "open": self._inflight}, b""
        if mode not in self.server.engines:
            return {"op": "ERR", "code": "bad_mode",
                    "error": f"mode {mode!r} not enabled; "
                             f"have {sorted(self.server.engines)}"}, b""
        # decode BEFORE the flush-capable submit: an undecodable body is a
        # bad frame from this one client — nothing was queued or drained,
        # so it must not be treated as a poisoned flush
        try:
            batch = decode_pytree(body)
        except Exception as e:      # noqa: BLE001 — remote input boundary
            return {"op": "ERR", "code": "bad_request",
                    "error": f"undecodable npz body: {e}"}, b""
        try:
            ticket = self.server.submit(user, batch, mode=mode)
        except Exception as e:      # noqa: BLE001 — the submit may have
            # auto-flushed a full queue, and THIS request's batch may be
            # the poison: the drain is spent, so fail the queued tickets
            # and report the cause instead of killing the connection
            msg = f"{type(e).__name__}: {e}"
            self._fail_queued(msg)
            self._resolve()
            return {"op": "ERR", "code": "server_error", "error": msg}, b""
        tid = conn.next_tid
        conn.next_tid += 1
        codec = "int8" if (self.wire_codec == "int8"
                           and header.get("codec_ok")) else "fp32"
        conn.records[tid] = _Record(ticket, user, codec=codec)
        self._inflight += 1
        # a full queue already flushed inside submit; otherwise the
        # deadline timer guarantees the partial queue drains within
        # flush_ms
        self._sync_flush_timer()
        self._resolve()
        return {"op": "OK", "ticket": tid, "window": ticket.stamp}, b""

    async def _op_poll(self, conn: _Conn,
                       header: Dict) -> Tuple[Dict, bytes]:
        refusal = self._subset_refusal(header)
        if refusal is not None:
            return refusal
        tid = int(header["ticket"])
        rec = conn.records.get(tid)
        if rec is None:
            return {"op": "ERR", "code": "unknown_ticket",
                    "error": f"no open ticket {tid}"}, b""
        wait_ms = header.get("wait_ms")
        if wait_ms and rec.ticket.status == "queued":
            try:
                await asyncio.wait_for(rec.event.wait(),
                                       float(wait_ms) / 1e3)
            except asyncio.TimeoutError:
                pass
        status = rec.ticket.status
        if rec.failed is not None:
            # the ticket's batch died with a poisoned flush: terminal
            del conn.records[tid]
            self._inflight -= 1
            return {"op": "ERR", "code": "server_error",
                    "error": f"request lost to a failed flush "
                             f"({rec.failed}); re-submit"}, b""
        if status == "queued":
            return {"op": "OK", "status": "queued"}, b""
        # terminal either way: the backpressure slot frees NOW
        del conn.records[tid]
        self._inflight -= 1
        ok = {"op": "OK", "status": "done", "window": self.server.window,
              "codec": rec.codec}
        if self._subset_desc is not None:
            ok["subset"] = self._subset_desc
        if rec.encoded is not None:
            return ok, rec.encoded
        try:
            head = self.server.poll(rec.ticket)
        except RuntimeError as e:
            if status in ("dropped", "capped"):
                code = status
            elif rec.ticket.window >= 0 and rec.ticket.window < (
                    self.server.window - self.server.ring.windows + 1):
                code = "superseded"
            else:
                code = "evicted"
            return {"op": "ERR", "code": code, "error": str(e)}, b""
        return ok, encode_pytree(head, codec=rec.codec)

    def _op_head(self, header: Dict) -> Tuple[Dict, bytes]:
        refusal = self._subset_refusal(header)
        if refusal is not None:
            return refusal
        user = header["user"]
        try:
            head = self.server.head(user)
        except KeyError:
            return {"op": "ERR", "code": "unknown_user",
                    "error": f"no cached head for {user!r}"}, b""
        codec = "int8" if (self.wire_codec == "int8"
                           and header.get("codec_ok")) else "fp32"
        ok = {"op": "OK", "user": user, "codec": codec}
        if self._subset_desc is not None:
            ok["subset"] = self._subset_desc
        return ok, encode_pytree(head, codec=codec)

    def _op_stats(self) -> Tuple[Dict, bytes]:
        stats = _jsonable(self.server.stats)
        stats.update({f"transport_{k}": v
                      for k, v in _jsonable(self.stats).items()})
        stats["transport_inflight"] = self._inflight
        stats["window"] = self.server.window
        stats["wire_codec"] = self.wire_codec
        ok = {"op": "OK", "stats": stats}
        if self._subset_desc is not None:
            ok["subset"] = self._subset_desc
        return ok, b""


# ---------------------------------------------------------------------------
# clients
# ---------------------------------------------------------------------------

def _check_reply(header: Dict) -> Dict:
    op = header.get("op")
    if op == "BUSY":
        raise TransportBusy(header.get("scope", "server"),
                            int(header.get("open", -1)))
    if op == "ERR":
        raise TransportError(header.get("code", "error"),
                             header.get("error", ""))
    if op != "OK":
        raise ProtocolError(f"unexpected reply op {op!r}")
    return header


class TransportClient:
    """Blocking client library — what a second OS process uses.

    One RPC at a time per connection; every method is a single
    request/reply frame pair.  ``poll`` returns None while the ticket is
    queued and the head pytree once served; refusals raise
    :class:`TransportError` (``.code`` = dropped/capped/superseded/
    evicted) and backpressure raises :class:`TransportBusy`.

    Subset-aware: every request declares ``subset_ok``, and when the
    server personalizes a subset the served head is a *subset pytree* —
    ``last_subset`` holds the reply's leaf-path descriptor (None for
    full-model servers); merge with ``repro.core.merge_subset``.

    Codec-aware: ``codec="int8"`` declares ``codec_ok`` (accept int8 head
    bodies from a quantized-wire server) AND quantizes this client's own
    SUBMIT batch bodies; the default ``"fp32"`` client negotiates nothing
    and always receives fp32 bodies.  ``last_codec`` records each head
    reply's actual body codec.
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0, *,
                 timeout: float = 30.0, codec: str = "fp32"):
        if codec not in WIRE_CODECS:
            raise ValueError(f"codec must be one of {WIRE_CODECS}, "
                             f"got {codec!r}")
        self.timeout = timeout
        self.codec = codec
        self.last_subset = None
        self.last_codec = None
        self._sock = socket.create_connection((host, port),
                                              timeout=timeout)
        _no_nagle(self._sock)

    def _recvn(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = self._sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("server closed the connection")
            buf.extend(chunk)
        return bytes(buf)

    def _rpc(self, header: Dict, body: bytes = b"",
             extra_wait_s: float = 0.0) -> Tuple[Dict, bytes]:
        self._sock.settimeout(self.timeout + extra_wait_s)
        self._sock.sendall(pack_frame(header, body))
        (n,) = _U32.unpack(self._recvn(4))
        if n < 4 or n > MAX_FRAME_BYTES:
            raise ProtocolError(f"frame length {n} out of bounds")
        rh, rb = split_frame(self._recvn(n))
        return _check_reply(rh), rb

    def submit(self, user, batch, mode: str = "C") -> int:
        h, _ = self._rpc({"op": "SUBMIT", "user": user, "mode": mode,
                          "subset_ok": True,
                          "codec_ok": self.codec == "int8"},
                         encode_pytree(batch, codec=self.codec))
        return int(h["ticket"])

    def poll(self, ticket: int, wait_ms: Optional[float] = None):
        header = {"op": "POLL", "ticket": int(ticket), "subset_ok": True}
        if wait_ms is not None:
            header["wait_ms"] = float(wait_ms)
        h, b = self._rpc(header,
                         extra_wait_s=(wait_ms or 0.0) / 1e3)
        if h["status"] != "done":
            return None
        self.last_subset = h.get("subset")
        self.last_codec = h.get("codec", "fp32")
        return decode_pytree(b)

    def head(self, user):
        h, b = self._rpc({"op": "HEAD", "user": user, "subset_ok": True,
                          "codec_ok": self.codec == "int8"})
        self.last_subset = h.get("subset")
        self.last_codec = h.get("codec", "fp32")
        return decode_pytree(b)

    def stats(self) -> Dict:
        h, _ = self._rpc({"op": "STATS"})
        return h["stats"]

    def flush(self) -> int:
        h, _ = self._rpc({"op": "FLUSH"})
        return int(h["served"])

    def advance(self, flush: bool = True) -> int:
        h, _ = self._rpc({"op": "ADVANCE", "flush": flush})
        return int(h["window"])

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass

    def __enter__(self) -> "TransportClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class AsyncTransportClient:
    """Asyncio twin of :class:`TransportClient` — the load generator runs
    N of these concurrently on one event loop.  Subset- and codec-aware
    like the blocking client (``subset_ok`` declared, ``codec=`` opt-in,
    ``last_subset``/``last_codec`` recorded)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0, *,
                 codec: str = "fp32"):
        if codec not in WIRE_CODECS:
            raise ValueError(f"codec must be one of {WIRE_CODECS}, "
                             f"got {codec!r}")
        self.host = host
        self.port = port
        self.codec = codec
        self.last_subset = None
        self.last_codec = None
        self._reader: Optional[asyncio.StreamReader] = None
        self._writer: Optional[asyncio.StreamWriter] = None

    async def connect(self) -> "AsyncTransportClient":
        self._reader, self._writer = await asyncio.open_connection(
            self.host, self.port)
        _no_nagle(self._writer.get_extra_info("socket"))
        return self

    async def _rpc(self, header: Dict,
                   body: bytes = b"") -> Tuple[Dict, bytes]:
        self._writer.write(pack_frame(header, body))
        await self._writer.drain()
        frame = await read_frame(self._reader)
        if frame is None:
            raise ConnectionError("server closed the connection")
        rh, rb = frame
        return _check_reply(rh), rb

    async def submit(self, user, batch, mode: str = "C") -> int:
        h, _ = await self._rpc({"op": "SUBMIT", "user": user, "mode": mode,
                                "subset_ok": True,
                                "codec_ok": self.codec == "int8"},
                               encode_pytree(batch, codec=self.codec))
        return int(h["ticket"])

    async def poll(self, ticket: int, wait_ms: Optional[float] = None):
        header = {"op": "POLL", "ticket": int(ticket), "subset_ok": True}
        if wait_ms is not None:
            header["wait_ms"] = float(wait_ms)
        h, b = await self._rpc(header)
        if h["status"] != "done":
            return None
        self.last_subset = h.get("subset")
        self.last_codec = h.get("codec", "fp32")
        return decode_pytree(b)

    async def head(self, user):
        h, b = await self._rpc({"op": "HEAD", "user": user,
                                "subset_ok": True,
                                "codec_ok": self.codec == "int8"})
        self.last_subset = h.get("subset")
        self.last_codec = h.get("codec", "fp32")
        return decode_pytree(b)

    async def stats(self) -> Dict:
        h, _ = await self._rpc({"op": "STATS"})
        return h["stats"]

    async def flush(self) -> int:
        h, _ = await self._rpc({"op": "FLUSH"})
        return int(h["served"])

    async def advance(self, flush: bool = True) -> int:
        h, _ = await self._rpc({"op": "ADVANCE", "flush": flush})
        return int(h["window"])

    async def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            try:
                await self._writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError, OSError):
                pass


# ---------------------------------------------------------------------------
# loopback selftest (the CI transport-smoke entry point)
# ---------------------------------------------------------------------------

def _selftest(n_clients: int, rounds: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import PersAFLConfig
    from repro.serving import PersonalizationServer

    d = 16
    rng = np.random.RandomState(0)

    def loss(p, b):
        logits = b["x"] @ p["w"] + p["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(jax.nn.one_hot(b["y"], 4) * logp, -1))

    params = {"w": jnp.asarray(0.1 * rng.randn(d, 4).astype(np.float32)),
              "b": jnp.zeros((4,))}
    pcfg = PersAFLConfig(option="C", lam=20.0, inner_steps=5,
                         inner_eta=0.05, beta=0.5)
    batches = [{"x": rng.randn(8, d).astype(np.float32),
                "y": rng.randint(0, 4, 8).astype(np.int32)}
               for _ in range(n_clients)]

    async def run() -> Dict:
        psrv = PersonalizationServer(params, loss, pcfg, modes=("C",),
                                     max_pending=n_clients)
        ts = await TransportServer(psrv, flush_ms=20.0,
                                   max_inflight=4 * n_clients).start()

        async def one_client(u: int) -> None:
            c = await AsyncTransportClient("127.0.0.1", ts.port).connect()
            for _ in range(rounds):
                tid = await c.submit(f"user{u}", batches[u], mode="C")
                head = await c.poll(tid, wait_ms=30_000)
                assert head is not None, "poll timed out"
                assert all(np.all(np.isfinite(leaf))
                           for leaf in jax.tree.leaves(head))
            again = await c.head(f"user{u}")
            assert all(np.array_equal(a, b) for a, b in
                       zip(jax.tree.leaves(head), jax.tree.leaves(again)))
            await c.close()

        await asyncio.gather(*(one_client(u) for u in range(n_clients)))
        admin = await AsyncTransportClient("127.0.0.1", ts.port).connect()
        await admin.advance()
        stats = await admin.stats()
        await admin.close()
        await ts.stop()
        return stats

    stats = asyncio.run(run())
    assert stats["host_materializations"] == 0, stats
    assert stats["cached_heads"] == n_clients, stats
    print(f"transport_selftest,clients={n_clients},rounds={rounds},"
          f"frames={stats['transport_frames']},"
          f"timer_flushes={stats['transport_timer_flushes']},"
          f"host_materializations={stats['host_materializations']},ok",
          flush=True)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(
        description="loopback transport selftest (CI transport-smoke)")
    ap.add_argument("--clients", type=int, default=6)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()
    # under ``python -m`` this file runs as __main__ while the package
    # __init__ imported it once already — delegate to the canonical
    # module instance so there is exactly one set of classes
    from repro.serving import transport as _canonical
    _canonical._selftest(args.clients, args.rounds)
