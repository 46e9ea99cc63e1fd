"""TCP RPC transport: framed request/response with task-code dispatch.

The rDSN network layer this build re-provides (SURVEY.md §2.4 'RPC /
network'): a serverlet registers handlers by task-code name
(reference: storage_serverlet::register_rpc_handlers,
src/server/pegasus_read_service.h:36-84) and a connection-pooling client
issues pipelined request/response calls with per-call timeouts
(reference: rrdb_client over partition_resolver::call_op,
src/include/rrdb/rrdb.client.h:41-120).

Frame: u32 LE payload length | payload. Payload = codec-encoded RpcHeader
followed by the body bytes. Requests and responses share the frame; the
`is_response` flag disambiguates (one socket carries both directions).
Every connection is full-duplex: a reader thread matches responses to
pending sequence numbers, so many calls can be in flight at once.
"""

import socket
import socketserver
import struct
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass

from . import codec
from ..runtime.fail_points import FailPointError, fail_point
from ..runtime.perf_counters import counters
from ..runtime.tasking import spawn_thread
from ..runtime.tracing import REQUEST_TRACER, TraceContext


# rDSN-style error codes carried at the RPC layer (engine-level status stays
# in each response body's `error` field, like the reference splits dsn::error
# from rocksdb status)
ERR_OK = 0
ERR_HANDLER_NOT_FOUND = 1
ERR_TIMEOUT = 2
ERR_INVALID_STATE = 3       # e.g. not primary / partition not served here
ERR_OBJECT_NOT_FOUND = 4    # no such app / partition
ERR_BUSY = 5
ERR_INVALID_DATA = 6
ERR_NETWORK_FAILURE = 7
ERR_FORWARD_TO_PRIMARY = 8  # follower meta: retry against the leader


@dataclass
class RpcHeader:
    seq: int = 0
    code: str = ""
    app_id: int = 0
    partition_index: int = 0
    partition_hash: int = 0
    error: int = 0          # response-only: rpc-level error
    error_text: str = ""
    is_response: bool = False
    # request tracing (runtime/tracing.py RequestTracer): the caller's
    # trace context rides every request frame; 0 = untraced. Appended
    # last per the codec's append-only evolution rule, so frames from an
    # older encoder still decode (the fields default).
    trace_id: int = 0
    trace_sampled: bool = False
    # True on every frame of a connection that carries ONE partition's
    # traffic only (ConnectionPool shard keys). A partition-group router
    # may hand such a connection off to the owning group executor wholesale
    # (replication/serve_groups.py); unsharded connections stay on the
    # per-frame relay path. Appended last (evolution rule).
    sharded: bool = False


class RpcError(Exception):
    def __init__(self, err: int, text: str = ""):
        super().__init__(f"rpc error {err}: {text}")
        self.err = err
        self.text = text


def _send_frame(sock, header: RpcHeader, body: bytes, lock=None) -> None:
    h = codec.encode(header)
    hl = len(h)
    # one buffer, one copy of the body (the old payload+frame concats
    # copied large values twice per send)
    frame = bytearray(8 + hl + len(body))
    struct.pack_into("<II", frame, 0, 4 + hl + len(body), hl)
    frame[8 : 8 + hl] = h
    frame[8 + hl :] = body
    if lock:
        with lock:
            sock.sendall(frame)
    else:
        sock.sendall(frame)


# the native read data plane's attribution counters (ISSUE 20): waves
# drained by the C reader and vectored sends. With PEGASUS_NATIVE=0 all
# three flatline — the metric-history fallback regression reads these.
_C_WAVE = counters.rate("native.wave_count")
_C_WRITEV = counters.rate("native.writev_count")
_C_WRITEV_BYTES = counters.rate("native.writev_bytes")


def _native_writer():
    """-> the fastcodec module when the native vectored writer should be
    used, else None (knob off, extension absent/stale, or the
    ``serve.native`` fail point forcing the pure-Python twin)."""
    from .. import native

    if not native.native_on():
        return None
    fc = native.fastcodec()
    if fc is None or not hasattr(fc, "sendmsg_frames"):
        return None
    try:
        if fail_point("serve.native") is not None:
            return None
    except FailPointError:
        return None
    return fc


def _send_encoded_frames(sock, enc, lock=None) -> None:
    """Vectored frame write: `enc` is [(header_bytes, body), ...] and the
    whole wave leaves in one call. Native path: fastcodec.sendmsg_frames
    gathers length prefixes + headers + bodies into iovecs and sendmsg()s
    with the GIL released (zero body copies). Fallback: one coalesced
    bytearray + sendall. Both write the exact same bytes in the exact
    same order — the byte-identity test pins that."""
    fc = _native_writer()
    ctx = lock if lock is not None else nullcontext()
    with ctx:
        if fc is not None:
            fd = sock.fileno()
            if fd >= 0:
                sent = fc.sendmsg_frames(fd, enc)
                _C_WRITEV.increment()
                _C_WRITEV_BYTES.increment(sent)
                return
        buf = bytearray()
        for h, b in enc:
            buf += struct.pack("<II", 4 + len(h) + len(b), len(h))
            buf += h
            buf += b
        sock.sendall(buf)


class _FrameReader:
    """Buffered framing for a socket with a SINGLE reader thread: one
    kernel recv typically yields several pipelined frames (length word +
    header + body used to cost 2+ recv syscalls per frame)."""

    __slots__ = ("sock", "buf", "pos")

    def __init__(self, sock, initial: bytes = b""):
        self.sock = sock
        self.buf = bytearray(initial)
        self.pos = 0

    def _fill(self, need: int) -> None:
        buf = self.buf
        if self.pos and (len(buf) == self.pos or self.pos > (1 << 16)):
            del buf[: self.pos]  # compact consumed bytes
            self.pos = 0
        while len(buf) - self.pos < need:
            chunk = self.sock.recv(1 << 16)
            if not chunk:
                raise ConnectionError("peer closed")
            buf += chunk

    def frame(self):
        self._fill(4)
        pos = self.pos
        (plen,) = struct.unpack_from("<I", self.buf, pos)
        self._fill(4 + plen)
        pos = self.pos  # _fill may have compacted
        (hlen,) = struct.unpack_from("<I", self.buf, pos + 4)
        if plen < 4 or hlen > plen - 4:
            # same validation, same error class as the C reader — the
            # adversarial-frame differential test pins the parity
            raise codec.CodecError("corrupt frame lengths")
        mv = memoryview(self.buf)
        try:
            header = codec.decode(RpcHeader, mv[pos + 8 : pos + 8 + hlen])
            body = bytes(mv[pos + 8 + hlen : pos + 4 + plen])  # ONE copy
        finally:
            mv.release()  # buf must be resizable before the next _fill
        self.pos = pos + 4 + plen
        return header, body

    def _buffered_frame(self) -> bool:
        """A complete frame sits in the buffer (no recv needed)?"""
        avail = len(self.buf) - self.pos
        if avail < 4:
            return False
        (plen,) = struct.unpack_from("<I", self.buf, self.pos)
        return avail >= 4 + plen

    def wave(self):
        """-> every complete frame currently available (blocking for the
        first): the pure-Python twin of fastcodec.FrameReader.read_wave."""
        out = [self.frame()]
        while self._buffered_frame():
            out.append(self.frame())
        return out


class _NativeFrameReader:
    """fastcodec.FrameReader wrapper: drains a pipelined frame wave in ONE
    C call (recv with the GIL released + header decode + body slicing),
    instead of re-entering Python per frame."""

    __slots__ = ("sock", "fr")

    def __init__(self, fc, sock, initial: bytes = b""):
        self.sock = sock
        self.fr = fc.FrameReader(codec._plan_of(RpcHeader))
        if initial:
            self.fr.feed(initial)

    def _fd(self):
        # resolve the fd per wave, never cache it: after sock.close() (a
        # timed-out connection being invalidated under this reader) the
        # number can be REUSED by a brand-new socket, and a cached fd
        # would recv another connection's bytes. fileno() on a closed
        # socket returns -1 -> EBADF -> clean reader exit.
        fd = self.sock.fileno()
        if fd < 0:
            raise ConnectionError("socket closed")
        return fd

    def wave(self):
        wave = self.fr.read_wave(self._fd())
        _C_WAVE.increment()
        return wave


def make_frame_reader(sock, initial: bytes = b""):
    """Best available frame reader for a blocking socket: the C wave
    drainer when PEGASUS_NATIVE is on, fastcodec is importable AND the
    RpcHeader plan compiled to a C plan (a Python-plan header would hand
    the C reader an incompatible object), else the buffered Python
    reader."""
    from .. import native

    if native.native_on():
        fc = native.fastcodec()
        if fc is not None and hasattr(fc, "FrameReader") \
                and isinstance(codec._plan_of(RpcHeader), fc.Plan):
            return _NativeFrameReader(fc, sock, initial)
    return _FrameReader(sock, initial)


class RpcServer:
    """Threaded TCP serverlet. Handlers: code -> fn(header, body) -> body.

    A handler may raise RpcError to return an rpc-level error. Handlers run
    on the connection's thread (the engine has its own locking)."""

    # requests run on a shared worker pool (a thread spawn per request cost
    # ~60us x thousands/s on the serving path). Requests beyond the pool
    # QUEUE (bounded dispatch — the old design spawned an unbounded raw
    # thread per overflow request), except PRIORITY_CODES: replication and
    # lifecycle RPCs keep the escape-hatch thread, because a pool whose 16
    # workers all sit in client_write waiting for secondary prepare acks
    # must still serve the prepares those acks depend on (the classic
    # distributed pool deadlock).
    POOL_WORKERS = 16
    PRIORITY_CODES = frozenset({
        "RPC_PREPARE", "RPC_LEARN", "RPC_FD_FAILURE_DETECTOR_PING",
        "RPC_LEARN_PREPARE", "RPC_LEARN_FETCH", "RPC_LEARN_TAIL",
        "RPC_LEARN_FINISH",
        "RPC_CONFIG_PROPOSAL_OPEN_REPLICA",
        "RPC_CONFIG_PROPOSAL_CLOSE_REPLICA",
    })

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self._handlers = {}
        self._middlewares = []   # fn(code, header, body, next) -> body
        from ..runtime.tasking import tracked_executor

        self._pool = tracked_executor(self.POOL_WORKERS,
                                      thread_name_prefix="rpc-serve")
        self._busy = 0
        self._busy_lock = threading.Lock()
        # live accepted connections: stop() shuts them down so a stopped
        # server looks like a KILLED one to its peers (in-flight calls
        # fail fast instead of dangling until the client timeout — the
        # chaos service-kill actor depends on this)
        self._conn_lock = threading.Lock()
        self._conns = set()  #: guarded_by self._conn_lock
        self._depth_gauge = counters.number("rpc.server.dispatch_queue_depth")
        outer = self

        class _Handler(socketserver.BaseRequestHandler):
            def handle(self):
                outer.serve_connection(self.request)

        class _Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._srv = _Server((host, port), _Handler)
        self.address = self._srv.server_address  # (host, actual_port)
        self._thread = spawn_thread(self._srv.serve_forever, daemon=True,
                                    start=False)

    def serve_connection(self, sock, initial: bytes = b"") -> None:
        """Serve one connection to exhaustion: drain pipelined frame waves
        (fastcodec.FrameReader when available — frame read + header decode
        stay in C for the whole wave) and dispatch each request."""
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            return
        wlock = threading.Lock()
        dispatch = self._dispatch
        with self._conn_lock:
            self._conns.add(sock)
        try:
            reader = make_frame_reader(sock, initial)
            while True:
                for header, body in reader.wave():
                    dispatch(sock, wlock, header, body)
        except (ConnectionError, OSError):
            pass
        finally:
            with self._conn_lock:
                self._conns.discard(sock)

    def serve_adopted(self, sock, initial: bytes = b"") -> None:
        """Adopt a connection accepted elsewhere (the partition-group
        router hands client sockets over with their already-read bytes);
        serving runs on a fresh daemon thread, closing the socket at EOF."""
        def run():
            try:
                self.serve_connection(sock, initial)
            finally:
                try:
                    sock.close()
                except OSError:
                    pass

        spawn_thread(run, daemon=True, name="rpc-adopted")

    def register(self, code: str, handler) -> None:
        self._handlers[code] = handler

    def register_serverlet(self, obj) -> None:
        """Register every (code, fn) pair from obj.rpc_handlers()."""
        for code, fn in obj.rpc_handlers().items():
            self.register(code, fn)

    def add_middleware(self, mw) -> None:
        """mw(code, header, body, next_fn) -> response body. The rDSN
        toollet seam: tracer/profiler/fault-injector wrap every handler."""
        self._middlewares.append(mw)

    def start(self) -> "RpcServer":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._srv.shutdown()
        self._srv.server_close()
        # shutdown (never close — the handler thread owns the fd and a
        # cross-thread close could race a reused descriptor) every live
        # connection: peers see EOF now, exactly like a process kill,
        # instead of requests silently dangling until their timeouts
        with self._conn_lock:
            conns = list(self._conns)
        for s in conns:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        self._pool.shutdown(wait=False)

    def _dispatch(self, sock, wlock, header: RpcHeader, body: bytes) -> None:
        parsed = time.perf_counter()   # rpc.queue starts here
        # serve.dispatch: the chaos seam for a wedged group executor —
        # sleep(ms) stalls this connection's whole dispatch loop (frames
        # queue in the kernel buffer, the client's timeout is the bound),
        # raise(msg) rejects the request with ERR_BUSY instead of serving
        try:
            fail_point("serve.dispatch")
        except FailPointError as e:
            resp = RpcHeader(seq=header.seq, code=header.code,
                             is_response=True, error=ERR_BUSY,
                             error_text=str(e))
            counters.rate("rpc.server.error_count").increment()
            if header.app_id:
                # tenant attribution (ISSUE 18): a rejected dispatch is
                # an error the TABLE saw, even though no replica handler
                # ran; no-op when the app_id is unmapped in this process
                from ..runtime.table_stats import TABLE_STATS

                TABLE_STATS.charge_app_error(header.app_id)
            try:
                _send_frame(sock, resp, b"", lock=wlock)
            except (ConnectionError, OSError):
                pass
            return
        if header.code in self.PRIORITY_CODES:
            with self._busy_lock:
                overflow = self._busy >= self.POOL_WORKERS
            if overflow:
                # liveness escape: replication/lifecycle must never queue
                # behind a pool full of work that is WAITING on them
                spawn_thread(self._serve_one, sock, wlock, header, body,
                             parsed, daemon=True)
                return
        with self._busy_lock:
            self._busy += 1
            depth = self._busy - self.POOL_WORKERS
        if depth > 0:
            self._depth_gauge.set(depth)
        try:
            self._pool.submit(self._serve_pooled, sock, wlock, header, body,
                              parsed)
        except RuntimeError:   # server stopping: pool already shut down
            with self._busy_lock:
                self._busy -= 1

    def _serve_pooled(self, sock, wlock, header, body, parsed) -> None:
        try:
            self._serve_one(sock, wlock, header, body, parsed)
        finally:
            with self._busy_lock:
                self._busy -= 1
                depth = self._busy - self.POOL_WORKERS
            self._depth_gauge.set(max(0, depth))

    def _serve_one(self, sock, wlock, header: RpcHeader, body: bytes,
                   parsed: float) -> None:
        resp = RpcHeader(seq=header.seq, code=header.code, is_response=True)
        out = b""
        t0 = time.perf_counter()
        # adopt the caller's trace context for the handler's whole stack
        # (replication, plog, engine spans all land in the same trace); a
        # frame without an id still closes rpc.server.<code> for the
        # stage totals
        ctx = (TraceContext(header.trace_id, header.trace_sampled,
                            remote=True) if header.trace_id else None)
        with REQUEST_TRACER.serve(ctx, header.code):
            # the frame's wait for this thread, recorded in its trace
            REQUEST_TRACER.event("rpc.queue", int((t0 - parsed) * 1e6))
            try:
                fn = self._handlers.get(header.code)
                if fn is None:
                    resp.error = ERR_HANDLER_NOT_FOUND
                    resp.error_text = header.code
                else:
                    call = fn
                    for mw in reversed(self._middlewares):
                        call = (lambda h, b, _mw=mw, _next=call:
                                _mw(h.code, h, b, _next))
                    out = call(header, body)
            except RpcError as e:
                resp.error, resp.error_text = e.err, e.text
            except Exception as e:  # handler bug -> error, not a dead connection
                resp.error, resp.error_text = ERR_INVALID_DATA, repr(e)
        counters.rate("rpc.server.qps").increment()
        counters.percentile("rpc.server.latency_us").set(
            int((time.perf_counter() - t0) * 1e6))
        if resp.error:
            counters.rate("rpc.server.error_count").increment()
        # encode + write, after the handler's span: in a onebox the
        # client's trace is still open and takes the record, a remote
        # view has finalized and only the totals move
        with REQUEST_TRACER.span_in(ctx, "rpc.reply", bytes=len(out)):
            try:
                _send_frame(sock, resp, out, lock=wlock)
            except (ConnectionError, OSError):
                pass


class RpcConnection:
    """One full-duplex client connection with pipelined calls.

    shard: any hashable marking this connection as carrying exactly ONE
    partition's traffic (the ConnectionPool's shard key). Sharded
    connections set RpcHeader.sharded on every frame, which lets a
    partition-group serving node hand the whole connection to the owning
    group executor instead of relaying frame by frame."""

    def __init__(self, addr, connect_timeout: float = 5.0, shard=None):
        self.addr = tuple(addr)
        self.shard = shard
        self._sock = socket.create_connection(self.addr, timeout=connect_timeout)
        self._sock.settimeout(None)
        # rpc frames are small request/response pairs: Nagle + delayed ACK
        # turns concurrent small calls into ~40ms stalls
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._wlock = threading.Lock()
        self._plock = threading.Lock()
        self._pending = {}   # seq -> (event, slot)
        self._seq = 0
        self._dead = None
        self._ev_pool = []   # recycled Events (success path only)
        self._reader = spawn_thread(self._read_loop, daemon=True)

    def _read_loop(self):
        try:
            reader = make_frame_reader(self._sock)
            while True:
                frames = reader.wave()
                # one lock round per WAVE: pipelined responses (call_many
                # peers, group-commit bursts) stop paying a lock handoff
                # per frame
                with self._plock:
                    ents = [(self._pending.pop(h.seq, None), h, b)
                            for h, b in frames]
                for ent, header, body in ents:
                    if ent:
                        ev, slot = ent
                        slot.append((header, body))
                        ev.set()
        except (ConnectionError, OSError) as e:
            self._dead = e
            with self._plock:
                pending = list(self._pending.values())
                self._pending.clear()
            for ev, slot in pending:
                slot.append(None)
                ev.set()

    def call(self, code: str, body: bytes, app_id: int = 0,
             partition_index: int = 0, partition_hash: int = 0,
             timeout: float = 10.0):
        """-> (RpcHeader, body bytes); raises RpcError on rpc-level failure."""
        if self._dead:
            raise RpcError(ERR_NETWORK_FAILURE, str(self._dead))
        with self._plock:
            self._seq += 1
            seq = self._seq
            # recycle Events from completed calls: one allocation
            # (Event + its Condition + lock) per RPC adds up at
            # thousands of calls/s
            ev = self._ev_pool.pop() if self._ev_pool else threading.Event()
            slot = []
            self._pending[seq] = (ev, slot)
        ctx = REQUEST_TRACER.current()
        header = RpcHeader(seq=seq, code=code, app_id=app_id,
                           partition_index=partition_index,
                           partition_hash=partition_hash,
                           trace_id=ctx.trace_id if ctx else 0,
                           trace_sampled=bool(ctx and ctx.sampled),
                           sharded=self.shard is not None)
        with REQUEST_TRACER.span(f"rpc.{code}", bytes=len(body)):
            try:
                _send_frame(self._sock, header, body, lock=self._wlock)
            except (ConnectionError, OSError) as e:
                with self._plock:
                    self._pending.pop(seq, None)
                raise RpcError(ERR_NETWORK_FAILURE, str(e))
            if not ev.wait(timeout):
                # do NOT recycle: the reader may still set this event later
                with self._plock:
                    self._pending.pop(seq, None)
                raise RpcError(ERR_TIMEOUT, f"{code} after {timeout}s")
        if not slot or slot[0] is None:
            raise RpcError(ERR_NETWORK_FAILURE, str(self._dead))
        rh, rbody = slot[0]
        # set + consumed: nobody else references this event again
        ev.clear()
        with self._plock:
            if len(self._ev_pool) < 64:
                self._ev_pool.append(ev)
        if rh.error != ERR_OK:
            raise RpcError(rh.error, rh.error_text)
        return rh, rbody

    def call_many(self, calls, timeout: float = 10.0):
        """Pipelined batch call: every request frame is buffered and
        leaves in ONE coalesced socket send (writev-style — the per-frame
        sendall of k small frames cost k syscalls and k wlock
        acquisitions), then the responses are collected in issue order.

        Each call is (code, body) or (code, body, app_id, pidx, phash) —
        the 5-tuple shape routes each frame like call() does, so the
        client's multi-partition fan-out (batch_get / scanner prefetch /
        duplicator shipping) pipelines through here too.

        -> [(RpcHeader, body)]; raises RpcError on the first failure. The
        replication catch-up path streams its backlog windows through
        here."""
        pend = self.call_many_send(calls)
        return self.call_many_collect(pend, calls, timeout)

    def call_many_send(self, calls):
        """Send half of call_many: one coalesced write, -> pending token.
        Lets a caller overlap waves across SEVERAL connections (fan-out
        sends first, then collects), so k partitions' worth of server work
        runs concurrently instead of lockstep."""
        if not calls:
            return []
        if self._dead:
            raise RpcError(ERR_NETWORK_FAILURE, str(self._dead))
        ctx = REQUEST_TRACER.current()
        sharded = self.shard is not None
        pend, enc, total = [], [], 0
        with self._plock:
            for call in calls:
                code, body = call[0], call[1]
                app_id, pidx, phash = (call[2], call[3], call[4]) \
                    if len(call) > 2 else (0, 0, 0)
                self._seq += 1
                seq = self._seq
                ev = self._ev_pool.pop() if self._ev_pool else threading.Event()
                slot = []
                self._pending[seq] = (ev, slot)
                pend.append((seq, ev, slot))
                header = RpcHeader(
                    seq=seq, code=code, app_id=app_id,
                    partition_index=pidx, partition_hash=phash,
                    trace_id=ctx.trace_id if ctx else 0,
                    trace_sampled=bool(ctx and ctx.sampled),
                    sharded=sharded)
                h = codec.encode(header)
                enc.append((h, body))
                total += 8 + len(h) + len(body)
        with REQUEST_TRACER.span("rpc.call_many", bytes=total,
                                 records=len(calls)):
            try:
                # vectored when native: the frame bodies go straight into
                # iovecs with the GIL released, instead of being copied
                # into one coalesced bytearray first
                _send_encoded_frames(self._sock, enc, lock=self._wlock)
            except (ConnectionError, OSError) as e:
                with self._plock:
                    for seq, _, _ in pend:
                        self._pending.pop(seq, None)
                raise RpcError(ERR_NETWORK_FAILURE, str(e))
        return pend

    def call_many_collect(self, pend, calls, timeout: float = 10.0):
        """Collect half of call_many: responses in issue order."""
        deadline = time.monotonic() + timeout
        out = []
        for i, (seq, ev, slot) in enumerate(pend):
            if not ev.wait(max(0.0, deadline - time.monotonic())):
                with self._plock:  # abandon everything still in flight
                    for s2, _, _ in pend[i:]:
                        self._pending.pop(s2, None)
                raise RpcError(ERR_TIMEOUT,
                               f"{calls[i][0]} after {timeout}s")
            if not slot or slot[0] is None:
                raise RpcError(ERR_NETWORK_FAILURE, str(self._dead))
            rh, rbody = slot[0]
            ev.clear()
            with self._plock:
                if len(self._ev_pool) < 64:
                    self._ev_pool.append(ev)
            if rh.error != ERR_OK:
                raise RpcError(rh.error, rh.error_text)
            out.append((rh, rbody))
        return out

    def close(self):
        try:
            self._sock.close()
        except OSError:
            pass


class ConnectionPool:
    """(addr, shard) -> RpcConnection cache with reconnect-on-failure.

    shard=None (default) is the classic one-connection-per-node behavior.
    A non-None shard keys a DEDICATED connection for one partition's
    traffic: the client's partition fan-out stops serializing behind a
    single socket, and a partition-group serving node can hand the whole
    connection to the owning group executor (RpcHeader.sharded)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._conns = {}

    def get(self, addr, shard=None) -> RpcConnection:
        addr = tuple(addr)
        key = (addr, shard)
        with self._lock:
            conn = self._conns.get(key)
        if conn is not None and not conn._dead:
            return conn
        # connect OUTSIDE the pool lock: a black-holed peer blocks
        # create_connection for its full timeout, and holding the pool-wide
        # lock through that would serialize every other caller (including
        # the replication write path) behind one dead host. The connect is
        # a wait of its own: a SYN the listener's backlog dropped comes
        # back a second later, inside whatever call needed the connection
        with REQUEST_TRACER.span("rpc.connect"):
            fresh = RpcConnection(addr, shard=shard)
        with self._lock:
            cur = self._conns.get(key)
            if cur is not None and not cur._dead and cur is not conn:
                fresh.close()  # lost the race to another connector
                return cur
            self._conns[key] = fresh
        return fresh

    def invalidate(self, addr) -> None:
        """Drop EVERY shard's connection to addr (a dead node is dead for
        all of its partitions)."""
        addr = tuple(addr)
        with self._lock:
            dead = [k for k in self._conns if k[0] == addr]
            conns = [self._conns.pop(k) for k in dead]
        for conn in conns:
            conn.close()

    def close(self):
        with self._lock:
            conns = list(self._conns.values())
            self._conns.clear()
        for c in conns:
            c.close()
