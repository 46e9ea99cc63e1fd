"""Native read data plane (ISSUE 20): C frame waves, vectored wave
writes, zero-copy mmap SSTs — and the byte-identical Python twins.

Three pinned properties:

  * differential errors: adversarial frames (corrupt length words,
    truncated payloads, garbage headers) fail IDENTICALLY through the C
    FrameReader and the pure-Python reader — same exception class for
    the same poison;
  * byte identity: the same pipelined get/multi_get/scanner wave against
    a PEGASUS_NATIVE=0 server and a =1 server produces identical wire
    bytes per sequence number, with the serve.native fail point armed
    or not;
  * mmap lifetime: an SST loaded through the zero-copy path stays
    readable after the file is unlinked (compaction deletes its inputs
    while readers may still hold their blocks).
"""

import contextlib
import os
import socket
import struct
import threading

import numpy as np
import pytest

from pegasus_tpu import native
from pegasus_tpu.base import key_schema
from pegasus_tpu.client import PegasusClient, StaticResolver
from pegasus_tpu.engine import EngineOptions
from pegasus_tpu.engine.replica_service import (RPC_GET, RPC_GET_SCANNER,
                                                RPC_MULTI_GET, RPC_SCAN,
                                                ReplicaService)
from pegasus_tpu.engine.server_impl import PegasusServer
from pegasus_tpu.rpc import codec
from pegasus_tpu.rpc import messages as msg
from pegasus_tpu.rpc.transport import (RpcConnection, RpcHeader, RpcServer,
                                       _FrameReader, make_frame_reader)
from pegasus_tpu.runtime import fail_points
from pegasus_tpu.runtime.perf_counters import counters

fc = native.fastcodec()
pytestmark = pytest.mark.skipif(
    fc is None, reason="fastcodec extension unavailable (no compiler?)")

APP_ID = 9
N_PARTITIONS = 2
TABLE = "native9"


def _frame(seq, code, body, pidx=0):
    h = codec.encode(RpcHeader(seq=seq, code=code, app_id=APP_ID,
                               partition_index=pidx))
    return struct.pack("<II", 4 + len(h) + len(body), len(h)) + h + body


def _c_reader():
    fc.register_error(codec.CodecError)
    plan = codec._fast_plan(RpcHeader, fc)
    assert isinstance(plan, fc.Plan)
    return fc.FrameReader(plan)


# ------------------------------------------------------------ wave parity


def _drain(reader_wave, n):
    """Waves until n frames arrived -> [(encoded header, body), ...]."""
    got = []
    while len(got) < n:
        got += [(codec.encode(h), body) for h, body in reader_wave()]
    return got


_PUT = "RPC_RRDB_RRDB_PUT"


@pytest.mark.parametrize("name,frames,sends", [
    ("one_frame", [(RPC_GET, b"a")], 1),
    ("nine_of_one_read_code", [(RPC_GET, b"k%d" % i) for i in range(9)], 1),
    ("codes_interleaved",
     [(RPC_GET, b"a"), (_PUT, b"w"), (RPC_MULTI_GET, b"m"), (RPC_GET, b"b"),
      (RPC_SCAN, b"s"), (RPC_GET, b"c"), (RPC_SCAN, b"t"), (_PUT, b"x")], 1),
    ("frame_split_across_two_sends",
     [(RPC_GET, b"a"), (RPC_SCAN, b"s" * 300), (RPC_GET, b"b")], 2),
])
def test_read_wave_matches_python_wave(name, frames, sends):
    """The C read_wave and the Python wave() hand dispatch the same
    (header, body) pairs in arrival order, whatever the codes and
    however the bytes were cut into sends."""
    blob = b"".join(_frame(i + 1, code, body)
                    for i, (code, body) in enumerate(frames))
    # two sends: the cut falls inside the second frame's body, and the
    # rest leaves only after the reader has parked on the first part
    cut = len(blob) if sends == 1 else len(_frame(1, *frames[0])) + 40

    def arrivals(make_wave):
        a, b = socket.socketpair()
        late = threading.Timer(0.05, a.sendall, (blob[cut:],))
        try:
            a.sendall(blob[:cut])
            late.start()
            return _drain(make_wave(b), len(frames))
        finally:
            if late.ident is not None:
                late.join()
            a.close()
            b.close()

    c_reader = _c_reader()
    c_got = arrivals(lambda b: lambda: c_reader.read_wave(b.fileno()))
    py_got = arrivals(lambda b: _FrameReader(b).wave)
    assert c_got == py_got, name
    assert [body for _, body in c_got] == [body for _, body in frames]
    assert [codec.decode(RpcHeader, h).seq for h, _ in c_got] \
        == list(range(1, len(frames) + 1))


def test_sendmsg_frames_matches_python_concat():
    """The vectored writer's bytes == the fallback bytearray's bytes."""
    h1 = codec.encode(RpcHeader(seq=3, code=RPC_GET, is_response=True))
    h2 = codec.encode(RpcHeader(seq=4, code=RPC_GET, is_response=True,
                                error=6, error_text="boom"))
    pairs = [(h1, b"value-one"), (h2, b""), (h1, os.urandom(4096))]
    expect = b"".join(
        struct.pack("<II", 4 + len(h) + len(b), len(h)) + h + b
        for h, b in pairs)
    a, b = socket.socketpair()
    try:
        sent = fc.sendmsg_frames(a.fileno(), pairs)
        assert sent == len(expect)
        got = bytearray()
        while len(got) < len(expect):
            got += b.recv(1 << 16)
        assert bytes(got) == expect
    finally:
        a.close()
        b.close()


def test_sendmsg_frames_peer_closed():
    a, b = socket.socketpair()
    b.close()
    try:
        h = codec.encode(RpcHeader(seq=1, code=RPC_GET, is_response=True))
        with pytest.raises((ConnectionError, OSError)):
            fc.sendmsg_frames(a.fileno(), [(h, b"x" * (1 << 20))] * 64)
    finally:
        a.close()


# ----------------------------------------------------- adversarial frames


def _c_poison(blob):
    a, b = socket.socketpair()
    try:
        r = _c_reader()
        a.sendall(blob)
        a.close()
        try:
            r.read_wave(b.fileno())
            return None
        except Exception as e:  # noqa: BLE001 - the class IS the assertion
            return type(e)
    finally:
        b.close()


def _py_poison(blob):
    a, b = socket.socketpair()
    try:
        a.sendall(blob)
        a.close()
        r = _FrameReader(b)
        try:
            r.wave()
            return None
        except Exception as e:  # noqa: BLE001 - the class IS the assertion
            return type(e)
    finally:
        b.close()


@pytest.mark.parametrize("name,blob", [
    # payload_len < 4: the frame cannot even hold its header-length word
    ("plen_too_small", struct.pack("<II", 2, 0) + b"xx"),
    # header_len exceeds payload_len - 4
    ("hlen_over_plen", struct.pack("<II", 10, 99) + b"x" * 6),
    # valid lengths, garbage header bytes (undecodable plan data)
    ("garbage_header", struct.pack("<II", 24, 20) + b"\xff" * 20),
    # truncated mid-payload then peer close
    ("truncated_frame", struct.pack("<II", 1000, 10) + b"x" * 20),
    # empty stream: peer closes immediately
    ("empty_close", b""),
])
def test_adversarial_frames_differential(name, blob):
    """Identical poison -> identical error class through C and Python."""
    c_exc, py_exc = _c_poison(blob), _py_poison(blob)
    assert c_exc is not None and py_exc is not None, name
    # corrupt framing surfaces as CodecError from both (the C reader
    # raises the registered class); a clean truncation is ConnectionError
    assert c_exc is py_exc, (name, c_exc, py_exc)


def test_trailing_bytes_after_header_differential():
    """A header shorter than header_len (trailing slack) errors in both
    readers — the C reader's explicit check vs the Python codec's."""
    h = codec.encode(RpcHeader(seq=1, code=RPC_GET))
    hl = len(h) + 4  # lie: claim 4 extra header bytes (eats body space)
    blob = struct.pack("<II", 4 + hl + 2, hl) + h + b"\x00" * 4 + b"ok"
    c_exc, py_exc = _c_poison(blob), _py_poison(blob)
    assert c_exc is not None and py_exc is not None
    assert issubclass(c_exc, codec.CodecError)
    assert issubclass(py_exc, codec.CodecError)


# --------------------------------------------------------- byte identity


@contextlib.contextmanager
def _serving(tmp_path, leg):
    """A fresh 1-node/2-partition replica server with fixed data loaded;
    -> (rpc server, [PegasusServer per partition])."""
    root = tmp_path / leg
    svc = ReplicaService()
    rpc = RpcServer().start()
    try:
        servers = []
        for pidx in range(N_PARTITIONS):
            ps = PegasusServer(str(root / f"p{pidx}"), app_id=APP_ID,
                               pidx=pidx,
                               options=EngineOptions(backend="cpu"),
                               server="node0")
            ps.set_table_name(TABLE)
            svc.add_replica(ps, N_PARTITIONS)
            servers.append(ps)
        rpc.register_serverlet(svc)
        resolver = StaticResolver(APP_ID,
                                  [rpc.address] * N_PARTITIONS)
        client = PegasusClient(resolver)
        try:
            for i in range(8):
                client.set(b"hk%d" % i, b"sk", b"val-%d" % i)
            client.multi_set(b"multi", {b"a": b"1", b"b": b"2", b"c": b"3"})
        finally:
            client.close()
        yield rpc, servers
    finally:
        rpc.stop()


def _one_send(addr, request_frames):
    """Fire `request_frames` as ONE send on a fresh raw connection and
    return {seq: raw response frame bytes}."""
    s = socket.create_connection(addr)
    try:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.sendall(b"".join(request_frames))
        got, buf = {}, bytearray()
        while len(got) < len(request_frames):
            chunk = s.recv(1 << 16)
            assert chunk, "server closed mid-response"
            buf += chunk
            while len(buf) >= 8:
                plen, hlen = struct.unpack_from("<II", buf, 0)
                if len(buf) < 4 + plen:
                    break
                frame = bytes(buf[: 4 + plen])
                header = codec.decode(RpcHeader, frame[8: 8 + hlen])
                got[header.seq] = frame
                del buf[: 4 + plen]
        return got
    finally:
        s.close()


def _run_leg(tmp_path, leg, request_frames):
    """One pipelined wave against a fresh server -> {seq: response}."""
    with _serving(tmp_path, leg) as (rpc, _):
        return _one_send(rpc.address, request_frames)


def _identity_wave():
    """The pipelined request wave: point gets (hits, a miss, a bad
    partition), multi_gets, an exhausting scanner open (context id is
    the COMPLETED constant — deterministic) and a bogus-context scan."""
    frames, seq = [], 0

    def add(code, body, pidx=0):
        nonlocal seq
        seq += 1
        frames.append(_frame(seq, code, body, pidx=pidx))

    for i in range(8):
        key = key_schema.generate_key(b"hk%d" % i, b"sk")
        pidx = key_schema.key_hash(key) % N_PARTITIONS
        add(RPC_GET, codec.encode(msg.KeyRequest(key=key)), pidx=pidx)
    add(RPC_GET, codec.encode(msg.KeyRequest(
        key=key_schema.generate_key(b"nope", b"sk"))),
        pidx=key_schema.key_hash(
            key_schema.generate_key(b"nope", b"sk")) % N_PARTITIONS)
    add(RPC_GET, codec.encode(msg.KeyRequest(key=b"x")), pidx=7)  # no replica
    mkey = key_schema.generate_key(b"multi", b"")
    mpidx = key_schema.key_hash(mkey) % N_PARTITIONS
    add(RPC_MULTI_GET, codec.encode(msg.MultiGetRequest(hash_key=b"multi")),
        pidx=mpidx)
    add(RPC_MULTI_GET, codec.encode(msg.MultiGetRequest(
        hash_key=b"multi", sort_keys=[b"a", b"zz"])), pidx=mpidx)
    for pidx in range(N_PARTITIONS):
        add(RPC_GET_SCANNER, codec.encode(msg.GetScannerRequest(
            batch_size=10_000, validate_partition_hash=False)), pidx=pidx)
    add(RPC_SCAN, codec.encode(msg.ScanRequest(context_id=12345)), pidx=0)
    return frames


def test_byte_identity_native_vs_python(tmp_path, monkeypatch):
    wave = _identity_wave()
    monkeypatch.setenv("PEGASUS_NATIVE", "0")
    py_frames = _run_leg(tmp_path, "python", wave)
    monkeypatch.setenv("PEGASUS_NATIVE", "1")
    nat_frames = _run_leg(tmp_path, "native", wave)
    assert set(py_frames) == set(nat_frames) == set(range(1, len(wave) + 1))
    for seq in py_frames:
        assert nat_frames[seq] == py_frames[seq], f"seq {seq} diverged"
    assert len(wave) > 10


def test_byte_identity_midwave_fallback(tmp_path, monkeypatch):
    """serve.native (the vectored writer's fallback switch) armed in the
    serving process: the same wave gets the same bytes."""
    wave = _identity_wave()
    monkeypatch.setenv("PEGASUS_NATIVE", "0")
    py_frames = _run_leg(tmp_path, "python", wave)
    monkeypatch.setenv("PEGASUS_NATIVE", "1")
    fail_points.setup()
    try:
        fail_points.cfg("serve.native", "3*return()")
        nat_frames = _run_leg(tmp_path, "native-fallback", wave)
    finally:
        fail_points.teardown()
    for seq in py_frames:
        assert nat_frames[seq] == py_frames[seq], f"seq {seq} diverged"


@pytest.mark.parametrize("k", [2, 9, 17, 33])
def test_pipelined_gets_answered_and_charged_once_a_frame(tmp_path,
                                                          monkeypatch, k):
    """k RPC_GET frames in one send on one connection: every reply
    carries its own seq and is byte-identical to the reply the same
    frame gets alone, and get_qps, the read CU charge and the table
    ledger's read count each rise by exactly k — the per-request
    bookkeeping runs once a frame, however the frames arrived."""
    monkeypatch.setenv("PEGASUS_NATIVE", "1")
    hks = [b"hk%d" % i for i in range(8)] + [b"nope"]   # 8 hits, a miss
    frames = []
    for i in range(k):
        key = key_schema.generate_key(hks[i % len(hks)], b"sk")
        frames.append(_frame(
            i + 1, RPC_GET, codec.encode(msg.KeyRequest(key=key)),
            pidx=key_schema.key_hash(key) % N_PARTITIONS))

    def over_partitions(name):
        return sum(counters.rate(f"app.{APP_ID}.{p}.{name}").total()
                   for p in range(N_PARTITIONS))

    def charged():
        return (over_partitions("get_qps"), over_partitions("recent_read_cu"),
                counters.rate(f"table.{TABLE}.read_qps").total())

    with _serving(tmp_path, f"pipelined{k}") as (rpc, _):
        alone = {}
        for f in frames:
            alone.update(_one_send(rpc.address, [f]))
        before = charged()
        together = _one_send(rpc.address, frames)
        after = charged()
    assert set(together) == set(alone) == set(range(1, k + 1))
    for seq in alone:
        assert together[seq] == alone[seq], f"seq {seq} diverged"
    # a 1-unit read each (values far under the 4 KiB CU size)
    assert [a - b for a, b in zip(after, before)] == [k, k, k]


def test_batch_dispatch_counters(tmp_path, monkeypatch):
    """The native plane's attribution series: a pipelined wave drained by
    the server's C reader moves native.wave_count, and a client
    call_many wave (one vectored send) moves native.writev_{count,bytes}."""
    monkeypatch.setenv("PEGASUS_NATIVE", "1")
    names = ("native.wave_count",
             "native.writev_count", "native.writev_bytes")
    base = {n: counters.rate(n).total() for n in names}
    _run_leg(tmp_path, "counters", _identity_wave())
    rpc = RpcServer().start()
    try:
        rpc.register("RPC_ECHO", lambda header, body: body)
        conn = RpcConnection(rpc.address)
        calls = [("RPC_ECHO", b"x%d" % i) for i in range(4)]
        try:
            got = conn.call_many(calls)
            assert [body for _, body in got] == [b for _, b in calls]
            after = {n: counters.rate(n).total() for n in names}
            for n in names:
                assert after[n] > base[n], n
            # serve.native forces the Python twin of the vectored write:
            # same answers, and the writev series stands still
            fail_points.setup()
            try:
                fail_points.cfg("serve.native", "1*return()")
                got = conn.call_many(calls)
            finally:
                fail_points.teardown()
            assert [body for _, body in got] == [b for _, b in calls]
            assert counters.rate("native.writev_count").total() \
                == after["native.writev_count"]
        finally:
            conn.close()
    finally:
        rpc.stop()


# ---------------------------------------------------------- mmap lifetime


def test_mmap_sst_survives_unlink(tmp_path, monkeypatch):
    """The zero-copy block stays readable after its file is deleted —
    the lifetime compaction relies on when it unlinks inputs while
    readers may still hold their blocks."""
    from pegasus_tpu.engine.block import KVBlock
    from pegasus_tpu.engine import sstable

    monkeypatch.setenv("PEGASUS_NATIVE", "1")
    rows = [(b"k%03d" % i, b"v%03d" % i, 0, False) for i in range(100)]
    block = KVBlock.from_records(rows)
    path = str(tmp_path / "x.sst")
    sstable.write_sst(path, block)
    loaded, header = sstable.read_sst(path)
    # zero-copy: the arena is a read-only VIEW over the mapping, not an
    # owning copy
    assert not loaded.key_arena.flags.writeable
    assert loaded.key_arena.base is not None
    os.unlink(path)
    assert not os.path.exists(path)
    for i in range(100):
        assert loaded.key(i) == b"k%03d" % i
        assert loaded.value(i) == b"v%03d" % i


def test_mmap_off_with_knob(tmp_path, monkeypatch):
    """PEGASUS_NATIVE=0 keeps the classic copying reader (writable,
    owning arrays) — and both paths materialize identical blocks."""
    from pegasus_tpu.engine.block import KVBlock
    from pegasus_tpu.engine import sstable

    rows = [(b"a%02d" % i, os.urandom(64), 0, i % 7 == 0)
            for i in range(50)]
    block = KVBlock.from_records(rows)
    path = str(tmp_path / "y.sst")
    sstable.write_sst(path, block)
    monkeypatch.setenv("PEGASUS_NATIVE", "0")
    copied, _ = sstable.read_sst(path)
    assert copied.key_arena.flags.writeable
    monkeypatch.setenv("PEGASUS_NATIVE", "1")
    mapped, _ = sstable.read_sst(path)
    for name in ("key_arena", "key_off", "key_len", "val_arena", "val_off",
                 "val_len", "expire_ts", "hash32", "deleted"):
        np.testing.assert_array_equal(getattr(copied, name),
                                      getattr(mapped, name))


def test_mmap_corruption_still_typed(tmp_path, monkeypatch):
    """The mmap reader keeps read_sst's typed-corruption contract."""
    from pegasus_tpu.engine.block import KVBlock
    from pegasus_tpu.engine import sstable

    monkeypatch.setenv("PEGASUS_NATIVE", "1")
    block = KVBlock.from_records([(b"\x00\x01k", b"v", 0, False)])
    path = str(tmp_path / "z.sst")
    sstable.write_sst(path, block)
    data = bytearray(open(path, "rb").read())
    data[-1] ^= 0xFF  # flip a section byte: crc must catch it
    with open(path, "wb") as f:
        f.write(data)
    with pytest.raises(sstable.CorruptionError):
        sstable.read_sst(path)
    with open(path, "wb") as f:
        f.write(data[:20])  # truncate into the header
    with pytest.raises(sstable.CorruptionError):
        sstable.read_sst(path)


# --------------------------------------------------------- reader gating


def test_make_frame_reader_respects_knob(monkeypatch):
    a, b = socket.socketpair()
    try:
        monkeypatch.setenv("PEGASUS_NATIVE", "0")
        assert isinstance(make_frame_reader(a), _FrameReader)
        monkeypatch.setenv("PEGASUS_NATIVE", "1")
        r = make_frame_reader(a)
        assert not isinstance(r, _FrameReader)
    finally:
        a.close()
        b.close()
