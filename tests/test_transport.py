"""Transport seam: framing, operand shipping, and death-not-hang contracts.

The wire protocol is the part of the cluster runtime a deployment actually
trusts: length-prefixed frames must round-trip every payload size (empty
frames and multi-MiB operand blocks alike), a truncated frame or peer
disconnect must surface as :class:`TransportClosed` — which the pool turns
into a *lost shard* event, never a hang — and a batch's operand blocks must
ship at most once per (worker, batch) on the socket path while shared
memory is provably released on the local path.

The in-process round-trips drive a real :class:`SocketTransport` listener
and a real :class:`LocalTransport` pipe pair against their worker
endpoints without spawning processes; the disconnect test goes through the
full :class:`ClusterBackend` dispatch (crash chaos = ``os._exit`` mid-task,
so the master sees a raw EOF on the stream).
"""
import multiprocessing
import socket
import struct
import threading
import time
from multiprocessing import shared_memory

import numpy as np
import pytest

from repro.cluster import (LocalTransport, SocketTransport, TransportClosed,
                           make_transport)
from repro.cluster.transport import (make_worker_endpoint, recv_frame,
                                     recv_msg, send_frame, send_msg)
from repro.core import MatDotCode, x_complex


# ----------------------------------------------------------------- framing

def _pair():
    a, b = socket.socketpair()
    a.settimeout(5.0)
    b.settimeout(5.0)
    return a, b


@pytest.mark.parametrize("size", [0, 1, 7, 1 << 16, (1 << 16) + 1, 1 << 21])
def test_frame_roundtrip_explicit_sizes(size):
    """Every frame size round-trips byte-exact — 0-byte frames are legal,
    and payloads past 64 KiB span multiple recv() chunks.  The sender runs
    on its own thread: frames larger than the kernel socket buffer need a
    live reader on the other end (exactly the deployment shape)."""
    a, b = _pair()
    try:
        payload = bytes(range(256)) * (size // 256) + bytes(size % 256)
        sender = threading.Thread(target=send_frame, args=(a, payload))
        sender.start()
        try:
            assert recv_frame(b) == payload
        finally:
            sender.join(timeout=5.0)
        assert not sender.is_alive()
    finally:
        a.close()
        b.close()


def test_msg_roundtrip_arrays_and_tuples():
    a, b = _pair()
    try:
        arr = np.arange(24.0).reshape(2, 3, 4) + 1j
        send_msg(a, ("done", 3, 0, 1, arr))
        kind, wid, bid, shard, got = recv_msg(b)
        assert (kind, wid, bid, shard) == ("done", 3, 0, 1)
        np.testing.assert_array_equal(got, arr)
    finally:
        a.close()
        b.close()


def _roundtrip(send, recv, payloads):
    """What ``recv`` reads back while ``send`` writes ``payloads`` from a
    thread: frames past the kernel socket buffer need a live reader."""
    sender = threading.Thread(target=lambda: [send(p) for p in payloads],
                              daemon=True)
    sender.start()
    try:
        return [recv() for _ in payloads]
    finally:
        sender.join(timeout=5.0)
        assert not sender.is_alive()


def test_frame_roundtrip_property_random_sizes():
    """Property (hypothesis): any sequence of message sizes — 0-byte and
    >64 KiB included — round-trips in order through both transports' wire
    formats: the socket frame stream and the local duplex pipe.  The
    frames are written from a thread while they are read, since several
    of them can exceed the socket buffer."""
    st = pytest.importorskip("hypothesis.strategies")
    hypothesis = pytest.importorskip("hypothesis")

    sizes_st = st.lists(
        st.one_of(st.integers(0, 512), st.just(0),
                  st.integers((1 << 16) + 1, (1 << 16) + 4096)),
        min_size=1, max_size=4)

    @hypothesis.given(sizes=sizes_st, seed=st.integers(0, 2**32 - 1))
    @hypothesis.settings(max_examples=25, deadline=None)
    def check(sizes, seed):
        rng = np.random.default_rng(seed)
        payloads = [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
                    for n in sizes]
        a, b = _pair()
        try:
            assert _roundtrip(lambda p: send_frame(a, p),
                              lambda: recv_frame(b), payloads) == payloads
        finally:
            a.close()
            b.close()
        parent, child = multiprocessing.get_context("spawn").Pipe()
        try:
            assert _roundtrip(lambda p: parent.send(("task", p)),
                              lambda: child.recv()[1], payloads) == payloads
        finally:
            parent.close()
            child.close()

    check()


def test_truncated_header_and_frame_raise_closed_not_hang():
    header = struct.Struct("!Q")
    # peer dies mid-header
    a, b = _pair()
    a.sendall(header.pack(100)[:3])
    a.close()
    with pytest.raises(TransportClosed, match="mid-header"):
        recv_frame(b)
    b.close()
    # peer dies mid-frame: header promises 100 bytes, only 10 arrive
    a, b = _pair()
    a.sendall(header.pack(100) + b"x" * 10)
    a.close()
    with pytest.raises(TransportClosed, match="mid-frame"):
        recv_frame(b)
    b.close()
    # clean EOF between frames is still a closure, reported as such
    a, b = _pair()
    a.close()
    with pytest.raises(TransportClosed, match="peer closed"):
        recv_frame(b)
    b.close()


def test_hostile_length_prefix_and_garbage_pickle_raise_closed():
    a, b = _pair()
    try:
        a.sendall(struct.Struct("!Q").pack(1 << 62))
        with pytest.raises(TransportClosed, match="exceeds cap"):
            recv_frame(b)
    finally:
        a.close()
        b.close()
    a, b = _pair()
    try:
        send_frame(a, b"not a pickle")
        with pytest.raises(TransportClosed, match="undecodable"):
            recv_msg(b)
    finally:
        a.close()
        b.close()


# ----------------------------------------- in-process transport round-trips

def test_socket_transport_roundtrip_ships_operands_once():
    """Full master<->worker conversation over real TCP, one process: ready
    handshake identifies the dialer, the operands frame rides the stream
    exactly once ahead of the first task that references it, results land
    on the shared queue, and an endpoint close marks the channel dead."""
    tr = SocketTransport(hosts=("127.0.0.1",))
    ep = None
    try:
        chan, arg = tr.connect(0)
        assert arg[0] == "socket"
        ep = make_worker_endpoint(arg)
        ep.send(("ready", 0))
        assert chan.poll_ready(5.0)
        E_A = np.arange(24.0).reshape(2, 3, 2, 2) + 0.5j
        E_B = np.arange(24.0).reshape(2, 3, 2, 2) - 1.0
        h = tr.publish(E_A, E_B)
        assert tr.live_operands == 1
        assert chan.send(("task", 7, 0, h.ref), operands=h)
        assert chan.send(("task", 7, 1, h.ref), operands=h)
        assert ep.recv() == ("task", 7, 0, h.ref)   # operand frame consumed
        got_A, got_B = ep.get_operands(h.ref)
        np.testing.assert_array_equal(got_A, E_A)
        np.testing.assert_array_equal(got_B, E_B)
        assert ep.recv() == ("task", 7, 1, h.ref)   # not shipped twice
        ep.send(("done", 0, 7, 0, got_A[:, 0]))
        kind, wid, bid, shard, P = tr.results.get(timeout=5.0)
        assert (kind, wid, bid, shard) == ("done", 0, 7, 0)
        np.testing.assert_array_equal(P, E_A[:, 0])
        h.release()
        assert tr.live_operands == 0
        ep.close()
        deadline = time.monotonic() + 5.0
        while not chan.dead and time.monotonic() < deadline:
            time.sleep(0.01)
        assert chan.dead                            # EOF → liveness sweep
    finally:
        if ep is not None:
            ep.close()
        tr.close()


def test_local_transport_roundtrip_and_shm_released():
    """Same conversation over the pipe/shm plumbing — and the operand
    blocks are *provably* unlinked on release: re-attaching by name fails."""
    ctx = multiprocessing.get_context("spawn")
    tr = make_transport("local", ctx=ctx)
    assert isinstance(tr, LocalTransport)
    chan, arg = tr.connect(0)
    ep = make_worker_endpoint(arg)
    try:
        ep.send(("ready", 0))
        assert chan.poll_ready(5.0)
        E_A = np.arange(24.0).reshape(2, 3, 2, 2) + 0.5j
        E_B = np.arange(24.0).reshape(2, 3, 2, 2) - 1.0
        h = tr.publish(E_A, E_B)
        token = h.token                             # == shm_a's name
        assert chan.send(("task", 7, 0, h.ref), operands=h)
        assert ep.recv() == ("task", 7, 0, h.ref)
        got_A, got_B = ep.get_operands(h.ref)
        np.testing.assert_array_equal(got_A, E_A)
        np.testing.assert_array_equal(got_B, E_B)
        ep.send(("done", 0, 7, 0, np.ascontiguousarray(got_A[:, 0])))
        kind, wid, bid, shard, P = tr.results.get(timeout=5.0)
        assert (kind, wid, bid, shard) == ("done", 0, 7, 0)
        np.testing.assert_array_equal(P, E_A[:, 0])
        ep.release_operands()                       # worker detaches
        h.release()                                 # master unlinks
        assert tr.live_operands == 0
        with pytest.raises(FileNotFoundError):
            shared_memory.SharedMemory(name=token)
    finally:
        ep.close()
        chan.close()
        tr.close()


def test_make_transport_rejects_unknown_name():
    with pytest.raises(ValueError, match="unknown transport .*valid: local, socket"):
        make_transport("carrier-pigeon")


# ------------------------------------------------- disconnect => lost shard

def test_peer_disconnect_reports_shard_lost_not_hung():
    """A worker whose stream dies mid-task (``os._exit`` on crash chaos —
    the master sees raw EOF, no farewell message) resolves as a lost-shard
    event in bounded wall-clock; the surviving shards all complete."""
    from repro.cluster.backend import ClusterBackend
    t0 = time.monotonic()
    code = MatDotCode(2, 4, x_complex(4, 0.1))
    rng = np.random.default_rng(13)
    As = [rng.standard_normal((8, 8)) for _ in range(2)]
    Bs = [rng.standard_normal((8, 8)) for _ in range(2)]
    with ClusterBackend(workers=4, chaos="crash:1", seed=0,
                        transport="socket") as be:
        d = be.dispatch_batch(code, As, Bs)
        d.set_abandon(20.0)
        done = []
        while d.outstanding:
            ev = d.next_event(timeout=5.0)
            if ev is None:
                break
            if ev.kind == "done":
                done.append(ev.shard)
        d.finalize()
    assert d.lost == {0: "crash"}
    assert sorted(done) == [1, 2, 3]
    assert time.monotonic() - t0 < 30.0
