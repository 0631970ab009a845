"""The native transport's thread model: a decoded frame is handled by the
thread that read it, on both ends of a round trip.

``serverd.cpp`` is one thread over one epoll set (it accepts, reads,
dispatches, answers and flushes), and ``libadlb.cpp`` has no thread of its
own: the thread that blocks in a call sleeps in ``poll`` and does the reads.
Each test here holds one invariant of that model, against a real daemon or a
real client library, with the test itself playing the other ranks on raw
sockets (its own little TLV encoder, so garbage is as easy as sense).

Two native ranks of one host talk over a Unix-domain socket named after the
listener's TCP port, and over TCP with everyone else (``hostsock.hpp``); the
family is chosen from the address map and the peer's answer. A Unix
connection begins with the connector's hello, which brings a ring in shared
memory when one can be made: the frames then go through the ring, and the
socket carries wake-ups (one byte, a bell) and the peer's death. Everything
above the connection is one code path. So the invariants are held once per
family (the ``family`` fixture): with ``ring`` the test's own ranks listen
on their port's name and connect to the native rank's, as a native rank
would, and play both ends of the rings from Python (``RingConn``,
``_RingIn``: the layout is ``hostsock.hpp``'s ``RingHdr``); with ``unix``
nobody has a segment, so every Unix connection carries its bytes on the
socket: the test's hello says so, and the native ranks are a test-only build
whose segment constructor fails (``-DADLB_TEST_NO_SEGMENT``; nothing at run
time selects that); with ``tcp`` the test's ranks have no Unix listener, as
a Python rank has none, and the native rank is told they live on another
host. Then come the ring's own invariants (frames of every size against the
ring's, a full ring toward a stopped reader, the lost wake-up, a peer killed
with frames in its ring, a connection without a hello, the counters), and
the last tests hold the choice itself: who is tried over which family, and
whose name is whose.

A rank that awaits a frame looks for it without blocking for a bounded time
before it sleeps (``hostsock::poll_budget_s``): the client in a wait for the
answer to its own request, the daemon after a turn that carried traffic. The
budget is 50 microseconds, which no test can aim at; what a test can decide
is whether the awaited thing is there *before* the wait begins, so that the
polling phase's first look finds it, or comes long after the budget has
passed, so that the sleeping call does (the ``phase`` fixture). The
invariants are held once per phase, and ``waits_polled`` / ``waits_slept``
(the daemon's ``STATS`` trailer, a client's ``adlb:waits`` event) say which
phase ended a wait.

No test times the host. Every wait has a limit far above what the step
needs, and running into it is the failure (a hang), not a slow pass.
"""

import collections
import contextlib
import ctypes
import json
import mmap
import os
import random
import selectors
import shutil
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

from adlb_tpu.native import daemon as daemon_mod
from adlb_tpu.runtime.transport_tcp import local_addr_map
from adlb_tpu.runtime.world import Config, WorldSpec

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no C++ toolchain"
)

LIMIT_S = 60.0  # a step that takes this long has hung

# An address that reaches this machine's listeners and is, by the strings of
# an address map, neither the host of a rank at 127.0.0.1 nor a loopback
# address: "another host" as far as a native rank can tell.
OTHER_HOST = "0.0.0.0"


def _noseg_builds():
    """(adlb_serverd, libadlb.so) built with ``-DADLB_TEST_NO_SEGMENT``: the
    shipped sources, but ``hostsock::make_segment`` fails, so every hello
    these ranks send says "no ring" and their connections carry the bytes on
    the socket. Content-keyed beside the shipped builds."""
    from adlb_tpu.native import build, capi

    flag = "-DADLB_TEST_NO_SEGMENT"
    serverd = build.build_artifact(
        "adlb_serverd",
        ["g++", "-O2", "-std=c++17", flag, "-o", "{out}", build._SERVERD_SRC],
        [build._SERVERD_SRC, build._WQ_HDR, build.HOSTSOCK_HDR])
    lib = build.build_artifact(
        "libadlb.so",
        ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", flag,
         f"-I{capi._INCLUDE}", "-o", "{out}", capi._SRC, capi._FSRC],
        [capi._SRC, capi._FSRC, capi._HDR, build.HOSTSOCK_HDR])
    return serverd, lib


@pytest.fixture
def noseg(monkeypatch):
    """The native ranks this test starts are the build without segments."""
    serverd, lib = _noseg_builds()
    monkeypatch.setattr("adlb_tpu.native.build.ensure_serverd",
                        lambda: serverd)
    monkeypatch.setattr("adlb_tpu.native.capi.build_libadlb", lambda: lib)


@pytest.fixture(params=["tcp", "unix", "ring"])
def family(request):
    if request.param == "unix":
        request.getfixturevalue("noseg")
    return request.param


def sock_of(family):
    """The socket family under ``family``: a ring rides a Unix connection."""
    return "tcp" if family == "tcp" else "unix"


@pytest.fixture(params=["polling", "asleep"])
def phase(request):
    return request.param


ASLEEP_S = 0.05  # a thousand polling budgets: whoever still waits, sleeps


def unix_name(port):
    """The abstract name of ``port`` (hostsock.hpp)."""
    return b"\0adlb_tpu.%d" % port


def bound_names():
    """The abstract Unix names bound in this network namespace."""
    with open("/proc/net/unix") as f:
        return {line.split()[-1] for line in f if "@adlb_tpu." in line}


def peer_host(family):
    """Where a native rank is told the test's ranks live."""
    return OTHER_HOST if family == "tcp" else "127.0.0.1"


# ---- hostsock.hpp's hello and ring, from Python ---------------------------

RING = 1 << 16  # kRingBytes
_HDR = 256  # sizeof(RingHdr); the data area follows
_MAGIC = 0x31676E6972424C44  # kRingMagic
_TAIL, _WAITS, _HEAD, _SLEEPS = 64, 72, 128, 136


def hello(ring_bytes, version=1, magic=b"ADLBring"):
    return magic + struct.pack("<II", version, ring_bytes)


def _cursors(m):
    """The segment's four shared words as ctypes views of the mapping: each
    load and store is one aligned access, as the native side's atomics are
    (``struct.pack_into`` writes byte by byte, and a reader would see a
    cursor torn)."""
    return {name: kind.from_buffer(m, off) for name, kind, off in (
        ("tail", ctypes.c_uint64, _TAIL), ("waits", ctypes.c_uint32, _WAITS),
        ("head", ctypes.c_uint64, _HEAD), ("sleeps", ctypes.c_uint32, _SLEEPS))}


class RingConn:
    """The connecting end of a Unix connection with a ring, as a native rank
    makes it: an anonymous segment, the hello with its descriptor attached,
    then every byte through the ring. Looks like the socket it wraps where
    the tests use one. Python has no fences, so after each publish it rings
    the bell whatever the reader's mark says (a bell too many is only a
    byte the reader drains); ``bell=False`` leaves the frames in the ring
    unannounced."""

    def __init__(self, sock):
        self.sock = sock
        fd = os.memfd_create("test.ring")
        os.ftruncate(fd, _HDR + RING)
        self.m = mmap.mmap(fd, _HDR + RING)
        struct.pack_into("<QI", self.m, 0, _MAGIC, RING)
        self.c = _cursors(self.m)
        self.c["sleeps"].value = 1
        socket.send_fds(sock, [hello(RING)], [fd])
        os.close(fd)
        self.tail = 0

    def sendall(self, data, bell=True):
        view = memoryview(data)
        deadline = _now() + (self.sock.gettimeout() or LIMIT_S)
        while len(view):
            room = RING - (self.tail - self.c["head"].value)
            if room == 0:
                # full: ask for the reader's bell and wait for it (or look
                # again shortly: the flag and the look are not fenced here)
                self.c["waits"].value = 1
                assert _now() < deadline, "the ring's reader stopped reading"
                self.sock.settimeout(0.005)
                try:
                    if self.sock.recv(64) == b"":
                        raise BrokenPipeError("the ring's reader is gone")
                except (socket.timeout, BlockingIOError):
                    pass
                finally:
                    self.sock.settimeout(deadline - _now())
                continue
            self.c["waits"].value = 0
            n = min(room, len(view))
            at = self.tail % RING
            first = min(n, RING - at)
            self.m[_HDR + at:_HDR + at + first] = view[:first]
            self.m[_HDR:_HDR + n - first] = view[first:n]
            self.tail += n
            self.c["tail"].value = self.tail
            view = view[n:]
            if bell:
                try:
                    self.sock.send(b"\1", socket.MSG_DONTWAIT)
                except BlockingIOError:
                    pass  # a bell is pending already

    def recv(self, n):
        return self.sock.recv(n)

    def settimeout(self, t):
        self.sock.settimeout(t)

    def close(self):
        self.sock.close()
        self.c.clear()  # the views go before the mapping they hold
        self.m.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _RingIn:
    """The accepting end: what a native rank's hello brought, mapped. It
    never clears ``reader_sleeps`` (set by the connector), so every publish
    of the native writer rings the socket and ``Peer.pump`` finds it."""

    def __init__(self, fd):
        self.m = mmap.mmap(fd, _HDR + RING)
        os.close(fd)
        assert struct.unpack_from("<QI", self.m, 0) == (_MAGIC, RING)
        self.c = _cursors(self.m)
        self.head = 0

    def take(self):
        """(the bytes the ring holds, whether there were any)."""
        tail = self.c["tail"].value
        n = tail - self.head
        assert 0 <= n <= RING
        at = self.head % RING
        first = min(n, RING - at)
        data = self.m[_HDR + at:_HDR + at + first] + self.m[_HDR:_HDR + n - first]
        self.head = tail
        self.c["head"].value = self.head
        return data, n > 0

ADLB_SUCCESS = 1
ADLB_PUT_REJECTED = -999999996

# wire tags and field ids (serverd.cpp / libadlb.cpp / codec.py)
FA_PUT, FA_RESERVE, FA_LOCAL_APP_DONE = 1001, 1007, 1012
TA_PUT_RESP, TA_ABORT, AM_APP = 1020, 1046, 1047
FA_INFO_NUM, TA_INFO_NUM_RESP = 1037, 1043
SS_QMSTAT, SS_EXHAUST_CHK_1, SS_PLAN_MIGRATE = 1101, 1111, 1119
SS_END_1, SS_END_2 = 1114, 1115
SS_STATE, SS_HUNGRY, F_HUNGRY = 1117, 1124, 60
SS_RFR_RESP, SS_PLAN_MATCH = 1103, 1118
F_PAYLOAD, F_WORK_TYPE, F_PRIO, F_TARGET_RANK, F_ANSWER_RANK = 1, 2, 3, 4, 5
F_COMMON_LEN, F_COMMON_SERVER, F_COMMON_SEQNO, F_RC, F_HINT = 6, 7, 8, 9, 10
F_REQ_TYPES, F_HANG, F_RQSEQNO, F_COUNT, F_NBYTES, F_CODE = 11, 12, 13, 17, 18, 20
F_APPTAG, F_DEST, F_SEQNOS, F_PUT_ID, F_MIG_ID = 26, 47, 48, 58, 77
F_ORIGIN, F_COMPLETE = 40, 42
F_SEQNO, F_FOR_RANK, F_REQ_HOME = 21, 29, 46


# ---- a TLV codec of the test's own ----------------------------------------

def tlv(tag, src, fields=()):
    """One frame, length prefix and all. ``fields``: (id, value) pairs; an
    int goes as i64, bytes as bytes, a list as a list of i64."""
    fields = list(fields)
    body = struct.pack("<BHiH", 1, tag, src, len(fields))
    for fid, v in fields:
        if isinstance(v, int):
            body += struct.pack("<BBq", fid, 0, v)
        elif isinstance(v, (bytes, bytearray)):
            body += struct.pack("<BBI", fid, 1, len(v)) + bytes(v)
        else:
            body += struct.pack("<BBH", fid, 2, len(v))
            body += struct.pack(f"<{len(v)}q", *v)
    return struct.pack("<I", len(body)) + body


def untlv(body):
    """(tag, src, {field id: value}) of a frame body."""
    _magic, tag, src, nf = struct.unpack_from("<BHiH", body, 0)
    off, out = 9, {}
    for _ in range(nf):
        fid, kind = struct.unpack_from("<BB", body, off)
        off += 2
        if kind in (0, 3):
            (out[fid],) = struct.unpack_from("<q" if kind == 0 else "<d",
                                             body, off)
            off += 8
        elif kind == 1:
            (n,) = struct.unpack_from("<I", body, off)
            out[fid] = bytes(body[off + 4:off + 4 + n])
            off += 4 + n
        elif kind in (2, 5):
            (n,) = struct.unpack_from("<H", body, off)
            out[fid] = list(struct.unpack_from(
                f"<{n}{'q' if kind == 2 else 'd'}", body, off + 2))
            off += 2 + 8 * n
        else:  # kind 4: list of byte strings
            (n,) = struct.unpack_from("<H", body, off)
            off += 2
            items = []
            for _ in range(n):
                (ln,) = struct.unpack_from("<I", body, off)
                items.append(bytes(body[off + 4:off + 4 + ln]))
                off += 4 + ln
            out[fid] = items
    return tag, src, out


def put_frame(src, payload, work_type=1, put_id=None):
    f = [(F_PAYLOAD, payload), (F_WORK_TYPE, work_type), (F_PRIO, 0),
         (F_TARGET_RANK, -1), (F_ANSWER_RANK, -1), (F_COMMON_LEN, 0),
         (F_COMMON_SERVER, -1), (F_COMMON_SEQNO, -1)]
    if put_id is not None:
        f.append((F_PUT_ID, put_id))
    return tlv(FA_PUT, src, f)


class Peer:
    """A listener the test owns, standing in for one or more ranks: accepts
    whoever connects and collects the frames they send. With ``family``
    "unix" it also listens on its port's Unix name, as a native rank does;
    ``accepted`` counts the connections by the listener they came to."""

    def __init__(self, port=0, family="tcp"):
        self.lsock = socket.socket()
        self.lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.lsock.bind(("127.0.0.1", port))
        self.lsock.listen(64)
        self.port = self.lsock.getsockname()[1]
        self.sel = selectors.DefaultSelector()
        self.sel.register(self.lsock, selectors.EVENT_READ, "tcp")
        self.usock = None
        if family != "tcp":
            self.usock = socket.socket(socket.AF_UNIX)
            self.usock.bind(unix_name(self.port))
            self.usock.listen(64)
            self.sel.register(self.usock, selectors.EVENT_READ, "unix")
        self.accepted = collections.Counter()
        self.bufs = {}
        self.hellos = {}  # Unix connections whose hello is still due
        self.rings = {}  # connections whose frames come through a ring
        self.paths = collections.Counter()  # frames by path: "ring" / "sock"
        self.frames = collections.deque()
        self.reading = True  # False: accept, but leave the bytes unread

    def pump(self, timeout):
        for key, _ in self.sel.select(timeout):
            s = key.fileobj
            if s is self.lsock or s is self.usock:
                c, _ = s.accept()
                self.accepted[key.data] += 1
                self.bufs[c] = bytearray()
                if key.data == "unix":
                    self.hellos[c] = b""
                if self.reading:
                    self.sel.register(c, selectors.EVENT_READ)
                continue
            if s in self.hellos:  # a native rank's first bytes: its hello
                got, fds, _flags, _addr = socket.recv_fds(
                    s, 16 - len(self.hellos[s]), 1)
                assert got, "a Unix connection ended before its hello"
                self.hellos[s] += got
                if fds:
                    self.rings[s] = _RingIn(fds[0])
                if len(self.hellos[s]) < 16:
                    continue
                h = self.hellos.pop(s)
                assert h in (hello(0), hello(RING)), h
                assert (h == hello(RING)) == (s in self.rings)
                continue  # what follows it is another event
            data = s.recv(1 << 20)
            buf = self.bufs[s]
            if s in self.rings:  # the socket's bytes are bells
                taken, any_taken = self.rings[s].take()
                buf += taken
                if data and any_taken:
                    # room was made: ring, whether the writer says it waits
                    # or not (no fences here; a bell too many harms nobody)
                    try:
                        s.send(b"\1", socket.MSG_DONTWAIT)
                    except OSError:
                        pass
            else:
                buf += data
            while len(buf) >= 4:
                (n,) = struct.unpack_from("<I", buf, 0)
                if len(buf) < 4 + n:
                    break
                self.frames.append(untlv(bytes(buf[4:4 + n])))
                self.paths["ring" if s in self.rings else "sock"] += 1
                del buf[:4 + n]
            if not data:
                self.sel.unregister(s)
                s.close()
                del self.bufs[s]
                self.rings.pop(s, None)

    def resume_reading(self):
        self.reading = True
        for c in self.bufs:
            try:
                self.sel.get_key(c)
            except KeyError:
                self.sel.register(c, selectors.EVENT_READ)

    def expect(self, tag, limit_s=LIMIT_S):
        """The next frame with ``tag``; frames of other tags stay queued."""
        deadline = _now() + limit_s
        while True:
            for i, fr in enumerate(self.frames):
                if fr[0] == tag:
                    del self.frames[i]
                    return fr
            left = deadline - _now()
            assert left > 0, f"no frame with tag {tag} within {limit_s} s"
            self.pump(min(left, 0.5))

    def close(self):
        for c in list(self.bufs):
            c.close()
        self.lsock.close()
        if self.usock is not None:
            self.usock.close()
        self.sel.close()


def _now():
    return time.monotonic()


def _connect(port, family="tcp"):
    """A connection to the rank that listens at ``port``, as a rank of
    ``family`` opens it: over Unix with the hello first, which under
    ``ring`` brings a segment and under ``unix`` says there is none."""
    if family != "tcp":
        s = socket.socket(socket.AF_UNIX)
        s.settimeout(LIMIT_S)
        s.connect(unix_name(port))
        if family == "ring":
            return RingConn(s)
        s.sendall(hello(0))
        return s
    s = socket.create_connection(("127.0.0.1", port), timeout=LIMIT_S)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


class Daemons:
    """adlb_serverd processes for some server ranks of a world; every other
    rank (apps, the remaining servers) is the test's ``peer``. With
    ``family`` "tcp" each daemon is told that every rank but itself lives
    at OTHER_HOST, so all it opens is TCP, to the peer and to the other
    daemons alike."""

    def __init__(self, n_apps, nservers, daemon_ranks, cfg=None, types=(1, 2),
                 family="unix", peer=None, own_host="127.0.0.1", others=None):
        self.world = WorldSpec(nranks=n_apps + nservers, nservers=nservers,
                               types=tuple(types))
        cfg = cfg or Config(server_impl="native")
        self.peer = peer or Peer(family=family)
        self.procs = {r: daemon_mod.spawn_daemon(self.world, cfg, r)
                      for r in daemon_ranks}
        self.ports = {r: daemon_mod.read_hello(p, r)
                      for r, p in self.procs.items()}
        # ``others``: ranks that are neither daemons nor the peer's, by port
        # (the planner's pseudo-rank, nranks, is told to a daemon this way)
        there = {**(others or {}), **self.ports}
        for me, p in self.procs.items():
            daemon_mod.send_addrs(p, {
                r: (own_host if r == me else peer_host(family),
                    there.get(r, self.peer.port))
                for r in sorted({*range(self.world.nranks), *there})})

    def finish(self):
        """End the world the way its ranks would and return each daemon's
        ``STATS``: every app rank finalizes at its home server, and where
        the test plays a server it passes the two tokens of the END ring
        on. For worlds whose servers are all daemons, or one daemon and
        the test."""
        n_apps = self.world.nranks - self.world.nservers
        servers = range(n_apps, self.world.nranks)
        played = [r for r in servers if r not in self.procs]
        assert len(played) <= 1 and len(servers) <= 2
        master = n_apps
        for app in range(n_apps):
            home = n_apps + app % self.world.nservers
            if home in self.procs:
                with _connect(self.ports[home], "tcp") as c:
                    c.sendall(tlv(FA_LOCAL_APP_DONE, app))
        for me in played:  # the ring is master -> me -> master
            with _connect(self.ports[master], "tcp") as c:
                for tag in (SS_END_1, SS_END_2):
                    self.peer.expect(tag)
                    c.sendall(tlv(tag, me, [(F_ORIGIN, master),
                                            (F_COMPLETE, 1)]))
        out = {}
        for r, p in self.procs.items():
            stats, _abort, rc = daemon_mod.collect_stats(p, timeout=LIMIT_S)
            assert rc == 0, (r, rc)
            out[r] = stats
        return out

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        for p in self.procs.values():
            if p.poll() is None:
                p.kill()
            p.wait()
            p.stdout.close()
            p.stdin.close()
        self.peer.close()


# ---- the daemon -----------------------------------------------------------

def test_one_connection_is_handled_in_order_and_its_eof_comes_last(family):
    """500 pipelined puts written at once and the socket closed behind them,
    with no LOCAL_APP_DONE: the daemon answers all 500 in the order sent,
    and only then reads the lost connection as rank death."""
    with Daemons(1, 1, [1], family=family) as w:
        c = _connect(w.ports[1], family)
        c.sendall(b"".join(put_frame(0, struct.pack("<q", i), put_id=i + 1)
                           for i in range(500)))
        c.close()
        ids = [w.peer.expect(TA_PUT_RESP)[2] for _ in range(500)]
        assert [f[F_PUT_ID] for f in ids] == list(range(1, 501))
        assert all(f[F_RC] == ADLB_SUCCESS for f in ids)
        _tag, _src, f = w.peer.expect(TA_ABORT)
        assert f[F_CODE] == -3
        assert not [fr for fr in w.peer.frames if fr[0] == TA_PUT_RESP]
        assert w.procs[1].wait(LIMIT_S) == 2
        assert "ABORT -3" in w.procs[1].stdout.read()
        assert set(w.peer.accepted) == {sock_of(family)}  # the answers' too
        if family != "tcp":  # and their path
            assert set(w.peer.paths) == {"ring" if family == "ring" else "sock"}


GARBAGE = {
    "non-binary": struct.pack("<I", 8) + b"\x99" * 8,
    "undecodable": struct.pack("<I", 41) + b"\x01" + bytes(range(40)),
    "over-the-cap": struct.pack("<I", 0x7FFFFFFF),
    "empty": struct.pack("<I", 0),
    "unknown-tag": struct.pack("<I", 9) + b"\x01"
    + struct.pack("<HiH", 4242, 0, 0),
}


@pytest.mark.parametrize("kind", sorted(GARBAGE))
def test_garbage_on_a_fresh_connection_closes_that_connection_alone(
        kind, family):
    with Daemons(1, 1, [1], family=family) as w:
        served = _connect(w.ports[1], family)
        served.sendall(put_frame(0, b"a"))
        assert w.peer.expect(TA_PUT_RESP)[2][F_RC] == ADLB_SUCCESS
        stray = _connect(w.ports[1], family)
        stray.sendall(GARBAGE[kind])
        assert stray.recv(16) == b""  # the daemon closed it
        served.sendall(put_frame(0, b"b"))
        assert w.peer.expect(TA_PUT_RESP)[2][F_RC] == ADLB_SUCCESS
        assert w.procs[1].poll() is None


def test_garbage_on_an_established_connection_dies_loudly(family):
    with Daemons(1, 1, [1], family=family) as w:
        served = _connect(w.ports[1], family)
        served.sendall(put_frame(0, b"a"))
        w.peer.expect(TA_PUT_RESP)
        served.sendall(GARBAGE["non-binary"])
        assert w.procs[1].wait(LIMIT_S) == 1


def _vm_kb(pid, key):
    with open(f"/proc/{pid}/status") as f:
        for line in f:
            if line.startswith(key + ":"):
                return int(line.split()[1])
    raise AssertionError(key)


def test_a_length_prefix_and_then_silence_stalls_nobody_and_allocates_nothing(
        family):
    """A connection announces 200 MB (under the cap), sends a few bytes of
    it and goes quiet: the daemon neither waits for the rest nor sets the
    memory aside, and serves its other connections."""
    with Daemons(1, 1, [1], family=family) as w:
        served = _connect(w.ports[1], family)
        served.sendall(put_frame(0, b"a"))
        w.peer.expect(TA_PUT_RESP)
        pid = w.procs[1].pid
        before = _vm_kb(pid, "VmSize")
        quiet = _connect(w.ports[1], family)
        quiet.sendall(struct.pack("<I", 200 << 20) + b"\x01\x02\x03")
        for i in range(50):
            served.sendall(put_frame(0, b"b%d" % i))
            assert w.peer.expect(TA_PUT_RESP)[2][F_RC] == ADLB_SUCCESS
        assert _vm_kb(pid, "VmSize") - before < (64 << 10)
        quiet.close()


def test_two_daemons_shipping_each_other_more_than_the_sockets_hold(family):
    """The blocked-send invariant. Each of two daemons is told, at the same
    moment, to migrate 24 MB to the other in one frame: far more than a
    socket takes from a sender whose peer is not reading (its send buffer
    and the peer's unread window, a few MB at most). A daemon that stood in
    ``send`` would never read what the other sends, and both would stand
    for ever; this one queues what the socket refuses and goes on reading.
    The 24 MB cross between the daemons over the family under test."""
    n, size = 24, 1 << 20
    with Daemons(1, 2, [1, 2], family=family) as w:
        conns = {s: _connect(w.ports[s], family) for s in (1, 2)}
        for s in (1, 2):
            for i in range(n):
                conns[s].sendall(put_frame(0, bytes([s]) * size))
                assert w.peer.expect(TA_PUT_RESP)[2][F_RC] == ADLB_SUCCESS
        seqnos = list(range(1, n + 1))  # a fresh daemon numbers puts from 1
        # the planner's pseudo-rank is the sender; nobody answers it
        conns[1].sendall(tlv(SS_PLAN_MIGRATE, 3, [
            (F_SEQNOS, seqnos), (F_DEST, 2), (F_MIG_ID, 1)]))
        conns[2].sendall(tlv(SS_PLAN_MIGRATE, 3, [
            (F_SEQNOS, seqnos), (F_DEST, 1), (F_MIG_ID, 1)]))
        # a daemon answers in the order it dispatches, so by the time it
        # answers a query sent behind the order to migrate, its own units
        # are gone; ask until the other's have arrived
        for s in (1, 2):
            deadline = _now() + LIMIT_S
            while True:
                conns[s].sendall(tlv(FA_INFO_NUM, 0, [(F_WORK_TYPE, 1)]))
                f = w.peer.expect(TA_INFO_NUM_RESP)[2]
                if f[F_COUNT] == n:
                    break
                assert f[F_COUNT] == 0, f
                assert _now() < deadline, "the migrations never landed"
                w.peer.pump(0.05)
            assert f[F_NBYTES] == n * size
        assert all(p.poll() is None for p in w.procs.values())


def test_periodic_keeps_its_deadlines_while_one_client_sends_without_pause(
        family):
    """qmstat and the exhaustion vote under a flood. The daemon is the
    master of two servers, the other being the test; its one local app is
    parked on a type nobody puts, so its vote passes, and a qmstat
    broadcast is due every interval. A second rank keeps 256 requests in
    flight without a gap: first puts, until five qmstats have come *while
    the flood lasts*; then queries, until two of the vote's tokens have
    (an accepted put withdraws a vote that is being held, by design, so a
    flood of puts rightly sees none). Either half fails if a quarter of a
    million requests went by without its duty showing."""
    cfg = Config(server_impl="native", qmstat_interval=0.02,
                 exhaust_check_interval=0.02)
    with Daemons(2, 2, [2], cfg=cfg, family=family) as w:
        parked = _connect(w.ports[2], family)
        parked.sendall(tlv(FA_RESERVE, 0, [
            (F_HANG, 1), (F_RQSEQNO, 1), (F_REQ_TYPES, [2])]))
        flood = _connect(w.ports[2], family)

        def flood_until(request, answer, duty, count):
            sent = acked = seen = 0
            while seen < count and sent < 250_000:
                if sent - acked < 256:
                    flood.sendall(b"".join(
                        request(sent + i + 1) for i in range(128)))
                    sent += 128
                w.peer.pump(0 if sent - acked < 256 else 1.0)
                while w.peer.frames:
                    tag, _src, _f = w.peer.frames.popleft()
                    acked += tag == answer
                    seen += acked > 0 and tag == duty
            assert seen >= count, (sent, acked, seen)
            assert sent - acked <= 256 + 128  # it was a flood to the end
            while acked < sent:  # leave nothing of this half in flight
                w.peer.expect(answer)
                acked += 1

        flood_until(lambda i: put_frame(1, b"12345678", put_id=i),
                    TA_PUT_RESP, SS_QMSTAT, 5)
        flood_until(lambda i: tlv(FA_INFO_NUM, 1, [(F_WORK_TYPE, 1)]),
                    TA_INFO_NUM_RESP, SS_EXHAUST_CHK_1, 2)
        assert w.procs[2].poll() is None


def test_a_snapshot_that_outlasts_its_interval_does_not_starve_the_reactor(
        family):
    """A planner-mode daemon with parked ranks somewhere (``SS_HUNGRY``)
    sends the planner a snapshot every ``balancer_interval``. A snapshot
    reads its first units off an index, passing by the pinned ones: with
    the first 100,000 units pinned by a plan's matches, that takes longer
    than the interval of a millisecond here. The next one is due an
    interval after the last one is DONE, so the reactor serves in between:
    five thousand more puts are answered within a snapshot or two. Counted
    from the snapshot's start it was due again the moment it ended, the
    turn's drain stopped at its first frame, and the daemon served one
    frame a snapshot for as long as anyone was hungry: five thousand puts,
    five thousand snapshots (what a flood that outran the planner's first
    migrations did to a whole world)."""
    n, pinned, more = 110_000, 100_000, 5_000
    cfg = Config(server_impl="native", balancer="tpu",
                 balancer_interval=1e-3, exhaust_check_interval=60.0)
    peer = Peer(family=family)  # rank 0 and the planner's pseudo-rank, 2
    with Daemons(1, 1, [1], cfg=cfg, types=(1,), family=family, peer=peer,
                 others={2: peer.port}) as w:
        c = _connect(w.ports[1], family)
        frame = put_frame(0, b"12345678")
        acked = 0
        for _ in range(0, n, 1000):
            c.sendall(frame * 1000)
            w.peer.pump(0)
            acked += sum(fr[0] == TA_PUT_RESP for fr in w.peer.frames)
            w.peer.frames.clear()
        while acked < n:
            w.peer.expect(TA_PUT_RESP)
            acked += 1
        # the planner matches each of the first units to rank 0, which is
        # not parked: the unit stays pinned, the answer goes to the peer
        planner = _connect(w.ports[1], family)
        answered = 0
        for first in range(1, pinned + 1, 1000):
            planner.sendall(b"".join(
                tlv(SS_PLAN_MATCH, 2, [(F_SEQNO, q), (F_FOR_RANK, 0),
                                       (F_REQ_HOME, 2), (F_RQSEQNO, 1)])
                for q in range(first, first + 1000)))
            w.peer.pump(0)
            answered += sum(fr[0] == SS_RFR_RESP for fr in w.peer.frames)
            w.peer.frames.clear()
        deadline = _now() + LIMIT_S
        while answered < pinned:
            assert _now() < deadline, (answered, pinned)
            w.peer.pump(0.5)
            answered += sum(fr[0] == SS_RFR_RESP for fr in w.peer.frames)
            w.peer.frames.clear()
        planner.sendall(tlv(SS_HUNGRY, 2, [(F_HUNGRY, 1)]))
        w.peer.frames.clear()
        for _ in range(3):  # the fast cadence is on: full snapshots come
            assert len(w.peer.expect(SS_STATE)[2]) >= 3
        w.peer.frames.clear()
        c.sendall(b"".join(put_frame(0, b"12345678", put_id=i + 1)
                           for i in range(more)))
        ids = [w.peer.expect(TA_PUT_RESP)[2][F_PUT_ID] for _ in range(more)]
        assert ids == list(range(1, more + 1))
        snapshots = sum(fr[0] == SS_STATE for fr in w.peer.frames)
        assert snapshots <= more // 10, snapshots
        assert w.procs[1].poll() is None


@contextlib.contextmanager
def _held_to(cpu, others):
    """This process on ``cpu`` and each of ``others`` (pid: cpu) on its
    own, for the length of the block."""
    before = os.sched_getaffinity(0)
    for pid, its in others.items():
        os.sched_setaffinity(pid, {its})
    os.sched_setaffinity(0, {cpu})
    try:
        yield
    finally:
        os.sched_setaffinity(0, before)


def _paced(sock, frame, every_s, n):
    """Write ``frame`` ``n`` times, one every ``every_s`` by a busy clock."""
    t_next = time.perf_counter()
    for _ in range(n):
        while time.perf_counter() < t_next:
            pass
        sock.sendall(frame)
        t_next += every_s


def test_periodic_keeps_its_deadlines_under_a_frame_every_20_microseconds(
        family):
    """The polling phase never loses the reactor to its peer. One rank
    sends a query every 20 microseconds, well inside the budget, so every
    wait of the daemon ends in its polling phase and it never sleeps; the
    qmstat broadcast due every interval still goes out, because each look
    is one turn of the same loop and ``periodic`` runs at its top. Ten
    broadcasts have to show before a quarter of a million frames have gone
    by (they are due every thousand), and the daemon's own count says the
    waits were polled. Sender and daemon are held to two processors: a
    sender that spins on its clock never sleeps, and a scheduler that wakes
    the daemon on the sender's processor lets the two take turns, which is
    another experiment."""
    cpus = sorted(os.sched_getaffinity(0))
    if len(cpus) < 2:
        pytest.skip("one processor: the budget is 0, nobody polls")
    cfg = Config(server_impl="native", qmstat_interval=0.02,
                 exhaust_check_interval=0.02)
    with Daemons(2, 2, [2], cfg=cfg, family=family) as w, \
            _held_to(cpus[0], {w.procs[2].pid: cpus[1]}):
        flood = _connect(w.ports[2], family)
        frame = tlv(FA_INFO_NUM, 1, [(F_WORK_TYPE, 1)])
        sent = acked = seen = 0
        while seen < 10 and sent < 250_000:
            _paced(flood, frame, 20e-6, 64)
            sent += 64
            w.peer.pump(0)
            while w.peer.frames:
                tag, _src, _f = w.peer.frames.popleft()
                acked += tag == TA_INFO_NUM_RESP
                seen += acked > 0 and tag == SS_QMSTAT
        assert seen >= 10, (sent, acked, seen)
        while acked < sent:
            w.peer.expect(TA_INFO_NUM_RESP)
            acked += 1
        stats = w.finish()[2]
        assert stats["waits_polled"] > stats["waits_slept"], stats


def test_a_burst_is_read_by_the_polling_phase_and_a_trickle_by_the_sleep(
        family, phase):
    """A frame split across reads, in both phases, and the counter that
    tells them apart. ``polling``: 600 puts of 1 KB written at once are ten
    reads of 64 KB, each ending inside a frame; every turn but the first
    finds its bytes already there, so the polling phase reads them.
    ``asleep``: twenty puts, each written in two halves a thousand budgets
    apart and the next only after the answer, so every read finds the
    daemon asleep. Both ways every put is answered, in order."""
    with Daemons(1, 1, [1], family=family) as w:
        c = _connect(w.ports[1], family)
        if phase == "polling":
            n = 600
            c.sendall(b"".join(put_frame(0, bytes(1024), put_id=i + 1)
                               for i in range(n)))
            acks = [w.peer.expect(TA_PUT_RESP)[2] for _ in range(n)]
        else:
            n, acks = 20, []
            for i in range(n):
                frame = put_frame(0, bytes(1024), put_id=i + 1)
                c.sendall(frame[:500])
                time.sleep(ASLEEP_S)
                c.sendall(frame[500:])
                acks.append(w.peer.expect(TA_PUT_RESP)[2])
                time.sleep(ASLEEP_S)
        assert [f[F_PUT_ID] for f in acks] == list(range(1, n + 1))
        assert all(f[F_RC] == ADLB_SUCCESS for f in acks)
        stats = w.finish()[1]
        if phase == "polling":
            assert stats["waits_polled"] >= 1, stats
        else:
            # nothing of the trickle was there within the budget; what was
            # is the world's end (the END token the daemon sends itself, an
            # end of file behind a last frame)
            assert stats["waits_polled"] <= 3, stats
            assert stats["waits_slept"] >= 2 * n, stats


def test_a_connection_opened_while_the_daemon_waits_is_accepted(
        family, phase):
    """``polling``: a second rank connects and puts while the daemon is
    still working through another's burst, so between turns that carry
    traffic; ``asleep``: into an idle daemon. It is served either way, and
    the burst is too."""
    with Daemons(2, 1, [2], family=family) as w:
        first = _connect(w.ports[2], family)
        n = 600 if phase == "polling" else 1
        first.sendall(b"".join(put_frame(0, bytes(1024), put_id=i + 1)
                               for i in range(n)))
        ids = []
        if phase == "asleep":
            ids.append(w.peer.expect(TA_PUT_RESP)[2][F_PUT_ID])
            time.sleep(ASLEEP_S)
        second = _connect(w.ports[2], family)
        second.sendall(put_frame(1, b"late", put_id=7777))
        while len(ids) < n + 1:
            ids.append(w.peer.expect(TA_PUT_RESP)[2][F_PUT_ID])
        assert sorted(ids) == list(range(1, n + 1)) + [7777]
        assert w.procs[2].poll() is None


# ---- the client library ---------------------------------------------------

CLIENT = r"""
import ctypes, sys
lib = ctypes.CDLL(sys.argv[1])
I = ctypes.c_int
types = (I * 1)(1)
a, b, n = I(), I(), I()
assert lib.ADLB_Init(1, 0, 0, 1, types, ctypes.byref(a), ctypes.byref(b),
                     ctypes.byref(n)) == 1
def threads():
    for line in open("/proc/self/status"):
        if line.startswith("Threads:"):
            return int(line.split()[1])
print("READY", threads(), flush=True)
for line in sys.stdin:
    cmd, *args = line.split()
    if cmd == "put":  # put <bytes>: one blocking ADLB_Put
        n = int(args[0])
        buf = ctypes.create_string_buffer((bytes(range(251)) * (n // 251 + 1))[:n], n)
        print("PUT", lib.ADLB_Put(buf, n, -1, -1, 1, 0), flush=True)
    elif cmd == "puts":  # puts <count> <pause_us>: blocking puts of 64 bytes,
        # a random pause of up to pause_us microseconds ahead of each
        import random, time
        buf = ctypes.create_string_buffer(b"q" * 64, 64)
        ok = 0
        for _ in range(int(args[0])):
            until = time.perf_counter() + random.random() * int(args[1]) * 1e-6
            while time.perf_counter() < until:
                pass
            ok += lib.ADLB_Put(buf, 64, -1, -1, 1, 0) == 1
        print("PUTS", ok, flush=True)
    elif cmd == "fetch":  # fetch <type> <bytes>: Reserve and Get_reserved
        import zlib
        req = (I * 2)(int(args[0]), -1)
        wt, wp, wl, ar = I(), I(), I(), I()
        handle = (I * 5)()
        rc = lib.ADLB_Reserve(req, ctypes.byref(wt), ctypes.byref(wp), handle,
                              ctypes.byref(wl), ctypes.byref(ar))
        buf = ctypes.create_string_buffer(int(args[1]))
        rc2 = lib.ADLB_Get_reserved(buf, handle)
        print("FETCH", rc, rc2, wl.value, zlib.crc32(buf.raw[:wl.value]),
              flush=True)
    elif cmd == "iput":  # iput <count>
        for i in range(int(args[0])):
            buf = ctypes.create_string_buffer(b"%08d" % i, 8)
            assert lib.ADLB_Iput(buf, 8, -1, -1, 1, 0) == 1
        print("IPUT", flush=True)
    elif cmd == "flush":
        print("FLUSH", lib.ADLB_Flush_puts(), threads(), flush=True)
    elif cmd == "app_recv":  # app_recv <count>
        got = []
        for _ in range(int(args[0])):
            buf = ctypes.create_string_buffer(64)
            src, tag = I(), I()
            m = lib.ADLB_App_recv(buf, 64, ctypes.byref(src), ctypes.byref(tag))
            got.append((src.value, tag.value, buf.raw[:m].decode()))
        print("APP", got, flush=True)
    elif cmd == "reserve":  # reserve <type>: blocks until work or the end
        req = (I * 2)(int(args[0]), -1)
        wt, wp, wl, ar = I(), I(), I(), I()
        print("RESERVE", lib.ADLB_Reserve(
            req, ctypes.byref(wt), ctypes.byref(wp), (I * 5)(),
            ctypes.byref(wl), ctypes.byref(ar)), flush=True)
    elif cmd == "finalize":
        print("FINALIZE", lib.ADLB_Finalize(), threads(), flush=True)
        break
"""


class Client:
    """A child process that loads libadlb.so and is rank 0 of a world whose
    every other rank is the test's ``peer``; driven line by line. With
    ``family`` "tcp" its rendezvous file puts every other rank at
    OTHER_HOST, so all it opens is TCP. ``ADLB_TRACE`` is set: its
    end-of-run record is ``self.trace``."""

    def __init__(self, tmp_path, n_apps=1, family="unix", port=None,
                 server_port=None):
        from adlb_tpu.native.capi import build_libadlb

        self.port = port or local_addr_map(1)[0][1]
        self.peer = Peer(family=family)
        rv = tmp_path / "world.adlb"
        ports = dict.fromkeys(range(1, n_apps + 1), self.peer.port)
        if server_port:  # the one server, the last rank, is a daemon
            ports[n_apps] = server_port
        rv.write_text(f"0 127.0.0.1 {self.port}\n" + "".join(
            f"{r} {peer_host(family)} {p}\n" for r, p in ports.items()))
        script = tmp_path / "client.py"
        script.write_text(CLIENT)
        self.trace = tmp_path / "t.0.trace.json"
        env = dict(os.environ, ADLB_RENDEZVOUS=str(rv), ADLB_RANK="0",
                   ADLB_TRACE=str(tmp_path / "t"))
        self.proc = subprocess.Popen(
            [sys.executable, str(script), build_libadlb()], env=env,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        # a hang anywhere below ends here, as a failure
        self.watchdog = threading.Timer(2 * LIMIT_S, self.proc.kill)
        self.watchdog.start()
        assert self.line() == ["READY", "1"]

    def tell(self, cmd):
        self.proc.stdin.write(cmd + "\n")
        self.proc.stdin.flush()

    def line(self):
        """The child's next line of output, split; [] once it has gone."""
        return self.proc.stdout.readline().split()

    def counted(self, name):
        """What the finalized client counted under the event ``name``."""
        assert self.proc.wait(LIMIT_S) == 0
        (ev,) = [e for e in json.loads(self.trace.read_text())
                 if e["name"] == name]
        return ev["args"]

    def conns(self):
        """Its connections, by family."""
        return self.counted("adlb:conns")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.watchdog.cancel()
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        for f in (self.proc.stdin, self.proc.stdout, self.proc.stderr):
            f.close()
        self.peer.close()


def test_an_abort_that_arrives_outside_the_library_ends_the_rank_at_its_next_call(
        tmp_path, family):
    """The frame lies in the kernel while the rank is outside the library
    (nothing of this process reads then); the next call that waits reads it
    before anything else and the process ends with the abort's code. The
    put itself is never answered, so nothing else can have ended it."""
    with Client(tmp_path, family=family) as c:
        s = _connect(c.port, family)
        s.sendall(tlv(TA_ABORT, 1, [(F_CODE, 7)]))
        c.tell("put 8")
        assert c.proc.wait(LIMIT_S) == 7
        assert "world aborted (code 7)" in c.proc.stderr.read()
        assert c.line() == []  # it never printed a PUT line
        c.peer.expect(FA_PUT)  # the put had left before the abort was read


def test_app_messages_sent_before_app_recv_arrive_in_order(tmp_path, family):
    with Client(tmp_path, n_apps=2, family=family) as c:
        s = _connect(c.port, family)
        s.sendall(b"".join(
            tlv(AM_APP, 1, [(F_PAYLOAD, b"m%d" % i), (F_APPTAG, 100 + i)])
            for i in range(50)))
        c.tell("app_recv 50")
        want = [(1, 100 + i, "m%d" % i) for i in range(50)]
        assert " ".join(c.line()) == "APP " + str(want)
        c.tell("finalize")
        assert c.line() == ["FINALIZE", "1", "1"]
        c.peer.expect(FA_LOCAL_APP_DONE)
        # one connection accepted, one opened (to its home server)
        assert c.conns() == {"conns_unix": 2 * (family != "tcp"),
                             "conns_tcp": 2 * (family == "tcp")}
        assert dict(c.peer.accepted) == {sock_of(family): 1}


def test_two_thousand_iputs_settle_on_the_callers_own_thread(tmp_path, family):
    """2,000 ``ADLB_Iput`` and a ``Flush_puts`` against a server that
    rejects every tenth put once: every response is read and settled inside
    the library's calls, the rejects are replayed at the hinted server, and
    the process never had a second thread."""
    with Client(tmp_path, family=family) as c:
        c.tell("iput 2000")
        c.tell("flush")
        back = None
        seen = collections.Counter()
        settled = 0
        while settled < 2000:
            _tag, src, f = c.peer.expect(FA_PUT)
            if back is None:
                back = _connect(c.port, family)
            assert src == 0 and len(f[F_PAYLOAD]) == 8
            pid = f[F_PUT_ID]
            seen[pid] += 1
            if pid % 10 == 0 and seen[pid] == 1:
                back.sendall(tlv(TA_PUT_RESP, 1, [
                    (F_RC, ADLB_PUT_REJECTED), (F_HINT, 1), (F_PUT_ID, pid)]))
            else:
                back.sendall(tlv(TA_PUT_RESP, 1, [
                    (F_RC, ADLB_SUCCESS), (F_PUT_ID, pid)]))
                settled += 1
        assert c.line() == ["IPUT"]
        assert c.line() == ["FLUSH", "1", "1"]  # ADLB_SUCCESS, one thread
        assert sorted(seen) == list(range(1, 2001))
        assert all(n == (2 if pid % 10 == 0 else 1)
                   for pid, n in seen.items())


def test_a_send_that_would_block_does_not_stop_the_clients_reads(
        tmp_path, family):
    """The client's half of the blocked-send invariant: it puts 32 MB to a
    server that is not reading, while a peer sends it 32 MB of app messages.
    A client that stood in ``write`` would leave those unread and the peer
    standing in its own send; this one reads them while it waits for room,
    so the peer gets through, the server then reads, and the put returns."""
    size, chunk, n = 32 << 20, 60, (32 << 20) // 90
    with Client(tmp_path, n_apps=2, family=family) as c:
        c.peer.reading = False
        c.tell(f"put {size}")
        s = _connect(c.port, family)
        msgs = b"".join(
            tlv(AM_APP, 1, [(F_PAYLOAD, b"x" * chunk), (F_APPTAG, i)])
            for i in range(n))
        sent = []

        def send_all():
            try:
                s.sendall(msgs)
                sent.append(len(msgs))
            except OSError:  # the client died; the assertion below says so
                pass

        sender = threading.Thread(target=send_all, daemon=True)
        sender.start()
        sender.join(LIMIT_S)
        assert sent == [len(msgs)], "the client stopped reading"
        c.peer.resume_reading()
        _tag, _src, f = c.peer.expect(FA_PUT)
        assert len(f[F_PAYLOAD]) == size
        s.sendall(tlv(TA_PUT_RESP, 2, [(F_RC, ADLB_SUCCESS)]))
        assert c.line() == ["PUT", "1"]
        c.tell(f"app_recv {n}")
        got = " ".join(c.line())
        assert got.startswith("APP [(1, 0, '") and got.count("(1, ") == n


def _back_connection(c, family):
    """A connection into the client that it has accepted and read from: one
    put, answered over it a thousand budgets late (so that wait slept)."""
    c.tell("put 8")
    c.peer.expect(FA_PUT)
    back = _connect(c.port, family)
    time.sleep(ASLEEP_S)
    back.sendall(tlv(TA_PUT_RESP, 1, [(F_RC, ADLB_SUCCESS)]))
    assert c.line() == ["PUT", "1"]
    return back


def test_an_answer_within_the_budget_is_polled_and_a_late_one_slept_for(
        tmp_path, family, phase):
    """The mechanism's two counters. ``polling``: each of twenty answers is
    in the client's socket before the put that awaits it is made, so the
    first look of the polling phase reads it: twenty waits polled, and not
    one sleeping call beyond the first put's. ``asleep``: each answer comes
    a thousand budgets after its put: every wait slept, none polled, which
    is also what says the polling phase ends."""
    with Client(tmp_path, family=family) as c:
        back = _back_connection(c, family)
        for _ in range(20):
            if phase == "polling":
                back.sendall(tlv(TA_PUT_RESP, 1, [(F_RC, ADLB_SUCCESS)]))
                time.sleep(0.01)  # it is there before the put is made
                c.tell("put 8")
                c.peer.expect(FA_PUT)
            else:
                c.tell("put 8")
                c.peer.expect(FA_PUT)
                time.sleep(ASLEEP_S)
                back.sendall(tlv(TA_PUT_RESP, 1, [(F_RC, ADLB_SUCCESS)]))
            assert c.line() == ["PUT", "1"]
        c.tell("finalize")
        assert c.line() == ["FINALIZE", "1", "1"]
        want = {"waits_polled": 20, "waits_slept": 1}
        if phase == "asleep":
            want = {"waits_polled": 0, "waits_slept": 21}
        assert c.counted("adlb:waits") == want


def test_answers_over_two_connections_in_turn_are_polled_all_the_same(
        tmp_path, family):
    """The read of one connection ahead of each look is for a rank whose
    answers keep coming over one connection, and is not in the way of one
    whose answers do not: twenty answers, each there before its put is
    made, come over two connections in turn, and the look at all of them
    finds every one; then the connection of the last answers closes, the
    next wait reads its end first, and the answer that comes over the other
    ends that wait."""
    with Client(tmp_path, family=family) as c:
        backs = [_back_connection(c, family), _back_connection(c, family)]
        for i in range(20):
            backs[i % 2].sendall(tlv(TA_PUT_RESP, 1, [(F_RC, ADLB_SUCCESS)]))
            time.sleep(0.01)
            c.tell("put 8")
            c.peer.expect(FA_PUT)
            assert c.line() == ["PUT", "1"]
        for _ in range(2):  # now backs[0] alone delivers, so it is read first
            backs[0].sendall(tlv(TA_PUT_RESP, 1, [(F_RC, ADLB_SUCCESS)]))
            time.sleep(0.01)
            c.tell("put 8")
            c.peer.expect(FA_PUT)
            assert c.line() == ["PUT", "1"]
        backs[0].close()  # what the next wait reads first is its end
        time.sleep(0.01)
        c.tell("put 8")
        c.peer.expect(FA_PUT)
        time.sleep(ASLEEP_S)
        backs[1].sendall(tlv(TA_PUT_RESP, 1, [(F_RC, ADLB_SUCCESS)]))
        assert c.line() == ["PUT", "1"]
        c.tell("finalize")
        assert c.line() == ["FINALIZE", "1", "1"]
        # the two that made the connections and the last slept
        assert c.counted("adlb:waits") == {"waits_polled": 22,
                                           "waits_slept": 3}


def test_frames_of_other_tags_read_in_a_wait_for_an_answer_are_handled(
        tmp_path, family, phase):
    """Fifty app messages arrive while the client awaits the answer to a
    put: before the put is made, so that the polling phase reads them, or
    once it sleeps. They are stashed in order on the way, the answer still
    ends the put, and ``App_recv`` then finds them without a read."""
    msgs = b"".join(
        tlv(AM_APP, 1, [(F_PAYLOAD, b"m%d" % i), (F_APPTAG, 100 + i)])
        for i in range(50))
    with Client(tmp_path, n_apps=2, family=family) as c:
        back = _back_connection(c, family)
        if phase == "polling":
            back.sendall(msgs)
            time.sleep(0.01)
        c.tell("put 8")
        c.peer.expect(FA_PUT)
        time.sleep(ASLEEP_S)
        if phase == "asleep":
            back.sendall(msgs)
            time.sleep(ASLEEP_S)
        back.sendall(tlv(TA_PUT_RESP, 2, [(F_RC, ADLB_SUCCESS)]))
        assert c.line() == ["PUT", "1"]
        c.tell("app_recv 50")
        want = [(1, 100 + i, "m%d" % i) for i in range(50)]
        assert " ".join(c.line()) == "APP " + str(want)


def test_a_connection_opened_while_the_client_awaits_an_answer_is_accepted(
        tmp_path, family, phase):
    """The answer to a put comes over a connection the client has not seen
    yet: opened, and the answer written, before the put is made, so that it
    waits in the listener's queue for the polling phase to accept and read
    it; or opened once the client sleeps."""
    with Client(tmp_path, family=family) as c:
        if phase == "polling":
            back = _connect(c.port, family)
            back.sendall(tlv(TA_PUT_RESP, 1, [(F_RC, ADLB_SUCCESS)]))
            time.sleep(0.01)
        c.tell("put 8")
        c.peer.expect(FA_PUT)
        if phase == "asleep":
            time.sleep(ASLEEP_S)
            back = _connect(c.port, family)
            back.sendall(tlv(TA_PUT_RESP, 1, [(F_RC, ADLB_SUCCESS)]))
        assert c.line() == ["PUT", "1"]
        c.tell("finalize")
        assert c.line() == ["FINALIZE", "1", "1"]
        # opened one, accepted one
        assert c.conns()["conns_" + sock_of(family)] == 2


def test_a_peer_that_sends_more_than_the_sockets_hold_into_a_wait_for_an_answer(
        tmp_path, family):
    """The blocked-send invariant from inside the polling phase and the
    sleep behind it: the client has a small put out and awaits its answer
    while a peer writes it 8 MB of app messages in one go. The client reads
    all the while, in both phases of the wait, or the peer would stand in
    its send; then the answer comes and the put returns."""
    chunk, n = 60, (8 << 20) // 90
    msgs = b"".join(
        tlv(AM_APP, 1, [(F_PAYLOAD, b"x" * chunk), (F_APPTAG, i)])
        for i in range(n))
    with Client(tmp_path, n_apps=2, family=family) as c:
        back = _back_connection(c, family)
        c.tell("put 8")
        c.peer.expect(FA_PUT)
        back.settimeout(LIMIT_S)
        back.sendall(msgs)  # a client that stopped reading ends this in a timeout
        back.sendall(tlv(TA_PUT_RESP, 2, [(F_RC, ADLB_SUCCESS)]))
        assert c.line() == ["PUT", "1"]
        c.tell(f"app_recv {n}")
        got = " ".join(c.line())
        assert got.startswith("APP [(1, 0, '") and got.count("(1, ") == n


def _cpu_s(pid):
    """User and system time of ``pid`` so far, in seconds."""
    with open(f"/proc/{pid}/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


@pytest.fixture(params=["ring", "socket"])
def build(request):
    """Which native ranks a test of real ranks starts: the shipped build,
    whose Unix connections carry rings, or the one without segments."""
    if request.param == "socket":
        request.getfixturevalue("noseg")
    return request.param


def test_an_idle_daemon_and_a_parked_client_use_no_processor_time(
        tmp_path, build):
    """What fails if the polling phase ever loses its bound. A daemon that
    has served a put and a client parked at it in a ``Reserve`` nobody will
    answer (the exhaustion vote is a minute away) each use under 5% of a
    core over two seconds: the client polled for one budget and sleeps, the
    daemon's turns end on their timeouts and poll nothing. With rings each
    sleeps marked asleep, and looks at no ring meanwhile."""
    import types

    port = local_addr_map(1)[0][1]
    cfg = Config(server_impl="native", exhaust_check_interval=60.0)
    stub = types.SimpleNamespace(port=port, close=lambda: None)
    with Daemons(1, 1, [1], cfg=cfg, types=(1,), peer=stub) as w, \
            Client(tmp_path, port=port, server_port=w.ports[1]) as c:
        c.tell("put 8")
        assert c.line() == ["PUT", "1"]
        c.tell("reserve 1")
        assert c.line() == ["RESERVE", "1"]  # its own unit
        c.tell("reserve 1")  # and now nothing is left: parked
        time.sleep(0.5)
        pids = {"daemon": w.procs[1].pid, "client": c.proc.pid}
        before = {k: _cpu_s(pid) for k, pid in pids.items()}
        time.sleep(2.0)
        used = {k: _cpu_s(pid) - before[k] for k, pid in pids.items()}
        assert all(u < 0.05 * 2.0 for u in used.values()), used
        assert w.procs[1].poll() is None and c.proc.poll() is None


# ---- the ring ---------------------------------------------------------------

_PUT = len(put_frame(0, b"", put_id=1))  # a put's frame with no payload

# whole frames, length prefix and all; the first two by their payload
SIZES = {"0B": _PUT, "64B": _PUT + 64, "ring-1": RING - 1, "ring": RING,
         "8xring": 8 * RING}


@pytest.mark.parametrize("size", list(SIZES))
def test_frames_of_every_size_against_the_rings_arrive_whole_and_in_order(
        size):
    """The ring is a byte stream: a frame that fits to the byte, one that
    leaves one byte free, and one of eight rings (which goes through in
    installments, the reader ringing for each refill) arrive as they were
    sent, between small frames that keep their places; so do a put of no
    payload and one of 64 bytes. Three of the size, at three different
    offsets of the ring."""
    with Daemons(1, 1, [1], family="ring") as w:
        c = _connect(w.ports[1], "ring")
        frames, want_bytes = [], 0
        for k in range(3):
            small = put_frame(0, b"s%d" % k, put_id=2 * k + 1)
            payload = bytes(i % 251 for i in range(SIZES[size] - _PUT))
            big = put_frame(0, payload, put_id=2 * k + 2)
            assert len(big) == SIZES[size]
            frames += [small, big]
            want_bytes += 2 + len(payload)
        c.sendall(b"".join(frames))
        acks = [w.peer.expect(TA_PUT_RESP)[2] for _ in frames]
        assert [f[F_PUT_ID] for f in acks] == list(range(1, 7))
        assert all(f[F_RC] == ADLB_SUCCESS for f in acks)
        c.sendall(tlv(FA_INFO_NUM, 0, [(F_WORK_TYPE, 1)]))
        f = w.peer.expect(TA_INFO_NUM_RESP)[2]
        assert (f[F_COUNT], f[F_NBYTES]) == (6, want_bytes)
        assert set(w.peer.paths) == {"ring"}  # the answers' path too
        stats = w.finish()[1]
        assert stats["frames_ring"] >= 7, stats


def test_a_unit_of_eight_rings_crosses_both_rings_of_a_native_pair(tmp_path):
    """Client and daemon, both native, both with the shipped build: a put
    of eight rings' worth goes to the daemon in installments and comes back
    in installments with the fetch, byte for byte; each end counts its
    frames as the ring's."""
    import types
    import zlib

    n = 8 * RING + 13
    port = local_addr_map(1)[0][1]
    stub = types.SimpleNamespace(port=port, close=lambda: None)
    with Daemons(1, 1, [1], types=(1,), peer=stub) as w, \
            Client(tmp_path, port=port, server_port=w.ports[1]) as c:
        c.tell(f"put {n}")
        assert c.line() == ["PUT", "1"]
        c.tell(f"fetch 1 {n}")
        want = zlib.crc32((bytes(range(251)) * (n // 251 + 1))[:n])
        assert c.line() == ["FETCH", "1", "1", str(n), str(want)]
        c.tell("finalize")
        assert c.line() == ["FINALIZE", "1", "1"]
        rings = c.counted("adlb:rings")
        assert rings["frames_ring"] >= 3 and rings["frames_sock"] == 0, rings
        stats, _abort, rc = daemon_mod.collect_stats(w.procs[1], LIMIT_S)
        assert rc == 0
        assert stats["frames_ring"] >= 4 and stats["frames_sock"] == 0, stats
        # each side published the unit in eight installments at least
        for end in (stats, rings):
            assert end["bells_rung"] + end["bells_elided"] >= 8, end


@pytest.mark.parametrize("family", ["unix", "ring"])
def test_a_full_connection_toward_a_stopped_reader_stops_neither_reads_nor_periodic(
        family, request):
    """A daemon's send never blocks, ring or socket. One app rank does not
    read, so the answers to the 40,000 puts sent in its name fill the
    daemon's connection toward it (a ring holds some 1,500 of them) and
    queue behind it. The daemon still reads every put, answers another
    rank's query with all of them counted, and sends the qmstat broadcast
    due every interval; when the stopped rank reads again it gets its
    answers, every one, in order."""
    if family == "unix":
        request.getfixturevalue("noseg")
    n = 40_000
    cfg = Config(server_impl="native", qmstat_interval=0.02,
                 exhaust_check_interval=60.0)
    stopped = Peer(family=family)
    stopped.reading = False
    try:
        with Daemons(2, 2, [2], cfg=cfg, family=family,
                     others={1: stopped.port}) as w:
            flood = _connect(w.ports[2], family)
            for at in range(0, n, 500):
                flood.sendall(b"".join(
                    put_frame(1, b"12345678", put_id=i + 1)
                    for i in range(at, at + 500)))
                stopped.pump(0)  # accepts; reads nothing
            ask = _connect(w.ports[2], family)
            deadline = _now() + LIMIT_S
            while True:
                ask.sendall(tlv(FA_INFO_NUM, 0, [(F_WORK_TYPE, 1)]))
                if w.peer.expect(TA_INFO_NUM_RESP)[2][F_COUNT] == n:
                    break
                assert _now() < deadline, "the daemon stopped reading"
            w.peer.frames.clear()
            for _ in range(5):  # and periodic() still keeps its deadlines
                w.peer.expect(SS_QMSTAT)
            assert not stopped.frames
            stopped.resume_reading()
            ids = [stopped.expect(TA_PUT_RESP)[2][F_PUT_ID] for _ in range(n)]
            assert ids == list(range(1, n + 1))
            assert w.procs[2].poll() is None
    finally:
        stopped.close()


WAKEUP_CPP = r"""
// Two processes, two rings (frames one way, acknowledgements the other), both
// ends under the discipline of hostsock.hpp: spin a random while, then mark
// asleep, fence, look once more, poll() WITHOUT a timeout; the writer
// publishes at a random offset after the acknowledgement that sends the
// reader toward its sleep. A lost wake-up is a hang.
#include <poll.h>
#include <sys/wait.h>
#include <cstdio>
#include <cstdlib>
#include "hostsock.hpp"
using namespace hostsock;
static long slept = 0;
static void await(RingRx& rx, int fd, std::string& buf, unsigned spins) {
  bool bell;
  for (;;) {
    if (rx.ready()) { rx.take(buf, &bell); if (buf.size() >= 8) break; }
    if (spins > 0) { --spins; continue; }
    rx.sleeps(true);
    sleep_fence();
    if (!rx.ready()) {
      pollfd p{fd, POLLIN, 0};
      if (poll(&p, 1, -1) < 0) _exit(5);
      ++slept;
      char b[64];
      if (recv(fd, b, sizeof b, MSG_DONTWAIT) == 0) _exit(6);
    }
    rx.sleeps(false);
  }
  buf.erase(0, 8);
}
static void end_of(int lport, int cport, bool writer, int rounds) {
  int ls = listen_unix(lport, 4);
  if (ls < 0) _exit(2);
  int out;
  while ((out = connect_unix(cport)) < 0) usleep(1000);
  RingTx tx;
  if (!tx.open(out) || !tx.on()) _exit(3);
  int in;
  while ((in = accept(ls, nullptr, nullptr)) < 0) usleep(100);
  HelloRx h; RingRx rx; Hello hr;
  while ((hr = recv_hello(in, h, &rx)) == Hello::kMore) {}
  if (hr != Hello::kRing) _exit(4);
  rx.sleeps(false);
  std::string buf;
  unsigned seed = writer ? 12345u : 54321u;
  char frame[8] = {0};
  for (int i = 0; i < rounds; ++i) {
    if (writer) {
      for (volatile unsigned k = rand_r(&seed) % 3000; k > 0; --k) {}
      tx.write(frame, 8);
      if (!tx.kick(out)) _exit(7);
    }
    await(rx, in, buf, rand_r(&seed) % 600);
    if (!writer) {
      tx.write(frame, 8);
      if (!tx.kick(out)) _exit(7);
    }
  }
  std::printf("%s rounds=%d slept=%ld rung=%lld elided=%lld\n",
              writer ? "WRITER" : "READER", rounds, slept,
              (long long)ring_stats().bells_rung,
              (long long)ring_stats().bells_elided);
  std::fflush(stdout);
}
int main(int argc, char** argv) {
  int port = std::atoi(argv[1]), rounds = std::atoi(argv[2]);
  pid_t pid = fork();
  if (pid == 0) { end_of(port, port + 1, false, rounds); _exit(0); }
  end_of(port + 1, port, true, rounds);
  int st = 0;
  waitpid(pid, &st, 0);
  (void)argc;
  return WIFEXITED(st) ? WEXITSTATUS(st) : 9;
}
"""


def test_no_wake_up_is_lost_in_a_hundred_thousand_entries_into_sleep(tmp_path):
    """The handshake, with no timer behind it. A writer publishes at random
    offsets around its reader's entry into sleep, 10**5 times, each end
    sleeping in ``poll`` with no timeout: store, fence, load on both sides
    means one of the two always sees the other, so the run ends. Both
    branches are taken many times (bells rung for a sleeper, bells elided
    for a reader that was awake), or the offsets missed the window."""
    from adlb_tpu.native import build

    src = tmp_path / "wakeup.cpp"
    src.write_text(WAKEUP_CPP)
    exe = build.build_artifact(
        "ring_wakeup",
        ["g++", "-O2", "-std=c++17", f"-I{os.path.dirname(build.HOSTSOCK_HDR)}",
         "-o", "{out}", str(src)],
        [str(src), build.HOSTSOCK_HDR])
    port = local_addr_map(2)[0][1]
    out = subprocess.run([exe, str(port), "100000"], capture_output=True,
                         text=True, timeout=4 * LIMIT_S)
    assert out.returncode == 0, (out.returncode, out.stdout, out.stderr)
    seen = {ln.split()[0]: dict(kv.split("=") for kv in ln.split()[1:])
            for ln in out.stdout.splitlines()}
    assert set(seen) == {"WRITER", "READER"}, out.stdout
    for end in seen.values():
        assert int(end["rounds"]) == 100_000
    if len(os.sched_getaffinity(0)) > 1:
        assert all(int(e["slept"]) > 100 and int(e["elided"]) > 100
                   for e in seen.values()), seen


def test_puts_at_random_offsets_around_the_daemons_entry_into_sleep(tmp_path):
    """The same through both native files: 20,000 blocking puts, each up to
    a hundred microseconds after the last one's answer, so around the end
    of the daemon's polling budget. The daemon's own deadlines are a minute
    away and the client's sleep has none, so a wake-up lost on either side
    is a hang. Both ends saw both cases."""
    import types

    port = local_addr_map(1)[0][1]
    cfg = Config(server_impl="native", qmstat_interval=60.0,
                 exhaust_check_interval=60.0)
    stub = types.SimpleNamespace(port=port, close=lambda: None)
    with Daemons(1, 1, [1], cfg=cfg, types=(1,), peer=stub) as w, \
            Client(tmp_path, port=port, server_port=w.ports[1]) as c:
        c.tell("puts 20000 100")
        assert c.line() == ["PUTS", "20000"]
        c.tell("finalize")
        assert c.line() == ["FINALIZE", "1", "1"]
        rings = c.counted("adlb:rings")
        stats, _abort, rc = daemon_mod.collect_stats(w.procs[1], LIMIT_S)
        assert rc == 0
        assert rings["frames_ring"] >= 20_000 and rings["frames_sock"] == 0
        assert stats["frames_ring"] >= 20_000 and stats["frames_sock"] == 0
        if len(os.sched_getaffinity(0)) > 1:
            assert rings["bells_rung"] > 0 and rings["bells_elided"] > 0, rings
            assert stats["waits_slept"] > 0 and stats["waits_polled"] > 0, stats


def test_a_peer_killed_with_frames_in_its_ring_is_read_out_before_its_death():
    """500 puts lie in the ring, unannounced (no bell, the daemon asleep a
    minute from its next deadline), when their writer is ended by SIGKILL.
    What tells the daemon is the socket's EOF; it takes what the ring still
    holds first, answers all 500 in order, and only then reads the lost
    connection as rank death."""
    cfg = Config(server_impl="native", qmstat_interval=60.0,
                 exhaust_check_interval=60.0)
    with Daemons(1, 1, [1], cfg=cfg, family="ring") as w:
        first = _connect(w.ports[1], "ring")
        first.sendall(put_frame(0, b"first", put_id=1000))
        assert w.peer.expect(TA_PUT_RESP)[2][F_PUT_ID] == 1000
        time.sleep(ASLEEP_S)  # the daemon sleeps, a minute from a deadline
        r, wr = os.pipe()
        pid = os.fork()
        if pid == 0:  # the rank that dies
            try:
                c = _connect(w.ports[1], "ring")
                c.sendall(b"".join(put_frame(0, struct.pack("<q", i),
                                             put_id=i + 1)
                                   for i in range(500)), bell=False)
                os.write(wr, b"k")
                signal.pause()
            finally:
                os._exit(1)
        assert os.read(r, 1) == b"k"
        os.kill(pid, signal.SIGKILL)
        os.waitpid(pid, 0)
        os.close(r)
        os.close(wr)
        ids = [w.peer.expect(TA_PUT_RESP)[2] for _ in range(500)]
        assert [f[F_PUT_ID] for f in ids] == list(range(1, 501))
        _tag, _src, f = w.peer.expect(TA_ABORT)
        assert f[F_CODE] == -3
        assert not [fr for fr in w.peer.frames if fr[0] == TA_PUT_RESP]
        assert w.procs[1].wait(LIMIT_S) == 2
        first.close()


def _was_closed(sock):
    """Did the other end close ``sock``? (With bytes of ours unread, a Unix
    socket says so by a reset.)"""
    try:
        return sock.recv(16) == b""
    except ConnectionResetError:
        return True


NO_HELLO = {
    "a-frame-first": put_frame(0, b"a perfectly good frame, and no hello"),
    "wrong-magic": hello(0, magic=b"ADLBrinG"),
    "wrong-version": hello(0, version=2),
    "a-size-and-no-segment": hello(RING),
    "another-size": hello(RING // 2),
    "two-bytes": b"\x99\x99",
}


@pytest.mark.parametrize("kind", sorted(NO_HELLO))
def test_a_unix_connection_that_does_not_begin_with_the_hello_is_closed(kind):
    """An untrusted stray, as garbage before a first frame is: the daemon
    closes that connection (at the first bytes that cannot be a hello's) and
    goes on serving the others, ring and socket."""
    with Daemons(1, 1, [1], family="ring") as w:
        served = [_connect(w.ports[1], "ring"), _connect(w.ports[1], "unix")]
        for c in served:
            c.sendall(put_frame(0, b"a"))
            assert w.peer.expect(TA_PUT_RESP)[2][F_RC] == ADLB_SUCCESS
        stray = socket.socket(socket.AF_UNIX)
        stray.settimeout(LIMIT_S)
        stray.connect(unix_name(w.ports[1]))
        stray.sendall(NO_HELLO[kind])
        assert _was_closed(stray)
        stray.close()
        for c in served:
            c.sendall(put_frame(0, b"b"))
            assert w.peer.expect(TA_PUT_RESP)[2][F_RC] == ADLB_SUCCESS
        assert w.procs[1].poll() is None


def test_an_abort_without_a_hello_does_not_end_the_client(tmp_path):
    """The client's side of the same policy: the frame that would end the
    rank had it come from a rank (an abort) arrives on a Unix connection
    with no hello before it; the connection is closed, the rank lives and
    its next put is answered."""
    with Client(tmp_path, family="ring") as c:
        stray = socket.socket(socket.AF_UNIX)
        stray.settimeout(LIMIT_S)
        stray.connect(unix_name(c.port))
        stray.sendall(tlv(TA_ABORT, 1, [(F_CODE, 7)]))
        back = _back_connection(c, "ring")  # a wait that reads the stray too
        assert _was_closed(stray)
        c.tell("put 8")
        c.peer.expect(FA_PUT)
        back.sendall(tlv(TA_PUT_RESP, 1, [(F_RC, ADLB_SUCCESS)]))
        assert c.line() == ["PUT", "1"]
        c.tell("finalize")
        assert c.line() == ["FINALIZE", "1", "1"]


def test_a_python_tcp_peer_is_served_while_a_ring_peer_floods():
    """While its frames come through rings the daemon's polling phase reads
    memory, and asks its descriptors once a budget: so a ``TcpEndpoint``
    peer (the sidecar, a Python rank of a mixed world) is not starved by a
    native peer that never lets the daemon sleep. One rank keeps 256
    queries in flight through a ring without a gap; a Python endpoint makes
    twenty blocking puts over TCP meanwhile, and each is answered while the
    flood lasts."""
    from adlb_tpu.runtime.messages import Tag, msg
    from adlb_tpu.runtime.transport_tcp import TcpEndpoint

    tcp_port = local_addr_map(1)[0][1]
    with Daemons(2, 1, [2], family="ring", others={1: tcp_port}) as w:
        addr = {1: ("127.0.0.1", tcp_port), 2: ("127.0.0.1", w.ports[2])}
        ep = TcpEndpoint(1, addr, binary_peers={2})
        done = []

        def tcp_puts():
            for _ in range(20):
                ep.send(2, msg(Tag.FA_PUT, 1, payload=b"abc", work_type=1,
                               prio=0, target_rank=-1, answer_rank=-1,
                               common_len=0, common_server=-1,
                               common_seqno=-1))
                resp = ep.recv(LIMIT_S)
                assert resp is not None and resp.tag is Tag.TA_PUT_RESP
                done.append(resp.rc)

        try:
            flood = _connect(w.ports[2], "ring")
            query = tlv(FA_INFO_NUM, 0, [(F_WORK_TYPE, 2)])
            sent = acked = 0
            putter = threading.Thread(target=tcp_puts, daemon=True)
            putter.start()
            while putter.is_alive() and sent < 2_000_000:
                if sent - acked < 256:
                    flood.sendall(query * 128)
                    sent += 128
                w.peer.pump(0 if sent - acked < 256 else 1.0)
                acked += len(w.peer.frames)
                w.peer.frames.clear()
            assert done == [ADLB_SUCCESS] * 20, (done, sent, acked)
            assert sent - acked <= 256 + 128  # it was a flood to the end
            while acked < sent:
                w.peer.expect(TA_INFO_NUM_RESP)
                acked += 1
            stats = w.finish()[2]
            assert stats["frames_ring"] >= sent, stats
            assert stats["frames_sock"] >= 21, stats
            assert stats["conns_tcp"] >= 2, stats
        finally:
            ep.close()


def test_the_counters_of_an_idle_world_say_the_ring_engaged(tmp_path):
    """Three thousand blocking puts between a client and a daemon that have
    a processor each: every frame either way came through a ring
    (``frames_ring`` is the puts and a few more, ``frames_sock`` nothing),
    and nine publishes in ten found their reader awake and rang no bell
    (``bells_elided`` against ``bells_rung``), on both ends."""
    import types

    cpus = sorted(os.sched_getaffinity(0))
    if len(cpus) < 3:
        pytest.skip("needs a processor each for client, daemon and test")
    port = local_addr_map(1)[0][1]
    cfg = Config(server_impl="native", exhaust_check_interval=60.0)
    stub = types.SimpleNamespace(port=port, close=lambda: None)
    with Daemons(1, 1, [1], cfg=cfg, types=(1,), peer=stub) as w, \
            Client(tmp_path, port=port, server_port=w.ports[1]) as c, \
            _held_to(cpus[0], {w.procs[1].pid: cpus[1], c.proc.pid: cpus[2]}):
        c.tell("puts 3000 0")
        assert c.line() == ["PUTS", "3000"]
        c.tell("finalize")
        assert c.line() == ["FINALIZE", "1", "1"]
        rings = c.counted("adlb:rings")
        stats, _abort, rc = daemon_mod.collect_stats(w.procs[1], LIMIT_S)
        assert rc == 0
        for end in (rings, stats):
            assert 3000 <= end["frames_ring"] <= 3010, end
            assert end["frames_sock"] == 0, end
            assert end["bells_elided"] > 9 * end["bells_rung"], end
            assert end["bells_elided"] + end["bells_rung"] >= 3000, end


# ---- which family, and whose name -----------------------------------------

def _examples():
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples")


@pytest.mark.parametrize("balancer", ["steal", "tpu"])
def test_a_one_host_native_world_is_unix_between_its_native_ranks(
        tmp_path, balancer, build):
    """C clients and C++ daemons of one host: every connection between two
    of them is a Unix-domain one, on both ends' counters. TCP appears only
    toward a Python peer, and the one such peer a native world can have is
    the planner's sidecar (``balancer="tpu"``): each daemon opens one
    connection to it and accepts at most one from it. With the shipped
    build every frame between two native ranks came through a ring, and
    what came over a socket is the sidecar's; with the build whose segment
    constructor fails the same world runs, every frame over a socket."""
    if shutil.which("gcc") is None:
        pytest.skip("no C toolchain")
    from adlb_tpu.native.capi import build_example, run_native_world

    exe = build_example(os.path.join(_examples(), "capi_smoke.c"))
    results, stats = run_native_world(
        n_clients=3, nservers=2, types=[1, 2], exe=exe,
        cfg=Config(server_impl="native", balancer=balancer,
                   exhaust_check_interval=0.2),
        env_extra={"ADLB_TRACE": str(tmp_path / "t")}, timeout=90.0)
    for rc, out, err in results:
        assert rc == 0, f"exit {rc}\nstdout:{out}\nstderr:{err}"
    for rank in (3, 4):
        assert stats[rank]["conns_unix"] >= 2, stats[rank]
        assert stats[rank]["conns_tcp"] in (
            (0,) if balancer == "steal" else (1, 2)), stats[rank]
        # every wait of the reactor ended one way or the other
        assert stats[rank]["waits_polled"] + stats[rank]["waits_slept"] > 0
        if build == "socket":
            assert stats[rank]["frames_ring"] == 0, stats[rank]
            assert stats[rank]["bells_rung"] == 0, stats[rank]
            assert stats[rank]["frames_sock"] > 0, stats[rank]
        else:
            assert stats[rank]["frames_ring"] > 0, stats[rank]
            if balancer == "steal":  # no Python peer: nothing over a socket
                assert stats[rank]["frames_sock"] == 0, stats[rank]
    for rank in range(3):
        events = json.loads((tmp_path / f"t.{rank}.trace.json").read_text())
        (ev,) = [e for e in events if e["name"] == "adlb:conns"]
        assert ev["args"]["conns_tcp"] == 0
        assert ev["args"]["conns_unix"] >= 2  # opened one, accepted one
        (ev,) = [e for e in events if e["name"] == "adlb:waits"]
        assert ev["args"]["waits_polled"] + ev["args"]["waits_slept"] > 0
        (ev,) = [e for e in events if e["name"] == "adlb:rings"]
        path, other = (("frames_sock", "frames_ring") if build == "socket"
                       else ("frames_ring", "frames_sock"))
        assert ev["args"][path] > 0 and ev["args"][other] == 0, ev["args"]


def test_a_python_peer_reaches_a_daemon_and_is_reached_by_it_over_tcp():
    """The fallback. A Python ``TcpEndpoint`` has no Unix listener and
    connects over TCP: the daemon serves it, finds nobody at the name of its
    port and answers over TCP, and counts exactly those two connections as
    ``tcp``; what is left is the daemon's connection to itself."""
    from adlb_tpu.runtime.messages import Tag, msg
    from adlb_tpu.runtime.transport_tcp import TcpEndpoint

    world = WorldSpec(nranks=2, nservers=1, types=(1,))
    proc = daemon_mod.spawn_daemon(world, Config(server_impl="native"), 1)
    ep = None
    try:
        addr = {0: ("127.0.0.1", local_addr_map(1)[0][1]),
                1: ("127.0.0.1", daemon_mod.read_hello(proc, 1))}
        ep = TcpEndpoint(0, addr, binary_peers={1})
        daemon_mod.send_addrs(proc, addr)
        ep.send(1, msg(Tag.FA_PUT, 0, payload=b"abc", work_type=1, prio=0,
                       target_rank=-1, answer_rank=-1, common_len=0,
                       common_server=-1, common_seqno=-1))
        resp = ep.recv(LIMIT_S)
        assert resp is not None and resp.tag is Tag.TA_PUT_RESP
        assert resp.rc == ADLB_SUCCESS
        ep.send(1, msg(Tag.FA_LOCAL_APP_DONE, 0))
        stats, _abort, rc = daemon_mod.collect_stats(proc, timeout=LIMIT_S)
        assert rc == 0, rc
        assert stats["conns_tcp"] == 2, stats
        assert stats["conns_unix"] in (0, 2), stats  # to itself, if at all
    finally:
        if ep is not None:
            ep.close()
        if proc.poll() is None:
            proc.kill()
        proc.wait()


def test_a_destination_on_another_host_is_never_tried_over_unix(tmp_path):
    """The address map alone decides who is tried: the test's rank listens
    on both families, and a daemon and a client that are told it lives at
    OTHER_HOST come to its TCP listener; told 127.0.0.1, to its name; and
    told OTHER_HOST by a map that puts the daemon itself there too (one
    host under a name that is no loopback address), to its name again."""
    for told, own, came in (("tcp", "127.0.0.1", "tcp"),
                            ("unix", "127.0.0.1", "unix"),
                            ("tcp", OTHER_HOST, "unix")):
        peer = Peer(family="unix")  # both listeners, whatever it is told
        with Daemons(1, 1, [1], family=told, peer=peer, own_host=own) as w:
            c = _connect(w.ports[1], "unix")
            c.sendall(put_frame(0, b"a"))
            assert w.peer.expect(TA_PUT_RESP)[2][F_RC] == ADLB_SUCCESS
            assert dict(w.peer.accepted) == {came: 1}
    sub = tmp_path / "client"
    sub.mkdir()
    with Client(sub, family="tcp") as c:
        c.peer.close()
        c.peer = Peer(port=c.peer.port, family="unix")
        c.tell("put 8")
        c.peer.expect(FA_PUT)
        assert dict(c.peer.accepted) == {"tcp": 1}


def test_two_worlds_at_once_on_one_host_do_not_meet():
    """Names follow ports: two worlds up at the same time have four
    different names, every rank answers at its own world's peer, and the
    other world sees nothing of it."""
    with Daemons(1, 1, [1]) as a, Daemons(1, 1, [1]) as b:
        ports = [a.ports[1], a.peer.port, b.ports[1], b.peer.port]
        assert len(set(ports)) == 4
        assert {"@adlb_tpu.%d" % p for p in ports} <= bound_names()
        conns = [_connect(w.ports[1], "unix") for w in (a, b)]
        for w, c, payload in ((a, conns[0], b"from-a"),
                              (b, conns[1], b"from-b")):
            c.sendall(put_frame(0, payload, put_id=7))
            assert w.peer.expect(TA_PUT_RESP)[2][F_RC] == ADLB_SUCCESS
        for w, c, n in ((a, conns[0], len(b"from-a")),
                        (b, conns[1], len(b"from-b"))):
            c.sendall(tlv(FA_INFO_NUM, 0, [(F_WORK_TYPE, 1)]))
            f = w.peer.expect(TA_INFO_NUM_RESP)[2]
            assert (f[F_COUNT], f[F_NBYTES]) == (1, n)
            w.peer.pump(0.05)
            assert not w.peer.frames
            assert dict(w.peer.accepted) == {"unix": 1}


def test_a_rank_that_dies_leaves_no_name_behind(tmp_path):
    """An abstract name has no file and dies with its process: a killed
    daemon's and a killed client's are free at once, and a second world may
    take the port, name and all."""
    with Daemons(1, 1, [1]) as w:
        port = w.ports[1]
        assert "@adlb_tpu.%d" % port in bound_names()
        w.procs[1].kill()
        w.procs[1].wait()
        assert "@adlb_tpu.%d" % port not in bound_names()
        with pytest.raises(ConnectionRefusedError):
            _connect(port, "unix")
        Peer(port=port, family="unix").close()  # the next world's rank
    for sub in ("first", "second"):
        (tmp_path / sub).mkdir()
    with Client(tmp_path / "first") as c:
        port = c.port
        assert "@adlb_tpu.%d" % port in bound_names()
        c.proc.kill()
        c.proc.wait()
        assert "@adlb_tpu.%d" % port not in bound_names()
        with Client(tmp_path / "second", port=port) as again:
            again.tell("finalize")
            assert again.line() == ["FINALIZE", "1", "1"]
            again.peer.expect(FA_LOCAL_APP_DONE)
