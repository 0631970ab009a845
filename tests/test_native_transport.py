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
family is chosen from the address map and the peer's answer, and everything
above the socket is one code path. So the invariants are held once per
family (the ``family`` fixture): with ``unix`` the test's own ranks listen
on their port's name and connect to the native rank's, as a native rank
would; with ``tcp`` they have no such listener, as a Python rank has none,
and the native rank is told they live on another host. The last tests hold
the choice itself: who is tried over which family, and whose name is whose.

No test times the host. Every wait has a limit far above what the step
needs, and running into it is the failure (a hang), not a slow pass.
"""

import collections
import json
import os
import selectors
import shutil
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


@pytest.fixture(params=["tcp", "unix"])
def family(request):
    return request.param


def unix_name(port):
    """The abstract name of ``port`` (hostsock.hpp)."""
    return b"\0adlb_tpu.%d" % port


def bound_names():
    """The abstract Unix names bound in this network namespace."""
    with open("/proc/net/unix") as f:
        return {line.split()[-1] for line in f if "@adlb_tpu." in line}


def peer_host(family):
    """Where a native rank is told the test's ranks live."""
    return "127.0.0.1" if family == "unix" else OTHER_HOST

ADLB_SUCCESS = 1
ADLB_PUT_REJECTED = -999999996

# wire tags and field ids (serverd.cpp / libadlb.cpp / codec.py)
FA_PUT, FA_RESERVE, FA_LOCAL_APP_DONE = 1001, 1007, 1012
TA_PUT_RESP, TA_ABORT, AM_APP = 1020, 1046, 1047
FA_INFO_NUM, TA_INFO_NUM_RESP = 1037, 1043
SS_QMSTAT, SS_EXHAUST_CHK_1, SS_PLAN_MIGRATE = 1101, 1111, 1119
F_PAYLOAD, F_WORK_TYPE, F_PRIO, F_TARGET_RANK, F_ANSWER_RANK = 1, 2, 3, 4, 5
F_COMMON_LEN, F_COMMON_SERVER, F_COMMON_SEQNO, F_RC, F_HINT = 6, 7, 8, 9, 10
F_REQ_TYPES, F_HANG, F_RQSEQNO, F_COUNT, F_NBYTES, F_CODE = 11, 12, 13, 17, 18, 20
F_APPTAG, F_DEST, F_SEQNOS, F_PUT_ID, F_MIG_ID = 26, 47, 48, 58, 77


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
        if family == "unix":
            self.usock = socket.socket(socket.AF_UNIX)
            self.usock.bind(unix_name(self.port))
            self.usock.listen(64)
            self.sel.register(self.usock, selectors.EVENT_READ, "unix")
        self.accepted = collections.Counter()
        self.bufs = {}
        self.frames = collections.deque()
        self.reading = True  # False: accept, but leave the bytes unread

    def pump(self, timeout):
        for key, _ in self.sel.select(timeout):
            s = key.fileobj
            if s is self.lsock or s is self.usock:
                c, _ = s.accept()
                self.accepted[key.data] += 1
                self.bufs[c] = bytearray()
                if self.reading:
                    self.sel.register(c, selectors.EVENT_READ)
                continue
            data = s.recv(1 << 20)
            if not data:
                self.sel.unregister(s)
                s.close()
                del self.bufs[s]
                continue
            buf = self.bufs[s]
            buf += data
            while len(buf) >= 4:
                (n,) = struct.unpack_from("<I", buf, 0)
                if len(buf) < 4 + n:
                    break
                self.frames.append(untlv(bytes(buf[4:4 + n])))
                del buf[:4 + n]

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
    """A raw connection to the rank that listens at ``port``."""
    if family == "unix":
        s = socket.socket(socket.AF_UNIX)
        s.settimeout(LIMIT_S)
        s.connect(unix_name(port))
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
                 family="unix", peer=None, own_host="127.0.0.1"):
        self.world = WorldSpec(nranks=n_apps + nservers, nservers=nservers,
                               types=tuple(types))
        cfg = cfg or Config(server_impl="native")
        self.peer = peer or Peer(family=family)
        self.procs = {r: daemon_mod.spawn_daemon(self.world, cfg, r)
                      for r in daemon_ranks}
        self.ports = {r: daemon_mod.read_hello(p, r)
                      for r, p in self.procs.items()}
        for me, p in self.procs.items():
            daemon_mod.send_addrs(p, {
                r: (own_host if r == me else peer_host(family),
                    self.ports.get(r, self.peer.port))
                for r in range(self.world.nranks)})

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
        assert set(w.peer.accepted) == {family}  # the answers' family too


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
        buf = ctypes.create_string_buffer(b"p" * int(args[0]), int(args[0]))
        print("PUT", lib.ADLB_Put(buf, int(args[0]), -1, -1, 1, 0), flush=True)
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

    def __init__(self, tmp_path, n_apps=1, family="unix", port=None):
        from adlb_tpu.native.capi import build_libadlb

        self.port = port or local_addr_map(1)[0][1]
        self.peer = Peer(family=family)
        rv = tmp_path / "world.adlb"
        rv.write_text(f"0 127.0.0.1 {self.port}\n" + "".join(
            f"{r} {peer_host(family)} {self.peer.port}\n"
            for r in range(1, n_apps + 1)))
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

    def conns(self):
        """The connections the finalized client counted, by family."""
        assert self.proc.wait(LIMIT_S) == 0
        (ev,) = [e for e in json.loads(self.trace.read_text())
                 if e["name"] == "adlb:conns"]
        return ev["args"]

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
        assert c.conns() == {"conns_unix": 2 * (family == "unix"),
                             "conns_tcp": 2 * (family == "tcp")}
        assert dict(c.peer.accepted) == {family: 1}


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


# ---- which family, and whose name -----------------------------------------

def _examples():
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples")


@pytest.mark.parametrize("balancer", ["steal", "tpu"])
def test_a_one_host_native_world_is_unix_between_its_native_ranks(
        tmp_path, balancer):
    """C clients and C++ daemons of one host: every connection between two
    of them is a Unix-domain one, on both ends' counters. TCP appears only
    toward a Python peer, and the one such peer a native world can have is
    the planner's sidecar (``balancer="tpu"``): each daemon opens one
    connection to it and accepts at most one from it."""
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
    for rank in range(3):
        events = json.loads((tmp_path / f"t.{rank}.trace.json").read_text())
        (ev,) = [e for e in events if e["name"] == "adlb:conns"]
        assert ev["args"]["conns_tcp"] == 0
        assert ev["args"]["conns_unix"] >= 2  # opened one, accepted one


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
