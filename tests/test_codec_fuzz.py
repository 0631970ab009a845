"""Codec parity fuzz: the compiled TLV codec (native/codec.cpp) vs the
pure-Python twin, byte-identical both directions over randomized frames
of every wire-native shape — including >IOV_MAX-segment frames, 0-byte
and 2 MiB payloads, and the field-97 job id present/absent — plus the
short-write/EINTR resume contract of ``TcpEndpoint._send_iov``.

The C leg skips with a note when the toolchain cannot build the .so
(the runtime degrades to the Python twin the same way)."""

import random

import numpy as np
import pytest

from adlb_tpu.runtime import codec as codec_mod
from adlb_tpu.runtime.codec import (
    ARRAY_FIELDS,
    FIELDS,
    IOV_INLINE_MAX,
    decode_binary_py,
    encodable,
    encode_binary_iov_py,
)
from adlb_tpu.runtime.messages import Msg, Tag, msg

_KIND_I64, _KIND_BYTES, _KIND_LIST, _KIND_F64, _KIND_BLIST, _KIND_FLIST = \
    range(6)

_HAVE_C = codec_mod._load_c_codec()

needs_c = pytest.mark.skipif(
    not _HAVE_C,
    reason="compiled codec unavailable (no toolchain); Python twin "
    "carries the wire — parity legs skipped",
)


def _rand_value(rng: random.Random, kind: int, wild: bool = False):
    if kind == _KIND_I64:
        return rng.choice([
            0, 1, -1, 97, 2**31, -(2**31), 2**62, -(2**62),
            rng.randrange(-(2**40), 2**40),
        ])
    if kind == _KIND_BYTES:
        n = rng.choice([0, 1, 7, IOV_INLINE_MAX - 1, IOV_INLINE_MAX,
                        IOV_INLINE_MAX + 1, 4096, 2 << 20])
        b = rng.randbytes(min(n, 4096)) * max(1, n // 4096)
        b = b[:n]
        if wild and rng.random() < 0.3:
            return bytearray(b) if rng.random() < 0.5 else memoryview(b)
        return b
    if kind == _KIND_LIST:
        n = rng.choice([0, 1, 5, 64, 1500])
        return [rng.randrange(-(2**40), 2**40) for _ in range(n)]
    if kind == _KIND_F64:
        return rng.choice([0.0, -1.5, 3.14159, 1e300, -1e-300,
                           float(rng.randrange(10**6))])
    if kind == _KIND_BLIST:
        n = rng.choice([0, 1, 8, 64])
        return [_rand_value(rng, _KIND_BYTES) if rng.random() < 0.3
                else rng.randbytes(rng.randrange(0, 64))
                for _ in range(n)]
    n = rng.choice([0, 1, 9, 257])
    return [rng.uniform(-1e6, 1e6) for _ in range(n)]


def _rand_frame(rng: random.Random, wild: bool = False) -> Msg:
    tag = rng.choice(list(Tag))
    names = list(FIELDS)
    rng.shuffle(names)
    data = {}
    for name in names[: rng.randrange(0, 12)]:
        _fid, kind = FIELDS[name]
        # None values encode by omission — fuzz that rule too
        data[name] = None if rng.random() < 0.1 else _rand_value(
            rng, kind, wild)
    # the field-97 job id, present/absent, is the service-mode
    # compatibility bit — force both arms to occur often
    if rng.random() < 0.5:
        data["job_id"] = rng.choice([0, 1, 97, 2**31])
    else:
        data.pop("job_id", None)
    # same treatment for the field-98 trace id (unit-lifecycle tracing):
    # omitted-for-unsampled is the trace_sample=0 frame-identity contract
    if rng.random() < 0.5:
        data["trace_id"] = rng.choice(
            [1, (1 << 32) | 1, (255 << 32) | 0xFFFFFFFF, 2**62]
        )
    else:
        data.pop("trace_id", None)
    return Msg(tag=tag, src=rng.randrange(-1, 1 << 20), data=data)


def _flat(parts) -> bytes:
    return b"".join(bytes(p) for p in parts)


def _same(a: Msg, b: Msg) -> bool:
    """Msg equality that also holds for the fields a decoder hands over
    as int64 arrays (codec.ARRAY_FIELDS): same type, dtype and values."""
    if (a.tag, a.src, list(a.data)) != (b.tag, b.src, list(b.data)):
        return False
    for name, va in a.data.items():
        vb = b.data[name]
        if name in ARRAY_FIELDS:
            if not (isinstance(va, np.ndarray) and isinstance(vb, np.ndarray)
                    and va.dtype == vb.dtype == np.int64
                    and np.array_equal(va, vb)):
                return False
        elif type(va) is not type(vb) or va != vb:
            return False
    return True


@needs_c
def test_parity_fuzz_roundtrip():
    """1,000 randomized frames: identical bytes out of both encoders,
    identical Msg out of both decoders (cross-decoded, so each decoder
    is also proven against the OTHER encoder's bytes)."""
    rng = random.Random(0xAD1B)
    for i in range(1000):
        m = _rand_frame(rng, wild=True)
        py = _flat(encode_binary_iov_py(m))
        c = _flat(codec_mod._c_encode_iov(m))
        assert py == c, f"frame {i} ({m.tag.name}): encode bytes differ"
        d_py = decode_binary_py(c)
        d_c = codec_mod._c_decode(py)
        assert _same(d_py, d_c), f"frame {i} ({m.tag.name}): decode differs"
        assert d_py.tag is m.tag and d_py.src == m.src
        # an array field carries the values that went in
        for name in ARRAY_FIELDS & set(d_py.data):
            assert d_py.data[name].tolist() == list(m.data[name])


@needs_c
def test_parity_known_corpus():
    """The deterministic edge corpus: 0-byte and 2 MiB payloads, the
    inline threshold's both sides, frozenset req_types, bools, empty
    frames, job id on and off."""
    big = b"\xa5" * (2 << 20)
    corpus = [
        msg(Tag.FA_PUT, 0, payload=b"", work_type=1, prio=0,
            target_rank=-1, answer_rank=-1, common_len=0,
            common_server=-1, common_seqno=-1),
        msg(Tag.FA_PUT, 3, payload=big, work_type=2, prio=-7,
            target_rank=-1, answer_rank=0),
        msg(Tag.FA_PUT, 1, payload=b"x" * (IOV_INLINE_MAX - 1)),
        msg(Tag.FA_PUT, 1, payload=b"x" * IOV_INLINE_MAX),
        msg(Tag.FA_PUT, 1, payload=b"x", job_id=7),
        msg(Tag.FA_PUT, 1, payload=b"x"),
        # field-98 trace id: the sampled-put arm and the bare twin whose
        # bytes must not change (trace_sample=0 frame identity)
        msg(Tag.FA_PUT, 1, payload=b"x", put_id=3,
            trace_id=(2 << 32) | 9),
        msg(Tag.FA_PUT, 1, payload=b"x", put_id=3),
        msg(Tag.FA_RESERVE, 0, req_types=frozenset({1, 2, 9}),
            hang=True, rqseqno=42),
        msg(Tag.FA_RESERVE, 0, req_types=None, hang=False, rqseqno=1),
        msg(Tag.TA_RESERVE_RESP, 6, rc=1, payloads=[big[:4096], b"", b"z"],
            work_types=[1, 2, 3], prios=[0, -1, 5],
            answer_ranks=[-1, 0, 2], times_on_q=[0.0, 0.5, 1e9]),
        msg(Tag.SS_STATE_DELTA, 4, seqnos=list(range(1000)),
            work_types=[1] * 1000, prios=[0] * 1000,
            work_lens=[64] * 1000, nbytes=64000),
        msg(Tag.FA_LOCAL_APP_DONE, 9),
        msg(Tag.TA_INFO_GET_RESP, 6, rc=1, value=3.5),
        # a native daemon's snapshot at its cap: the task table is
        # handed over as one int64 array, the requesters as a list
        msg(Tag.SS_STATE, 5, tasks_flat=list(range(-4, 4 * 2048 - 4)),
            reqs_flat=[7, 1, -1, 8, 2, 2, 1, 3], nbytes=1 << 20,
            consumers=4, mig_acks=[5, 17]),
        msg(Tag.SS_STATE, 5, tasks_flat=[], reqs_flat=[]),
    ]
    for m in corpus:
        assert encodable(m), m.tag
        py = _flat(encode_binary_iov_py(m))
        c = _flat(codec_mod._c_encode_iov(m))
        assert py == c, m.tag
        assert _same(decode_binary_py(c), codec_mod._c_decode(py))


@needs_c
def test_parity_beyond_iov_max_segments():
    """A batch-fetch frame whose payload list alone exceeds IOV_MAX
    segments (1024): both encoders must agree byte-for-byte and the
    part count must exceed the kernel's gather cap (the _send_iov
    chunking path's precondition)."""
    m = msg(
        Tag.TA_RESERVE_RESP, 6, rc=1,
        payloads=[b"P" * IOV_INLINE_MAX] * 1100,
        work_types=[1] * 1100, prios=[0] * 1100,
        answer_ranks=[-1] * 1100,
    )
    py_parts = encode_binary_iov_py(m)
    c_parts = codec_mod._c_encode_iov(m)
    assert len(py_parts) > 1024 and len(c_parts) > 1024
    assert _flat(py_parts) == _flat(c_parts)
    assert decode_binary_py(_flat(c_parts)) == codec_mod._c_decode(
        _flat(py_parts))


@needs_c
def test_c_codec_unknown_field_skipped_and_errors_match():
    """Unknown wire fields are skipped by both decoders; oversized list
    fields raise on both encoders."""
    import struct

    body = bytearray(_flat(encode_binary_iov_py(
        msg(Tag.TA_PUT_RESP, 5, rc=1))))
    # append an unknown field id 200, kind i64, bump nfields
    body += struct.pack("<BBq", 200, 0, 12345)
    nf = struct.unpack_from("<H", body, 7)[0]
    struct.pack_into("<H", body, 7, nf + 1)
    d_py = decode_binary_py(bytes(body))
    d_c = codec_mod._c_decode(bytes(body))
    assert d_py == d_c and d_py.data == {"rc": 1}

    too_long = msg(Tag.SS_STATE_DELTA, 0, seqnos=list(range(70000)))
    with pytest.raises(ValueError):
        encode_binary_iov_py(too_long)
    with pytest.raises(ValueError):
        codec_mod._c_encode_iov(too_long)


def test_select_codec_roundtrip():
    """select_codec swaps the active implementation and the dispatchers
    follow; 'py' always works, 'c' works iff the .so built."""
    before = codec_mod.active_codec()
    try:
        assert codec_mod.select_codec("py") == "py"
        m = msg(Tag.TA_PUT_RESP, 5, rc=1)
        assert codec_mod.decode_binary(
            codec_mod.encode_binary(m)) == decode_binary_py(
            _flat(encode_binary_iov_py(m)))
        if _HAVE_C:
            assert codec_mod.select_codec("c") == "c"
            assert codec_mod.decode_binary(
                codec_mod.encode_binary(m)).data == {"rc": 1}
        else:
            with pytest.raises(RuntimeError):
                codec_mod.select_codec("c")
        assert codec_mod.select_codec("auto") in ("c", "py")
    finally:
        codec_mod.select_codec("auto" if before == "c" else "py")


# ------------------------------------------------- _send_iov resume contract


class _ShortWriteSock:
    """A socket double whose sendmsg accepts a random prefix of the
    gather (including 0) and raises EINTR at scripted points; sendall
    records the no-sendmsg fallback."""

    def __init__(self, rng: random.Random, eintr_every: int = 7) -> None:
        self.rng = rng
        self.got = bytearray()
        self.calls = 0
        self.eintr_every = eintr_every

    def sendmsg(self, parts):
        self.calls += 1
        if self.eintr_every and self.calls % self.eintr_every == 0:
            raise InterruptedError(4, "scripted EINTR")
        total = sum(len(p) for p in parts)
        n = self.rng.randrange(0, total + 1) if total else 0
        taken = 0
        for p in parts:
            if taken >= n:
                break
            b = bytes(p)[: n - taken]
            self.got += b
            taken += len(b)
        return n


def test_send_iov_short_write_eintr_resume():
    """Random short writes + scripted EINTRs: the receiver-side bytes
    must equal the exact concatenation of the gather, for frames from
    tiny to >IOV_MAX segments."""
    from adlb_tpu.runtime.transport_tcp import TcpEndpoint

    rng = random.Random(7)
    for _case in range(40):
        nparts = rng.choice([1, 2, 5, 30, 1100])
        parts = [rng.randbytes(rng.randrange(0, 600)) for _ in range(nparts)]
        want = b"".join(parts)
        sock = _ShortWriteSock(random.Random(_case), eintr_every=5)
        TcpEndpoint._send_iov(sock, list(parts))
        assert bytes(sock.got) == want, f"case {_case}: stream corrupted"
