"""Binary wire codec round-trips (the native-client protocol)."""

import pickle

import pytest

from adlb_tpu.runtime.codec import (
    FIELDS,
    WIRE_TAG,
    decode_binary,
    encodable,
    encode_binary,
)
from adlb_tpu.runtime.messages import Msg, Tag, msg


CASES = [
    msg(Tag.FA_PUT, 3, payload=b"\x00\xffhello", work_type=2, prio=-7,
        target_rank=-1, answer_rank=0, common_len=0, common_server=-1,
        common_seqno=-1),
    msg(Tag.TA_PUT_RESP, 5, rc=1, hint=-1),
    msg(Tag.FA_RESERVE, 0, req_types=[1, 2, 9], hang=True, rqseqno=42),
    msg(Tag.FA_RESERVE, 0, req_types=None, hang=False, rqseqno=1),
    msg(Tag.TA_RESERVE_RESP, 6, rc=1, work_type=1, prio=3,
        handle=[7, 5, 0, -1, -1], work_len=12, answer_rank=-1),
    msg(Tag.TA_GET_RESERVED_RESP, 6, rc=1, payload=b"", time_on_q=0.125),
    msg(Tag.FA_INFO_GET, 2, key=7),
    msg(Tag.TA_INFO_GET_RESP, 6, rc=1, value=3.5),
    msg(Tag.TA_ABORT, 6, code=-2),
    msg(Tag.FA_LOCAL_APP_DONE, 1),
    # batched put delta (round 4): parallel per-unit lists so streaming
    # producers reach the balancer within one rate-limit gap
    msg(Tag.SS_STATE_DELTA, 4, seqnos=[11, 12, 13], work_types=[1, 1, 2],
        prios=[0, -3, 9], work_lens=[8, 0, 4096], nbytes=4104),
]


@pytest.mark.parametrize("m", CASES, ids=lambda m: m.tag.name)
def test_roundtrip(m):
    assert encodable(m)
    body = encode_binary(m)
    assert body[0] == 0x01
    out = decode_binary(body)
    assert out.tag is m.tag
    assert out.src == m.src
    expect = {k: v for k, v in m.data.items() if v is not None}
    assert out.data == expect


def test_pickle_discriminator():
    """Pickled frames must never look like binary frames."""
    for m in CASES:
        body = pickle.dumps(m, protocol=pickle.HIGHEST_PROTOCOL)
        assert body[0] == 0x80


def test_wire_ids_total_and_unique():
    assert set(WIRE_TAG) == set(Tag), "every tag needs a wire id"
    assert len(set(WIRE_TAG.values())) == len(WIRE_TAG)
    ids = [fid for fid, _ in FIELDS.values()]
    assert len(set(ids)) == len(ids)


def test_pickled_abort_carries_module_path():
    """The C client (libadlb.cpp parse_frames) honors a pickled frame as
    the TA_ABORT fan-out only when the body contains the pickled Msg's
    module path — this pins the invariant that heuristic depends on, so
    a module rename fails here instead of silently breaking abort
    delivery to native clients that a Python server hasn't learned are
    binary peers."""
    body = pickle.dumps(
        msg(Tag.TA_ABORT, 4, code=-2), protocol=pickle.HIGHEST_PROTOCOL
    )
    assert body[0] == 0x80
    assert b"adlb_tpu" in body
