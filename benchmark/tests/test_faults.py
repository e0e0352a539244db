"""The rest of a run with the timed path broken underneath (the harness's
look for a chip skipped): ``correct`` has to come out false for each fault
a cell can have."""

from __future__ import annotations

import pytest

from conftest import BIG_SEED, SMALL, SPEC

import harness


def _flip(decision):
    from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

    return pb.DENY if decision == pb.PERMIT else pb.PERMIT


def answer_altered(monkeypatch):
    """A decision altered where it is produced (the native wire path's
    first served row of each batch, and every answer of the other
    paths)."""
    from access_control_srv_tpu.srv import transport_grpc as tg

    decode, to_pb = tg.decode_native_rows, tg.response_to_pb

    def bad_decode(messages, out):
        rows = decode(messages, out)
        unserved = set(rows.fallback) | set(rows.fallback_rows)
        served = [b for b in range(len(rows.codes)) if b not in unserved]
        if served:
            # a frame-table code is decision * 2 + cacheable: the next
            # decision, the same cacheable bit
            n_codes = len(tg.frame_table())
            rows.codes[served[0]] = (rows.codes[served[0]] + 2) % n_codes
        return rows

    def bad_to_pb(response):
        msg = to_pb(response)
        msg.decision = _flip(msg.decision)
        return msg

    monkeypatch.setattr(tg, "decode_native_rows", bad_decode)
    monkeypatch.setattr(tg, "response_to_pb", bad_to_pb)


def half_left_out(monkeypatch):
    """Half of each batch's answers left out."""
    from access_control_srv_tpu.srv import transport_grpc as tg

    serialize = tg.serialize_batch_response
    monkeypatch.setattr(tg, "serialize_batch_response",
                        lambda rs: serialize(rs[:len(rs) // 2]))


FAULTS = [(cell, fault) for cell in sorted(SMALL)
          for fault in (answer_altered, half_left_out)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_is_not_correct(monkeypatch, cell, fault):
    fault(monkeypatch)
    out = harness.run(cell, BIG_SEED + 7, 2.5, False, allow_cpu=True,
                      scale=SMALL[cell], spec=SPEC)
    assert out["correct"] is False, out["checks"]
