"""The program's own turn stamps (``TurnRecord.wall_*``) against the
benchmark's client and admission wrapper, in a whole tiny run on the
CPU: both read the host's monotonic clock, so they must agree."""
import pytest

from bench import run
from bench.tests import tiny


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root = tiny.make(tmp_path_factory.mktemp("tinycell"))
    gateways = []
    out = run.execute(tiny.CELL, 2**31 + 141, 4.0, False, root=root,
                      require_tpu=False, fault=gateways.append,
                      check_outputs=False)
    r = out["run"]
    recs = {}
    for rec in gateways[0].metrics().turns:
        recs.setdefault(rec.session_id, []).append(rec)
    pairs = []
    for t in r.client.turns:
        if r.in_window(t.t_send):
            mine = sorted(recs[t.session_id], key=lambda x: x.turn_index)
            k = sum(1 for u in r.client.turns
                    if u.session_id == t.session_id and u.t_send < t.t_send)
            pairs.append((t, mine[k], r.admits[t.session_id][k]))
    assert pairs
    return pairs


def test_stamps_are_ordered_along_the_turn(served):
    for _, rec, _ in served:
        stamps = [rec.wall_speech_end, rec.wall_request, rec.wall_handled,
                  rec.wall_admitted, rec.wall_first_packet]
        assert None not in stamps
        assert stamps == sorted(stamps)


def test_the_wall_ttfp_lies_within_the_clients(served):
    for t, rec, _ in served:
        assert t.t_speech_end <= rec.wall_speech_end
        assert rec.wall_first_packet <= t.t_first
        assert 0 < rec.ttfp_wall_s <= t.t_first - t.t_speech_end


def test_the_admission_wait_matches_the_wrappers(served):
    for t, rec, admitted in served:
        program = rec.wall_admitted - rec.wall_request
        wrapper = admitted - t.t_send
        assert program == pytest.approx(wrapper, abs=1e-3)
