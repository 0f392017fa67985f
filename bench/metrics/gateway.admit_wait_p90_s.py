"""90th percentile, over the turns sent in the window, of the wait from
the client's TurnRequest to the gateway admitting the turn to a slot
(a wrapper on ``RealtimeGateway._record_admit``; host monotonic clock).
The k-th admission of a session is its k-th turn."""
from bench.stats import percentile


def read(run):
    by_sid = {}
    for t in sorted(run.client.turns, key=lambda t: t.t_send):
        by_sid.setdefault(t.session_id, []).append(t)
    waits = []
    for sid, turns in by_sid.items():
        for t, at in zip(turns, run.admits.get(sid, [])):
            if run.in_window(t.t_send):
                waits.append(at - t.t_send)
    return percentile(waits, 90)
