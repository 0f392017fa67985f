"""Host milliseconds inside ``PagedRealtimeEngine.submit_turn`` (where a
turn's offloaded pages are settled) per turn admitted in the window,
counting calls that the pool turned away."""


def read(run):
    calls = [s for s in run.submits if run.in_window(s[0])]
    ok = sum(1 for s in calls if s[4])
    if not ok:
        return None
    return sum(s[1] - s[0] for s in calls) / ok * 1e3
