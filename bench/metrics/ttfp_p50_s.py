"""Median audio time-to-first-packet over every turn the client sent in
the window, from its SpeechEnd to its first AudioChunk on the client's
monotonic clock; a turn with no packet by the grace deadline counts at
the deadline."""
from bench.stats import percentile


def read(run):
    return percentile(run.ttfps(), 50)
