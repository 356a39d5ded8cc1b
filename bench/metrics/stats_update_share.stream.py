"""Share of the window the host spends inside the statistics front end.

The total seconds of the program's span ``stats.update_stream``
(``repro.obs``), opened in ``NgramStats.update_stream_many`` around the h1
lookup's dispatch, the stream executor's checks and dispatch and the token
count, over the window; the harness waits for the device outside it. Less
the span's total at the window's start (``stats_update_s0``: set-up's warm
call). None where the program has no recorder or no such span.
"""
from bench.metrics._obs import span

PATH = "stats.update_stream"


def read(facts, trace, peaks):
    s = span(PATH)
    if s is None or not facts.get("window_s"):
        return None
    return (100.0 * (s["total_s"] - facts.get("stats_update_s0", 0.0))
            / facts["window_s"])
