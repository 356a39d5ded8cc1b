"""Share of the window the host waits on signing's device-to-host fetches.

The total seconds of the program's span path
``dedup.add_batch/dedup.sign/dedup.sign.fetch`` (``repro.obs``), opened in
``MinHashDeduper.signature_many`` around each 64-document group's blocking
``np.asarray(finalize(...))``, over the window. It holds the wait for the
group's queued device work as well as the copy. Set-up signs without
``add_batch``, so only window time counts. None where the program has no
recorder or no such path.
"""
from bench.metrics._obs import window_share

PATH = "dedup.add_batch/dedup.sign/dedup.sign.fetch"


def read(facts, trace, peaks):
    return window_share(PATH, facts)
