"""Share of the window the host spends packing token tiles for signing.

The total seconds of the program's span path
``dedup.add_batch/dedup.sign/dedup.sign.pack`` (``repro.obs``), opened in
``MinHashDeduper.signature_many`` around the packing of each chunk block
and closed before the block goes to the device, over the window. Set-up
signs without ``add_batch``, so only window time counts. None where the
program has no recorder or no such path.
"""
from bench.metrics._obs import window_share

PATH = "dedup.add_batch/dedup.sign/dedup.sign.pack"


def read(facts, trace, peaks):
    return window_share(PATH, facts)
