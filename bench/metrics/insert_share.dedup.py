"""Share of the window spent inserting kept documents into the band index.

The total seconds of the program's span path
``dedup.add_batch/dedup.insert`` (``repro.obs``), opened in
``DedupService.add_batch`` around ``_insert_bands`` (every band's inserts
sent to each of its replicas), over the window. Only window time counts:
set-up calls no ``add_batch``. None where the program has no recorder or
no such path.
"""
from bench.metrics._obs import window_share

PATH = "dedup.add_batch/dedup.insert"


def read(facts, trace, peaks):
    return window_share(PATH, facts)
