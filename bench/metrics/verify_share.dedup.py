"""Share of the window spent in the per-document verify loop.

The total seconds of the program's span path
``dedup.add_batch/dedup.verify`` (``repro.obs``), opened in
``DedupService.add_batch`` around the loop that verifies each document
against its candidates, first-wins, and gathers the kept documents' band
inserts, over the window. Only window time counts: set-up calls no
``add_batch``. None where the program has no recorder or no such path.
"""
from bench.metrics._obs import window_share

PATH = "dedup.add_batch/dedup.verify"


def read(facts, trace, peaks):
    return window_share(PATH, facts)
