"""Share of the window spent probing the band index for candidates.

The total seconds of the program's span path
``dedup.add_batch/dedup.probe`` (``repro.obs``), opened in
``DedupService.add_batch`` around ``_probe_batch`` (the fan-out of one
group-by per band to the band workers and the merge of their candidate
sets), over the window. Only window time counts: set-up calls no
``add_batch``. None where the program has no recorder or no such path.
"""
from bench.metrics._obs import window_share

PATH = "dedup.add_batch/dedup.probe"


def read(facts, trace, peaks):
    return window_share(PATH, facts)
