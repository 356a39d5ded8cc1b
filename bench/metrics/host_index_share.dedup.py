"""Share of the window the host spends in ``add_batch`` outside signing.

That is the band probes, the verify loop and the replicated inserts of the
service's LSH index (``data/service.py``), from the harness's own host
spans around ``add_batch`` and ``MinHashDeduper.signature_many``.
"""


def read(facts, trace, peaks):
    if "add_batch_s" not in facts:
        return None
    return 100.0 * (facts["add_batch_s"] - facts["sign_s"]) / facts["window_s"]
