"""Blocking device-to-host fetches of signing per million tokens.

The count of the program's span path
``dedup.add_batch/dedup.sign/dedup.sign.fetch`` (``repro.obs``): one per
document group that ``MinHashDeduper.signature_many`` signs, over the
window's tokens. Set-up signs without ``add_batch``, so only the window's
fetches count. None where the program has no recorder or no such path.
"""
from bench.metrics._obs import span

PATH = "dedup.add_batch/dedup.sign/dedup.sign.fetch"


def read(facts, trace, peaks):
    s = span(PATH)
    if s is None or not facts.get("tokens"):
        return None
    return s["count"] / (facts["tokens"] / 1e6)
