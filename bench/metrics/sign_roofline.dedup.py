"""Signing's share of the HBM roofline over the device's busy time.

The least that signing must move is each token id read once and each
signature written once (``min_bytes``); at the chip's HBM bandwidth that
takes ``min_bytes / hbm_bytes_per_s``. The share is that time over the
device's busy seconds in the traced window: in the dedup cells nearly all
device work is signing. It counts the layer's work, not a named kernel's
events, so a later signer of another shape is read on the same yardstick.
"""


def min_bytes(tokens: int, docs: int, k: int) -> int:
    """4-byte token ids in, k 4-byte lanes out per document."""
    return 4 * tokens + 4 * k * docs


def read(facts, trace, peaks):
    if trace is None or trace["busy_s"] <= 0 or "k" not in facts:
        return None
    least = min_bytes(facts["tokens"], facts["docs"], facts["k"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least / trace["busy_s"]
