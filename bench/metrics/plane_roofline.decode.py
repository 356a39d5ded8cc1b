"""The decode plane's share of the HBM roofline over the device's busy time.

Per step the plane must read the (sessions, vocab) float32 logits once and
write the tokens and the session carry once (``min_bytes``). The share is
the least HBM time of all the window's steps over the device's busy seconds
in the traced window. It counts the plane's work (probes, masking,
sampling, advance), not a named kernel's events.
"""


def min_bytes(sessions: int, vocab: int, carry_bytes: int) -> int:
    """float32 logits in; int32 tokens and the carry out, per step."""
    return 4 * sessions * vocab + 4 * sessions + carry_bytes


def read(facts, trace, peaks):
    if trace is None or trace["busy_s"] <= 0 or "steps" not in facts:
        return None
    least = facts["steps"] * min_bytes(
        facts["sessions"], facts["vocab"], facts["carry_bytes"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least / trace["busy_s"]
