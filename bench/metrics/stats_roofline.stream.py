"""The statistics pass's share of the HBM roofline over the device's busy
time.

Per call the pass must read each 4-byte token id once, and read and write
the HLL register file (2^b int32) and the CountMin table (depth x 2^l
int32) once (``min_bytes``): quantities of the configuration, not of the
implementation. The share is the least HBM time of all the window's calls
over the device's busy seconds in the traced window. It counts the layer's
work (h1 lookup, hashing, both sketch epilogues), not a named kernel's
events.
"""


def min_bytes(tokens: int, calls: int, hll_b: int, cms_depth: int,
              cms_log2_width: int) -> int:
    """4-byte token ids in; per call the registers and the table read and
    written once."""
    state = 4 * ((1 << hll_b) + cms_depth * (1 << cms_log2_width))
    return 4 * tokens + calls * 2 * state


def read(facts, trace, peaks):
    if trace is None or trace["busy_s"] <= 0 or "cms_depth" not in facts:
        return None
    least = min_bytes(facts["tokens"], facts["calls"], facts["hll_b"],
                      facts["cms_depth"], facts["cms_log2_width"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least / trace["busy_s"]
