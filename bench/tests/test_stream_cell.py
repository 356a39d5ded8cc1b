"""The statistics cell at a size a test run can hold: 16 rows of 256
tokens, a 2^12 vocabulary and CountMin columns.

A sound run is correct; the control (the reference at 32-bit lanes) is
caught; and a run with its timed path broken underneath is not correct,
once for each fault the cell can have: a call that leaves the state
unchanged, half the rows left out, and one cell altered where the state
is read out. The cell's three readers return None where they have nothing
to read, and known answers where they do.
"""
import sys
import time

import jax
import numpy as np
import pytest

from bench import cell as cells
from bench import run
from bench.spans import Spans

SEED = 2 ** 31 + 4321
CELL = "stream.zipf-stats"
READERS = ("stats_roofline.stream", "idle_share.stream",
           "stats_update_share.stream")


def _small():
    c = cells.resolve(CELL)
    c.traffic.update(rows=16, row_len=256, batch_docs=64, pool_batches=2)
    c.traffic["length"] = dict(c.traffic["length"], max=1500)
    c.config["stats"] = dict(c.config["stats"], vocab=1 << 12,
                             cms_log2_width=12)
    return c


def _run(seconds=0.5):
    return run.measure(_small(), SEED, seconds, False, jax.devices()[:1],
                       time.perf_counter())


def test_sound_run_is_correct():
    result, log = _run()
    assert result["correct"], log
    assert set(result["checks"]) == {"register_mismatches",
                                     "cell_mismatches", "token_count_error"}
    assert result["attempted"] > 1, "the window holds several calls"


def test_packed_rows_continue_across_calls():
    c = _small()
    ring, pool = cells.load_module(
        cells.BENCH / "drivers" / "ngram_stats.py").packed_ring(
            c.traffic, 1 << 12, SEED)
    assert ring.shape == (8, 16, 256)
    stream = np.concatenate(list(ring.swapaxes(0, 1)), axis=None)
    doc0 = pool.tokens[: pool.offsets[1]]
    assert (stream[: len(doc0)] == doc0).all()
    assert stream[len(doc0)] == (1 << 12) - 1            # one EOS after it


def test_control_fails():
    c = _small()
    d = c.driver().Driver(c, SEED)
    d.setup()
    d.window(0.3, Spans())
    d.release()
    numbers = d.control()
    assert any(v > d.ref.LIMITS[k] for k, v in numbers.items()), numbers


def _fault(monkeypatch, fault):
    from repro.data.stats import NgramStats
    update = NgramStats.update_stream_many
    finalize = NgramStats.finalize_stream
    if fault == "state_unchanged":
        calls = []

        def once_unchanged(self, sstate, tokens, lengths=None):
            calls.append(1)
            # the third call (the window's second) folds nothing
            if len(calls) == 3:
                return sstate
            return update(self, sstate, tokens, lengths)
        monkeypatch.setattr(NgramStats, "update_stream_many", once_unchanged)
    elif fault == "half_batch":
        def half(self, sstate, tokens, lengths=None):
            return update(self, sstate, tokens[:, : tokens.shape[1] // 2])
        monkeypatch.setattr(NgramStats, "update_stream_many", half)
    elif fault == "answer_altered":
        def altered(self, sstate):
            out = finalize(self, sstate)
            return dict(out, cms=out["cms"].at[1, 3].add(1))
        monkeypatch.setattr(NgramStats, "finalize_stream", altered)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    _fault(monkeypatch, fault)
    result, log = _run()
    assert not result["correct"], log


def _reader(name):
    return cells.load_module(cells.BENCH / "metrics" / f"{name}.py")


FACTS = {"window_s": 50.0, "tokens": 100 * 1536 * 2048, "calls": 100,
         "hll_b": 14, "cms_depth": 4, "cms_log2_width": 20,
         "stats_update_s0": 0.25}
TRACE = {"busy_s": 45.0, "window_s": 50.0}
PEAKS = {"hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("name", READERS)
def test_reader_with_nothing_to_read(name, monkeypatch):
    """No trace and a program without ``repro.obs``: every reader is None."""
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _reader(name).read(FACTS, None, PEAKS) is None


def test_readers_answer(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "totals", lambda: {"spans": {
        "stats.update_stream": {"count": 101, "total_s": 1.25,
                                "self_s": 1.25}}, "counters": {}})
    state = 4 * ((1 << 14) + 4 * (1 << 20))
    least = (4 * FACTS["tokens"] + 100 * 2 * state) / 819e9
    assert _reader("stats_roofline.stream").read(
        FACTS, TRACE, PEAKS) == pytest.approx(100 * least / 45.0)
    assert _reader("idle_share.stream").read(
        FACTS, TRACE, PEAKS) == pytest.approx(10.0)
    assert _reader("stats_update_share.stream").read(
        FACTS, None, PEAKS) == pytest.approx(100 * 1.0 / 50.0)
    monkeypatch.setattr(obs, "totals", lambda: {"spans": {}, "counters": {}})
    assert _reader("stats_update_share.stream").read(FACTS, None,
                                                     PEAKS) is None
