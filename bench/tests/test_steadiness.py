"""What the harness logs about a window's steadiness: Python's garbage
collections in the window, and the decode driver's step-time quantiles.
Log lines and facts only; nothing is compared."""
import gc
import json
import time

import jax
import numpy as np
import pytest
from bench import run
from bench.spans import Spans
from bench.tests.test_correctness import DECODE, SEED, _small


def test_gc_in_window_counts_a_forced_full_collection(monkeypatch):
    c = _small(DECODE)
    window = c.driver().Driver.window

    def collecting(self, seconds, spans):
        gc.collect()
        return window(self, seconds, spans)

    monkeypatch.setattr(c.driver().Driver, "window", collecting)
    result, log = run.measure(c, SEED, 0.3, False, jax.devices()[:1],
                              time.perf_counter())
    line = next(x for x in log if x.startswith("gc_in_window="))
    got = json.loads(line.split("=", 1)[1])
    assert got["gen2"] >= 1, got
    assert got["max_ms"] > 0 and got["total_ms"] >= got["max_ms"]
    assert result["correct"], log


def test_gc_count_sees_only_its_own_span():
    gc.collect()
    with run.GcCount() as n:
        gc.collect(1)
        gc.collect(2)
    gc.collect()
    assert n.summary()["gen1"] == 1 and n.summary()["gen2"] == 1
    assert n not in gc.callbacks


def test_step_quantiles_see_one_planted_slow_step():
    c = _small(DECODE)
    d = c.driver().Driver(c, SEED)
    d.setup()
    warm = d.tokens[0]

    def step(t):
        time.sleep(0.4 if t == 4 else 0.02)
        return warm

    d._step = step
    facts = d.window(0.8, Spans())
    assert facts["steps_over_2x_median"] == 1, facts
    assert facts["step_ms_max"] >= 400 > 4 * facts["step_ms_p50"]
    assert facts["step_ms_p50"] <= facts["step_ms_p99"] <= facts["step_ms_max"]
    # the slow step is the window's 4th, among the first 8
    assert facts["step_ms_first8_mean"] > 2 * facts["step_ms_p50"]
    d.release()


def test_step_quantiles_of_known_times():
    from bench.drivers.session_pool import step_quantiles
    q = step_quantiles(np.array([10.0] * 99 + [50.0]))
    assert q["step_ms_p50"] == 10.0 and q["step_ms_max"] == 50.0
    assert q["steps_over_2x_median"] == 1
    assert q["step_ms_first8_mean"] == 10.0



def test_gc_count_unregisters_when_the_window_raises():
    with pytest.raises(RuntimeError):
        with run.GcCount() as n:
            raise RuntimeError("window failed")
    assert n not in gc.callbacks
