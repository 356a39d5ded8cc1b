"""The comparison that decides ``correct``, at a size a test run can hold.

Each case drives the rest of a run (set-up, window, check) without the
harness's look for a chip. A sound run is correct; the control (the
reference in a lower precision, or with half its probes) is not; and a run
with its timed path broken underneath is not, once for each fault the
cells can have: a step that leaves its state unchanged, half of the batch
left out, and an answer altered where it is produced. (One-chip cells have
no exchange between chips to leave out.)
"""
import time

import jax
import numpy as np
import pytest

from bench import cell as cells
from bench import run

SEED = 2 ** 31 + 1234


def _small(workload):
    c = cells.resolve(workload)
    if c.traffic["kind"] == "documents":
        c.traffic.update(batch_docs=32, pool_batches=2)
        c.traffic["length"] = dict(c.traffic["length"], min=64, max=1500,
                                   median=min(c.traffic["length"]["median"],
                                              300))
        c.config["dedup"] = dict(c.config["dedup"], vocab=1 << 14)
    else:
        c.traffic.update(sessions=8, prompt_len=24)
        c.config["vocab_size"] = 2048
    return c


def _run(workload, seconds=0.6):
    c = _small(workload)
    return run.measure(c, SEED, seconds, False, jax.devices()[:1],
                       time.perf_counter())


DEDUP = "dedup.fineweb-web"
DECODE = "decode.kimi-k2-256"


@pytest.mark.parametrize("workload", [DEDUP, DECODE])
def test_sound_run_is_correct(workload):
    result, log = _run(workload)
    assert result["correct"], log
    assert list(result)[-1] == "checks"
    assert log[-1].startswith("check ")


@pytest.mark.parametrize("workload", [DEDUP, DECODE])
def test_control_fails(workload):
    c = _small(workload)
    d = c.driver().Driver(c, SEED)
    d.setup()
    from bench.spans import Spans
    d.window(0.4, Spans())
    d.release()
    numbers = d.control()
    assert any(v > d.ref.LIMITS[k] for k, v in numbers.items()), numbers


def _dedup_fault(monkeypatch, fault):
    from repro.data.dedup import MinHashDeduper
    from repro.data.service import DedupService
    sign = MinHashDeduper.signature_many
    if fault == "state_unchanged":
        # the index never takes the batch's kept documents
        monkeypatch.setattr(DedupService, "_insert_bands",
                            lambda self, inserts: None)
    elif fault == "half_batch":
        def half(self, docs):
            out = sign(self, docs[: len(docs) // 2])
            return np.concatenate(
                [out, np.full((len(docs) - len(out), out.shape[1]),
                              0xFFFFFFFF, np.uint32)])
        monkeypatch.setattr(MinHashDeduper, "signature_many", half)
    elif fault == "answer_altered":
        def flipped(self, docs):
            out = sign(self, docs).copy()
            out[len(out) // 2, 3] ^= np.uint32(1)
            return out
        monkeypatch.setattr(MinHashDeduper, "signature_many", flipped)


def _decode_fault(monkeypatch, fault):
    from repro.serve import sessions
    step = sessions._step_plain

    def broken(*args):
        state = args[6]
        token, new = step(*args)
        if fault == "state_unchanged":
            return token, state
        if fault == "half_batch":
            half = token.shape[0] // 2
            new = {k: v.at[half:].set(state[k][half:]) for k, v in new.items()}
            return token.at[half:].set(0), new
        return token.at[0].add(1), new

    monkeypatch.setattr(sessions, "_step_plain", broken)
    monkeypatch.setattr(sessions, "_step_donated", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("workload", [DEDUP, DECODE])
def test_broken_timed_path_is_not_correct(monkeypatch, workload, fault):
    (_dedup_fault if workload == DEDUP else _decode_fault)(monkeypatch, fault)
    result, log = _run(workload)
    assert not result["correct"], log
