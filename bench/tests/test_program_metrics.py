"""The readers of the program's own spans, on hand-made ``repro.obs``
snapshots with known answers, and on a program without a recorder."""
import contextvars
import sys

import pytest

from bench import cell as cells

ROOT_PATH = "dedup.add_batch"
SIGN = f"{ROOT_PATH}/dedup.sign"


def _reader(name):
    return cells.load_module(cells.BENCH / "metrics" / f"{name}.py")


def _span(count, total_s, self_s=None):
    return {"count": count, "total_s": total_s,
            "self_s": total_s if self_s is None else self_s}


SNAPSHOT = {"spans": {
    ROOT_PATH: _span(26, 20.0, 0.1),
    SIGN: _span(26, 9.0, 2.0),
    f"{SIGN}/dedup.sign.pack": _span(300, 0.5),
    f"{SIGN}/dedup.sign.fetch": _span(26 * 64, 6.5),
    f"{ROOT_PATH}/dedup.probe": _span(26, 4.0),
    f"{ROOT_PATH}/dedup.verify": _span(26, 1.5),
    f"{ROOT_PATH}/dedup.insert": _span(26, 5.4),
    # set-up's signing: a root path, never read
    "dedup.sign/dedup.sign.fetch": _span(4, 30.0),
}, "counters": {"stream.dispatches": 1700}}
FACTS = {"window_s": 25.0, "tokens": 45_500_000}

EXPECTED = {
    "sign_pack_share.dedup": 100 * 0.5 / 25.0,
    "sign_fetch_share.dedup": 100 * 6.5 / 25.0,
    "sign_fetches_per_mtoken.dedup": 26 * 64 / 45.5,
    "probe_share.dedup": 100 * 4.0 / 25.0,
    "verify_share.dedup": 100 * 1.5 / 25.0,
    "insert_share.dedup": 100 * 5.4 / 25.0,
}


@pytest.fixture
def snapshot(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "totals", lambda: SNAPSHOT)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_answers(name, snapshot):
    got = _reader(name).read(FACTS, None, {})
    assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_the_path(name, monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "totals", lambda: {
        "spans": {"dedup.sign/dedup.sign.fetch": _span(4, 30.0)},
        "counters": {}})
    assert _reader(name).read(FACTS, None, {}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_a_recorder(name, monkeypatch):
    """A program without ``repro.obs``, as before it had one."""
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _reader(name).read(FACTS, None, {}) is None


def test_readers_see_the_program_recorder():
    """The readers read what the recorder sums in the calling context."""
    from repro import obs

    def work():
        with obs.span("dedup.add_batch", batch=1):
            with obs.span("dedup.sign"):
                for _ in range(3):
                    with obs.span("dedup.sign.fetch"):
                        pass
            with obs.span("dedup.probe"):
                pass
        facts = {"window_s": 1.0, "tokens": 1_000_000}
        return {name: _reader(name).read(facts, None, {})
                for name in EXPECTED}

    got = contextvars.Context().run(work)
    assert got["sign_fetches_per_mtoken.dedup"] == 3.0
    for name in ("sign_fetch_share.dedup", "probe_share.dedup"):
        assert 0 <= got[name] < 100
    for name in ("sign_pack_share.dedup", "verify_share.dedup",
                 "insert_share.dedup"):
        assert got[name] is None
