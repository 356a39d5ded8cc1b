"""The program's span-and-counter recorder (``repro.obs``) and the spans the
dedup batch path opens with it."""
import contextvars
import time
import warnings

import jax
import numpy as np
import pytest

from repro import obs


def fresh(fn, *args):
    """Run ``fn`` in an empty context: a recorder with no sums yet."""
    return contextvars.Context().run(fn, *args)


def test_span_paths_nest():
    def work():
        with obs.span("a"):
            with obs.span("b"):
                with obs.span("c"):
                    pass
            with obs.span("b"):
                pass
        with obs.span("b"):
            pass
        return obs.totals()["spans"]

    spans = fresh(work)
    assert {p: s["count"] for p, s in spans.items()} == {
        "a": 1, "a/b": 2, "a/b/c": 1, "b": 1}


def test_self_time_is_total_less_children():
    def work():
        with obs.span("outer"):
            time.sleep(0.02)
            for _ in range(2):
                with obs.span("inner"):
                    time.sleep(0.03)
        return obs.totals()["spans"]

    spans = fresh(work)
    outer, inner = spans["outer"], spans["outer/inner"]
    assert inner["count"] == 2
    assert inner["self_s"] == inner["total_s"] >= 0.06
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-9)
    assert outer["self_s"] >= 0.02
    assert outer["total_s"] >= 0.08


def test_span_is_recorded_when_the_block_raises():
    def work():
        with pytest.raises(KeyError):
            with obs.span("fails"):
                raise KeyError("x")
        with obs.span("after"):
            pass
        return obs.totals()["spans"]

    assert set(fresh(work)) == {"fails", "after"}


def test_counters():
    def work():
        assert obs.counter("t.count") == 0
        obs.count("t.count")
        obs.count("t.count", 4)
        obs.count("t.other", 2)
        return obs.counter("t.count"), obs.totals()["counters"]

    n, counters = fresh(work)
    assert n == 5
    assert counters == {"t.count": 5, "t.other": 2}


def test_copied_contexts_keep_their_own_sums():
    name = "t.isolated"

    def work(n):
        obs.count(name, n)
        with obs.span(name):
            pass
        return obs.counter(name), obs.totals()["spans"][name]["count"]

    base = obs.counter(name)
    first = contextvars.copy_context()
    assert first.run(work, 2) == (base + 2, 1)
    assert contextvars.copy_context().run(work, 5) == (base + 5, 1)
    assert first.run(work, 1) == (base + 3, 2)
    assert obs.counter(name) == base
    assert name not in obs.totals()["spans"]


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    def work():
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("t.window"):
                with obs.span("t.batch", batch=7):
                    with obs.span("t.part"):
                        time.sleep(0.005)
        finally:
            jax.profiler.stop_trace()
        return obs.totals()["spans"]

    spans = fresh(work)
    assert spans["t.batch/t.part"]["count"] == 1
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    events = {}
    with warnings.catch_warnings():
        # the profiler's stat type warns when its stats are iterated
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("t."):
                        events[e.name] = (e.start_ns, e.end_ns,
                                          dict(e.stats))
    (w0, w1, _), (b0, b1, args), (p0, p1, _) = (
        events["t.window"], events["t.batch"], events["t.part"])
    assert w0 <= b0 <= p0 < p1 <= b1 <= w1
    assert args["batch"] == 7


DEDUP_PATHS = {
    "dedup.add_batch", "dedup.add_batch/dedup.sign",
    "dedup.add_batch/dedup.sign/dedup.sign.pack",
    "dedup.add_batch/dedup.sign/dedup.sign.fetch",
    "dedup.add_batch/dedup.probe", "dedup.add_batch/dedup.verify",
    "dedup.add_batch/dedup.insert"}


@pytest.mark.parametrize("kind", ["service", "deduper"])
def test_dedup_batch_records_its_spans(kind):
    from repro.data.dedup import DedupConfig, MinHashDeduper
    from repro.data.service import DedupService, ServiceConfig
    from repro.kernels import stream

    cfg = DedupConfig(ngram_n=5, n_signatures=16, lsh_bands=4, vocab=1000)
    rng = np.random.default_rng(3)
    docs = [rng.integers(0, 1000, int(n)).astype(np.int32)
            for n in rng.integers(8, 1200, 140)]
    docs += docs[:10]
    groups = -(-len(docs) // cfg.stream_rows)

    def work():
        dd = (DedupService(cfg, ServiceConfig(n_workers=4, replication=2))
              if kind == "service" else MinHashDeduper(cfg))
        try:
            flags = dd.add_batch(docs)
        finally:
            dd.close()
        return flags, obs.totals(), stream.dispatch_count()

    flags, tot, dispatches = fresh(work)
    assert flags[-10:].all()
    spans = tot["spans"]
    assert set(spans) == DEDUP_PATHS
    assert spans["dedup.add_batch"]["count"] == 1
    assert spans["dedup.add_batch/dedup.sign"]["count"] == 1
    assert spans["dedup.add_batch/dedup.sign/dedup.sign.fetch"]["count"] \
        == groups
    assert spans["dedup.add_batch/dedup.sign/dedup.sign.pack"]["count"] \
        >= groups
    for path, s in spans.items():
        kids = [c["total_s"] for p, c in spans.items()
                if p.rpartition("/")[0] == path]
        assert sum(kids) <= s["total_s"]
        assert 0 <= s["self_s"] <= s["total_s"]
    assert tot["counters"] == {"stream.dispatches": dispatches}
    assert dispatches >= groups
