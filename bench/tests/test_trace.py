"""The reduction from trace to metrics, on hand-made records with known
answers and on a small trace in the profiler's own format."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import cell as cells
from bench import trace

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"hbm_bytes_per_s": 819e9}


def _record():
    return {"devices": {"/device:TPU:0": [
                ["fusion.1", 100, 200], ["fusion.2", 150, 300],
                ["_plan_kernel", 500, 700], ["fusion.3", 1100, 1200]]},
            "spans": [["bench.window", 50, 1050], ["bench.sign", 90, 320],
                      ["bench.probe", 320, 600], ["bench.add_batch", 60, 1000],
                      ["bench.traffic", 1000, 1040]]}


def test_union_clip_and_gaps():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (3, 4)])
    assert merged == [(0, 4), (5, 7)]
    assert trace.clip(merged, 1, 6) == [(1, 4), (5, 6)]
    assert trace.gaps(merged, -1, 9) == [(-1, 0), (4, 5), (7, 9)]
    assert trace.gaps([], 0, 3) == [(0, 3)]


def test_gap_named_by_innermost_span():
    spans = _record()["spans"][1:]
    assert trace.name_at(400, spans) == "bench.probe"
    assert trace.name_at(1020, spans) == "bench.traffic"
    assert trace.name_at(2000, spans) == "bench.window"


def test_summarize_busy_union_and_breakdown():
    s = trace.summarize(_record())
    # union inside the window: [100, 300) and [500, 700)
    assert s["busy_s"] == pytest.approx(400e-9)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["device_ops"] == [["_plan_kernel", pytest.approx(200e-9)],
                               ["fusion.2", pytest.approx(150e-9)],
                               ["fusion.1", pytest.approx(100e-9)]]
    # gaps: [700, 1050) under add_batch (inner than the window) at 875,
    # [300, 500) under the probe, [50, 100) under add_batch at 75
    assert s["idle_gaps"] == [["bench.add_batch", pytest.approx(350e-9)],
                              ["bench.probe", pytest.approx(200e-9)],
                              ["bench.add_batch", pytest.approx(50e-9)]]


def _profile(planes):
    """A stand-in for ``ProfileData``: {plane: {line: [(name, s, e)]}}."""
    ev = lambda n, s, e: SimpleNamespace(name=n, start_ns=s, end_ns=e)
    return SimpleNamespace(planes=[
        SimpleNamespace(name=p, lines=[
            SimpleNamespace(name=ln, events=[ev(*x) for x in evs])
            for ln, evs in lines.items()])
        for p, lines in planes.items()])


def test_extract_reads_only_the_cells_own_chips():
    data = _profile({
        "/device:TPU:0": {"XLA Ops": [("fusion.1", 100, 200)],
                          "XLA Modules": [("jit_f", 90, 900)]},
        "/device:TPU:1": {"XLA Ops": [("fusion.9", 0, 1000)]},
        "/host:CPU": {"python": [("bench.window", 50, 1050),
                                 ("PjitFunction", 60, 70)]}})
    rec = trace.extract(data, ["/device:TPU:0"])
    assert rec == {"devices": {"/device:TPU:0": [["fusion.1", 100, 200]]},
                   "spans": [["bench.window", 50, 1050]]}
    # the idle chip's plane would have halved the busy time
    assert trace.summarize(rec)["busy_s"] == pytest.approx(100e-9)
    both = trace.extract(data, ["/device:TPU:0", "/device:TPU:1"])
    assert sorted(both["devices"]) == ["/device:TPU:0", "/device:TPU:1"]


@pytest.mark.parametrize("planes, message", [
    (["/device:TPU:2"], "no plane for"),
    (["/device:TPU:0", "/device:TPU:1"], "no 'XLA Ops' line")])
def test_extract_refuses_a_missing_plane_or_ops_line(planes, message):
    data = _profile({
        "/device:TPU:0": {"XLA Ops": [("fusion.1", 100, 200)]},
        "/device:TPU:1": {"XLA Modules": [("jit_f", 90, 900)]}})
    with pytest.raises(ValueError, match=message):
        trace.extract(data, planes)


def test_plane_names_of_devices():
    devs = [SimpleNamespace(platform="tpu", id=i) for i in (0, 3)]
    assert trace.plane_names(devs) == ["/device:TPU:0", "/device:TPU:3"]


def test_summarize_needs_a_window_and_a_device():
    rec = _record()
    with pytest.raises(ValueError):
        trace.summarize({"devices": rec["devices"], "spans": []})
    with pytest.raises(ValueError):
        trace.summarize({"devices": {}, "spans": rec["spans"]})


def _reader(name):
    return cells.load_module(cells.BENCH / "metrics" / f"{name}.py")


def test_sign_roofline_against_hand_computed_bytes():
    r = _reader("sign_roofline.dedup")
    # 1e6 token ids of 4 bytes in, 1000 signatures of 112 lanes out
    assert r.min_bytes(10 ** 6, 1000, 112) == 4_000_000 + 448_000
    facts = {"tokens": 10 ** 6, "docs": 1000, "k": 112}
    got = r.read(facts, {"busy_s": 1e-3, "window_s": 2e-3}, PEAKS)
    assert got == pytest.approx(100 * 4_448_000 / 819e9 / 1e-3)
    assert r.read(facts, None, PEAKS) is None


def test_plane_roofline_against_hand_computed_bytes():
    r = _reader("plane_roofline.decode")
    step = 4 * 256 * 163840 + 4 * 256 + 537600
    assert r.min_bytes(256, 163840, 537600) == step == 168310784
    facts = {"steps": 10, "sessions": 256, "vocab": 163840,
             "carry_bytes": 537600}
    got = r.read(facts, {"busy_s": 28.0, "window_s": 28.5}, PEAKS)
    assert got == pytest.approx(100 * 10 * step / 819e9 / 28.0)


def test_idle_share_and_host_spans():
    s = {"busy_s": 0.25, "window_s": 1.0}
    for cellkind in ("dedup", "decode"):
        assert _reader(f"idle_share.{cellkind}").read({}, s, PEAKS) == 75.0
    facts = {"add_batch_s": 0.9, "sign_s": 0.3, "window_s": 1.2,
             "tokens": 2_000_000, "sign_dispatches": 50}
    assert _reader("host_index_share.dedup").read(facts, None, PEAKS) \
        == pytest.approx(50.0)
    assert _reader("sign_dispatches_per_mtoken.dedup").read(
        facts, None, PEAKS) == pytest.approx(25.0)
    assert _reader("host_index_share.dedup").read({}, None, PEAKS) is None


def test_profiler_trace_extracts_to_the_same_answers():
    from jax.profiler import ProfileData
    data = ProfileData.from_text_proto(
        (DATA / "tpu_window.xplane.txt").read_text())
    rec = trace.extract(data, ["/device:TPU:0"])
    # only the device's op line and the harness's own spans are kept
    assert list(rec["devices"]) == ["/device:TPU:0"]
    assert len(rec["devices"]["/device:TPU:0"]) == 4
    assert sorted(s[0] for s in rec["spans"]) == [
        "bench.add_batch", "bench.probe", "bench.sign", "bench.traffic",
        "bench.window"]
    # the same intervals as the hand-made record, shifted by 5000 ns
    shift = lambda r: {"devices": {k: [[n, s - 5000, e - 5000]
                                       for n, s, e in v]
                                   for k, v in r["devices"].items()},
                       "spans": [[n, s - 5000, e - 5000]
                                 for n, s, e in r["spans"]]}
    got = trace.summarize(shift(rec))
    want = trace.summarize(_record())
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert got["device_ops"] == want["device_ops"]
    assert got["idle_gaps"] == want["idle_gaps"]
