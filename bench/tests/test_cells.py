"""Every cell of BENCHMARK.json resolves to its files; the harness refuses to
run without a TPU and refuses a device it has no peaks for."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import cell as cells

SPEC = cells.benchmark()
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_to_its_files(workload):
    c = cells.resolve(workload, SPEC)
    driver = c.driver()
    assert hasattr(driver.Driver, "window")
    ref = c.reference()
    assert set(ref.LIMITS) and all(v >= 0 for v in ref.LIMITS.values())
    assert c.traffic["kind"] in ("documents", "decode")
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(c.reader(m["name"]).read)
        assert m["moves"] in e2e, "a cell reports what its metrics move"


def test_benchmark_names_and_entries():
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
        for e in SPEC[group]:
            assert set(e) == keys and NAME.match(e["name"])
            assert 1 <= len(e["why"]) <= 200
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        json.loads((cells.ROOT / c["file"]).read_text())


def test_unknown_device_kind_is_an_error():
    assert cells.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        cells.peaks("cpu")


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="unknown workload"):
        cells.resolve("no.such-cell", SPEC)


def test_run_without_a_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(cells.BENCH / "run.py"), "--workload",
         WORKLOADS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=str(cells.ROOT))
    assert p.returncode != 0
    assert "runs on a TPU only" in p.stderr
    assert p.stdout.strip() == ""


def test_run_without_the_program_exits_nonzero(tmp_path):
    import shutil
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(cells.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=str(tmp_path))
    assert p.returncode == 2
    assert "cannot load the cell or the program" in p.stderr
    assert p.stdout.strip() == ""
