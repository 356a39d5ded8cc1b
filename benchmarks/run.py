"""Benchmark entry point: one section per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV (one line per measurement) and also
writes a machine-readable JSON map ``{name: us_per_call}`` so the perf
trajectory is tracked PR over PR (default ``BENCH_pr10.json`` at the repo
root; override the path with REPRO_BENCH_JSON).

Scale via REPRO_BENCH_CHARS (default 4.3 Mchar = the paper's corpus size;
CI/pytest smoke uses a smaller value for time).

The shard_scaling section needs multiple devices: if jax has not been
imported yet and the operator did not pin a device count, 8 virtual CPU
devices are exposed (the same flag test.sh exports) so the 1/2/4/8 sweep is
real under a bare ``python benchmarks/run.py``.

Device-bench profile: ``_bench_env`` pins the rest of the exemplar-harness
environment (32-bit default dtypes, quiet TF logging, tcmalloc large-alloc
report threshold) before jax is imported, so a bare ``python
benchmarks/run.py`` measures the same configuration as ``./test.sh --bench``
on any host. Only the tcmalloc LD_PRELOAD itself must come from the shell
(test.sh does it) — a process cannot preload into itself.
"""
from __future__ import annotations

import json
import os
import sys


def _bench_env() -> None:
    """Pin the measurement environment (idempotent; operator env wins).

    Adapted from the olmax/HomebrewNLP TPU bench harnesses: dtype pinning
    guards against an x64 leak doubling every buffer mid-sweep, the log
    level keeps CSV output parseable, and the tcmalloc threshold silences
    benign large-alloc reports when test.sh preloaded tcmalloc. All are
    backend-agnostic, so the harness runs unchanged on TPU/GPU hosts."""
    env = {
        "JAX_ENABLE_X64": "0",
        "JAX_DEFAULT_DTYPE_BITS": "32",
        "TF_CPP_MIN_LOG_LEVEL": "4",
        "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
    }
    for k, v in env.items():
        os.environ.setdefault(k, v)
    if "jax" not in sys.modules:
        _flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in _flags:
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=8 " + _flags).strip()


def main() -> None:
    # must precede the section imports below (they import jax); kept inside
    # main() so merely importing this module has no environment side effect
    _bench_env()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (chaos_bench, durable_resume, fig1_speed,
                            pipeline_bench, serve_decode, shard_scaling,
                            sketch_fusion, stats_onepass, stream_scaling,
                            table1_properties)
    n_chars = int(os.environ.get("REPRO_BENCH_CHARS", 4_300_000))
    rows = []
    print("name,us_per_call,derived")
    # INVARIANT: shard_scaling runs FIRST. The 1/2/4/8 device sweep compares
    # its points against each other, so every point must see identical
    # runtime state (thread pools, allocator, jit caches) — not whatever a
    # previous section left behind. BENCH_pr4 recorded an inverted sweep
    # (d1 beating d2/4/8) when the sweep ran under degraded smoke settings;
    # the assert below pins the ordering half of that invariant so a
    # refactor cannot silently demote the section again.
    sections = ((shard_scaling, {"scale": n_chars / 4_300_000}),
                (stream_scaling, {"scale": n_chars / 4_300_000}),
                (serve_decode, {"scale": n_chars / 4_300_000}),
                (fig1_speed, {"n_chars": n_chars}),
                (table1_properties, {}),
                (pipeline_bench, {}),
                (sketch_fusion, {}),
                (stats_onepass, {}),
                (durable_resume, {"scale": n_chars / 4_300_000}),
                (chaos_bench, {"scale": n_chars / 4_300_000}))
    assert sections[0][0] is shard_scaling, \
        "shard_scaling must be the first benchmark section (see comment)"
    for mod, kw in sections:
        try:
            section = mod.run(**kw)
        except Exception as e:  # noqa: BLE001 - a broken section must not
            # take down the others (or the JSON trajectory record)
            msg = str(e).replace(",", ";")    # keep the 3-column CSV contract
            print(f"{mod.__name__},0.0,failed ({type(e).__name__}: {msg})",
                  flush=True)
            continue
        for r in section:
            line = f"{r['name']},{r['us_per_call']:.1f},{r['derived']}"
            rows.append(r)
            print(line, flush=True)
    # roofline summary (if dry-run artifacts exist)
    try:
        from repro.launch import roofline
        for line in roofline.bench_rows():
            print(line, flush=True)
    except Exception as e:  # noqa: BLE001
        print(f"roofline_summary,0.0,skipped ({type(e).__name__})", flush=True)
    # static-analysis cost: the --analyze CI gate's wall time is part of the
    # perf trajectory (a contract matrix that quietly grows to minutes is a
    # regression), and its finding count must be 0 on a clean tree
    try:
        import time
        from repro.analysis import contracts, discard, lint
        t0 = time.perf_counter()
        n_lint = len(lint.lint_tree())
        t_lint = time.perf_counter() - t0
        t0 = time.perf_counter()
        n_disc = (len(discard.static_findings())
                  + len(discard.verify_decode_discard()))
        t_disc = time.perf_counter() - t0
        t0 = time.perf_counter()
        import jax
        devs = tuple(d for d in (1, 2, 4, 8) if d <= len(jax.devices()))
        n_con = len(contracts.verify_contracts(device_counts=devs))
        t_con = time.perf_counter() - t0
        n_find = n_lint + n_disc + n_con
        for r in ({"name": "analysis_lint", "us_per_call": t_lint * 1e6,
                   "derived": f"findings={n_lint}"},
                  {"name": "analysis_discard", "us_per_call": t_disc * 1e6,
                   "derived": f"findings={n_disc}"},
                  {"name": "analysis_contracts", "us_per_call": t_con * 1e6,
                   "derived": f"findings={n_con} devices={devs}"}):
            rows.append(r)
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}",
                  flush=True)
        assert n_find == 0, f"analyzer found {n_find} issue(s) on this tree"
    except Exception as e:  # noqa: BLE001
        print(f"analysis_pass,0.0,failed ({type(e).__name__})", flush=True)
    out_path = os.environ.get(
        "REPRO_BENCH_JSON",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "BENCH_pr10.json"))
    with open(out_path, "w") as f:
        json.dump({r["name"]: round(r["us_per_call"], 1) for r in rows},
                  f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {out_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
