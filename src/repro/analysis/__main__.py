"""CLI driver: ``python -m repro.analysis`` — the repo-wide analysis pass.

Default runs every layer (lint, discard static+trace, contract matrix) and
exits nonzero if anything fires; ``--lint`` / ``--discard`` / ``--contracts``
select a subset. ``--devices`` narrows the contract matrix (the full 1/2/4/8
sweep needs ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="jaxpr/HLO invariant checking, Theorem-discard lint, "
                    "and the repo-wide AST lint")
    ap.add_argument("--lint", action="store_true",
                    help="run only the repo-wide AST lint")
    ap.add_argument("--discard", action="store_true",
                    help="run only the Theorem-1/2 discard checks")
    ap.add_argument("--contracts", action="store_true",
                    help="run only the kernel-contract matrix")
    ap.add_argument("--devices", type=int, nargs="*", default=None,
                    metavar="D",
                    help="contract-matrix device counts (default: every "
                         "count <= the available device pool)")
    args = ap.parse_args(argv)

    run_all = not (args.lint or args.discard or args.contracts)
    failures = 0
    t0 = time.perf_counter()

    if run_all or args.lint:
        from repro.analysis import lint
        findings = lint.lint_tree()
        for f in findings:
            print(f)
        failures += len(findings)
        print(f"lint: {len(findings)} finding(s)")

    if run_all or args.discard:
        from repro.analysis import discard
        static = discard.static_findings()
        for f in static:
            print(f)
        trace = discard.verify_decode_discard() + \
            discard.verify_stats_discard()
        for f in trace:
            print(f)
        failures += len(static) + len(trace)
        print(f"discard: {len(static)} static + {len(trace)} trace "
              f"finding(s)")

    if run_all or args.contracts:
        from repro.analysis import contracts
        kw = {}
        if args.devices is not None:
            kw["device_counts"] = tuple(args.devices)
        else:
            import jax
            avail = len(jax.devices())
            kw["device_counts"] = tuple(
                d for d in (1, 2, 4, 8) if d <= avail)
        violations = contracts.verify_contracts(**kw)
        for v in violations:
            print(v)
        failures += len(violations)
        print(f"contracts: {len(contracts.registry())} entries over "
              f"device counts {kw['device_counts']}, "
              f"{len(violations)} violation(s)")

    dt = time.perf_counter() - t0
    status = "FAIL" if failures else "OK"
    print(f"analysis: {status} — {failures} total finding(s) in {dt:.1f}s")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
