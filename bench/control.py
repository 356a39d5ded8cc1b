"""Read a cell's control on the chip: the reference in the program's place.

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n> ...

For each seed, in one process: set up the cell, run a short window of the
program at the cell's own size, then compare the control's outputs (the
reference with the precision or probe cut its module names) with the
reference over the same inputs. Prints one JSON line per seed with every
number beside its limit; a control that is caught exceeds a limit. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cell as cells  # noqa: E402
from bench import run  # noqa: E402
from bench.spans import Spans  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    try:
        run.chips(cell.chips)
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    run.enable_compile_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        d = cell.driver().Driver(cell, seed)
        d.setup()
        d.window(args.seconds, Spans())
        d.release()
        limits = d.ref.LIMITS
        numbers = d.control()
        print(json.dumps({
            "workload": cell.name, "seed": seed, "control": {
                k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items()},
            "caught": any(v > limits[k] for k, v in numbers.items()),
            "info": d.info(), "seconds": time.perf_counter() - t0}),
            flush=True)
        del d
    return 0


if __name__ == "__main__":
    sys.exit(main())
