"""Static analysis: jaxpr/HLO invariant checking, kernel contracts, the
Theorem-1/2 discard checker, and the repo-wide lint.

- jaxpr.py     the shared jaxpr/HLO walker: primitive & collective census
               (recursing through jit/shard_map/scan/pallas bodies),
               donation/aliasing verification from lowered text, x64-leak &
               dtype-promotion detection, per-pallas_call VMEM estimates
- contracts.py @kernel_contract declarations next to every entry point +
               verify_contracts(): trace the plan/spec/device-count matrix
               and diff each graph against its declaration
- discard.py   Theorem-1/2 discard checking — statically (AST: probes must
               route through spec.hash_mask / out_bits) and at trace time
               (mask propagation over the jaxpr)
- lint.py      repo-wide AST lint distilled from real past bugs; findings
               with file:line anchors, nonzero exit for CI

Run the whole pass: ``python -m repro.analysis`` (``--lint`` / ``--discard``
/ ``--contracts`` select layers; default runs everything).

This package imports no kernel module at import time — the entry points
import ``analysis.contracts`` for the decorator, and ``verify_contracts``
imports them back lazily, so the dependency stays one-way at import time.
"""
