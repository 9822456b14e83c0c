#!/usr/bin/env python
"""rltcheck: the project-native static-analysis suite, wired into tier-1
next to check_metrics_docs.py.

Runs, in one fast no-JAX-import pass over the source tree:

1. the lock-order analyzer (cycles in the acquisition graph, blocking
   calls under a lock) over runtime/, serving/, observability/;
2. the ``RLT_*`` env-knob registry gate (generated
   ``analysis/knobs.py`` freshness + docs drift in both directions);
3. the invariant lints (raw ``os.replace`` outside utils/fsio.py,
   ledger/journal writes bypassing fsio, unknown ``rlt_*`` metric
   literals, private cross-module imports).

Exit status is non-zero iff any non-allowlisted violation exists.
Audited findings are suppressed via ``ray_lightning_tpu/analysis/
allowlist.txt`` (``<key>  # justification``). Regenerate the knob
registry with ``--write-knobs`` after adding/removing env knobs.

The analyzers live in ``ray_lightning_tpu/analysis/`` but are loaded
here through a synthetic parent package so this script never imports
``ray_lightning_tpu`` itself (whose __init__ pulls in JAX).
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import types
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
PACKAGE = REPO / "ray_lightning_tpu"
ANALYSIS = PACKAGE / "analysis"
ALLOWLIST = ANALYSIS / "allowlist.txt"
KNOBS = ANALYSIS / "knobs.py"
DOCS = REPO / "docs"
KNOB_EXTRA = tuple(sorted((REPO / "scripts").glob("*.py")))

_MODULES = ("core", "lockgraph", "sanitizer", "envknobs", "docs_drift", "invariants")


def load_analysis():
    """Import the analysis modules without importing ray_lightning_tpu."""
    if "ray_lightning_tpu" in sys.modules:
        base = "ray_lightning_tpu.analysis"
    else:
        base = "_rltcheck_analysis"
        if base not in sys.modules:
            pkg = types.ModuleType(base)
            pkg.__path__ = [str(ANALYSIS)]
            sys.modules[base] = pkg
    return types.SimpleNamespace(
        **{m: importlib.import_module(f"{base}.{m}") for m in _MODULES}
    )


def run_checks(a, *, package=PACKAGE, docs=DOCS, allowlist_path=ALLOWLIST,
               knobs_path=KNOBS, knob_extra=KNOB_EXTRA):
    """Run every analyzer; returns (violations, warnings, allowlist)."""
    allowlist = a.core.load_allowlist(allowlist_path)
    violations = list(allowlist.problems)

    lock_viol, _graph = a.lockgraph.analyze(package, allowlist)
    violations += lock_viol

    knob_viol, knob_warn, _ = a.envknobs.gate(
        package, docs, knobs_path, allowlist, extra=knob_extra
    )
    violations += knob_viol

    violations += a.invariants.run_all(package, allowlist)

    warnings = list(knob_warn)
    for key in allowlist.unused():
        warnings.append(f"allowlist entry matches nothing (stale?): {key}")
    return violations, warnings, allowlist


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--write-knobs",
        action="store_true",
        help="regenerate ray_lightning_tpu/analysis/knobs.py and exit",
    )
    ap.add_argument("--json", action="store_true", help="machine output")
    ap.add_argument(
        "--quiet", action="store_true", help="suppress warnings and the ok line"
    )
    args = ap.parse_args(argv)

    a = load_analysis()

    if args.write_knobs:
        knobs = a.envknobs.scan_knobs(PACKAGE, extra=KNOB_EXTRA)
        KNOBS.write_text(a.envknobs.emit_registry(knobs), encoding="utf-8")
        print(f"wrote {KNOBS.relative_to(REPO)} ({len(knobs)} knobs)")
        return 0

    violations, warnings, _ = run_checks(a)

    if args.json:
        print(
            json.dumps(
                {
                    "violations": [v.__dict__ for v in violations],
                    "warnings": warnings,
                },
                indent=2,
            )
        )
        return 1 if violations else 0

    by_kind = {}
    for v in violations:
        by_kind.setdefault(v.kind, []).append(v)
    for kind in sorted(by_kind):
        print(f"== {kind} ({len(by_kind[kind])}) ==")
        for v in by_kind[kind]:
            print(v.render())
        print()
    if not args.quiet:
        for w in warnings:
            print(f"warning: {w}")
    if violations:
        print(f"rltcheck: {len(violations)} violation(s)")
        return 1
    if not args.quiet:
        print("rltcheck: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
