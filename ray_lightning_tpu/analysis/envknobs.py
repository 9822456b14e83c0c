"""``RLT_*`` env-knob registry: extraction, generation, drift gate.

The stack reads well over a hundred ``RLT_*`` environment knobs across
dozens of modules, with no single place that says what exists, what the
defaults are, or which are user-facing versus internal actor-boot
plumbing. This pass:

1. AST-scans the package (plus ``scripts/``) for every
   ``RLT_*`` read (``os.environ.get`` / ``os.getenv`` / subscript
   loads / any ``.get("RLT_…")``), write (subscript stores,
   ``setdefault``, ``pop``/``del``) and literal mention, with literal
   defaults when present;
2. emits the generated registry module (``analysis/knobs.py``) via
   :func:`emit_registry` — regenerate with
   ``python scripts/rltcheck.py --write-knobs``; a stale registry fails
   the gate so the file can never drift from the code;
3. drift-gates the scan against the docs through the shared
   :mod:`.docs_drift` engine: every scanned knob must be documented
   somewhere under ``docs/`` (or allowlisted as internal with a
   justification: ``knob-undocumented:RLT_X  # why``), and every
   knob-table *row* in the docs must name a knob that still exists in
   code. Wildcard rows/prose like ``RLT_SLO_*`` match by prefix.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from .core import Allowlist, Violation, iter_sources, module_name, parse_source
from . import docs_drift

__all__ = [
    "Knob",
    "scan_knobs",
    "emit_registry",
    "load_registry_names",
    "gate",
    "KNOB_DOC_TOKEN",
    "GENERATED_HEADER",
]

_KNOB_NAME = re.compile(r"RLT_[A-Z][A-Z0-9_]*\Z")
# docs-side token; may carry a trailing * (family wildcard, e.g. RLT_SLO_*)
KNOB_DOC_TOKEN = re.compile(r"\bRLT_[A-Z][A-Z0-9_]*\*?")

GENERATED_HEADER = (
    '"""AUTOGENERATED by `python scripts/rltcheck.py --write-knobs` — do\n'
    "not edit by hand. One entry per ``RLT_*`` env knob the code reads,\n"
    "writes, or mentions; the rltcheck gate fails when this file is\n"
    'stale or when a knob is neither documented nor allowlisted."""\n'
)


@dataclass
class Knob:
    name: str
    modules: Set[str] = field(default_factory=set)
    defaults: Set[str] = field(default_factory=set)  # repr() of literals
    read: bool = False
    set: bool = False


def _record(
    knobs: Dict[str, Knob],
    name: str,
    module: str,
    *,
    read: bool = False,
    set_: bool = False,
    default: Optional[ast.AST] = None,
) -> None:
    k = knobs.setdefault(name, Knob(name))
    k.modules.add(module)
    k.read = k.read or read
    k.set = k.set or set_
    if default is not None and isinstance(default, ast.Constant):
        k.defaults.add(repr(default.value))


def _knob_const(node: ast.AST) -> Optional[str]:
    if (
        isinstance(node, ast.Constant)
        and isinstance(node.value, str)
        and _KNOB_NAME.match(node.value)
    ):
        return node.value
    return None


def _scan_tree(tree: ast.Module, module: str, knobs: Dict[str, Knob]) -> None:
    claimed: Set[int] = set()  # ids of Constant nodes already classified

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            func = node.func
            fname = (
                func.attr
                if isinstance(func, ast.Attribute)
                else func.id if isinstance(func, ast.Name) else ""
            )
            arg0 = node.args[0] if node.args else None
            name = _knob_const(arg0) if arg0 is not None else None
            if name is None:
                continue
            default = node.args[1] if len(node.args) > 1 else None
            if fname in ("get", "getenv"):
                _record(knobs, name, module, read=True, default=default)
                claimed.add(id(arg0))
            elif fname == "setdefault":
                _record(knobs, name, module, set_=True, default=default)
                claimed.add(id(arg0))
            elif fname == "pop":
                _record(knobs, name, module, set_=True)
                claimed.add(id(arg0))
        elif isinstance(node, ast.Subscript):
            name = _knob_const(node.slice)
            if name is None:
                continue
            claimed.add(id(node.slice))
            if isinstance(node.ctx, ast.Load):
                _record(knobs, name, module, read=True)
            else:  # Store / Del
                _record(knobs, name, module, set_=True)

    # anything else (dict keys in worker_env templates, comparisons,
    # fault-spec strings) counts as a mention tying the knob to a module
    for node in ast.walk(tree):
        name = _knob_const(node)
        if name is not None and id(node) not in claimed:
            _record(knobs, name, module)


def scan_knobs(
    package_root: Path, extra: Tuple[Path, ...] = ()
) -> Dict[str, Knob]:
    knobs: Dict[str, Knob] = {}
    package_root = Path(package_root)
    for path, module in iter_sources(package_root):
        if module == "analysis.knobs":
            continue  # the generated registry names every knob — skip
            # it or nothing could ever go stale
        tree = parse_source(path)
        if tree is not None:
            _scan_tree(tree, module, knobs)
    for path in extra:
        path = Path(path)
        if not path.exists():
            continue
        tree = parse_source(path)
        if tree is not None:
            _scan_tree(tree, path.stem, knobs)
    return knobs


def emit_registry(knobs: Dict[str, Knob]) -> str:
    lines = [GENERATED_HEADER, "", "KNOBS = {"]
    for name in sorted(knobs):
        k = knobs[name]
        lines.append(f"    {name!r}: {{")
        lines.append(f"        \"modules\": {sorted(k.modules)!r},")
        if k.defaults:
            lines.append(f"        \"defaults\": {sorted(k.defaults)!r},")
        lines.append(
            f"        \"read\": {k.read!r}, \"set\": {k.set!r},"
        )
        lines.append("    },")
    lines.append("}")
    lines.append("")
    return "\n".join(lines)


def load_registry_names(knobs_path: Path) -> Optional[Set[str]]:
    """Knob names committed in the generated registry (regex — the file
    may not be importable in the standalone-loaded context)."""
    p = Path(knobs_path)
    if not p.exists():
        return None
    return set(re.findall(r"^    '(RLT_[A-Z0-9_]+)':", p.read_text(), re.M))


def gate(
    package_root: Path,
    docs_dir: Path,
    knobs_path: Path,
    allowlist: Optional[Allowlist] = None,
    extra: Tuple[Path, ...] = (),
) -> Tuple[List[Violation], List[str], Dict[str, Knob]]:
    """Run the full knob gate. Returns (violations, warnings, scan)."""
    allowlist = allowlist or Allowlist()
    violations: List[Violation] = []
    warnings: List[str] = []
    knobs = scan_knobs(package_root, extra)

    # 1) generated registry freshness
    want = emit_registry(knobs)
    p = Path(knobs_path)
    have = p.read_text(encoding="utf-8") if p.exists() else ""
    if have != want:
        violations.append(
            Violation(
                kind="knob-registry-stale",
                key="knob-registry-stale",
                message=(
                    f"{p.name} does not match the current scan — run "
                    "`python scripts/rltcheck.py --write-knobs`"
                ),
                path=str(p),
            )
        )

    # 2) docs drift, both directions
    doc_paths = sorted(Path(docs_dir).glob("*.md"))
    readme = Path(docs_dir).parent / "README.md"
    if readme.exists():
        doc_paths.append(readme)
    documented = docs_drift.doc_mentions(doc_paths, KNOB_DOC_TOKEN)
    rows = docs_drift.doc_table_rows(doc_paths, KNOB_DOC_TOKEN)
    report = docs_drift.drift(set(knobs), documented, rows)
    for name in report.missing_docs:
        key = f"knob-undocumented:{name}"
        if allowlist.allows(key):
            continue
        mods = ", ".join(sorted(knobs[name].modules))
        violations.append(
            Violation(
                kind="knob-undocumented",
                key=key,
                message=(
                    f"env knob {name} (read in {mods}) is documented "
                    "nowhere under docs/ — add a knob-table row or "
                    "allowlist it as internal"
                ),
            )
        )
    for name in report.stale_rows:
        key = f"knob-stale-doc:{name}"
        if allowlist.allows(key):
            continue
        violations.append(
            Violation(
                kind="knob-stale-doc",
                key=key,
                message=(
                    f"docs knob-table row names {name}, which no code "
                    "reads or writes — remove the row or restore the knob"
                ),
            )
        )
    for name in report.prose_only:
        warnings.append(
            f"docs prose mentions {name} but no code reads it"
        )
    return violations, warnings, knobs
