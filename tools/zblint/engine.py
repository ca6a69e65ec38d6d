"""zblint core: file model, suppression, baseline, and the run loop.

Each rule lives in its own module (rule_*.py) and registers through RULES
in __init__.py. A rule reports Findings with a stable message (NO line
numbers inside the message) so the checked-in baseline survives unrelated
line churn: the baseline key is ``path::rule::message`` with a count.

Suppression is inline and visible in review:

    something_deliberate()  # zblint: disable=unobserved-actor-future (why)

or, for multi-line statements, a comment-only line directly above the
flagged line. ``disable=all`` silences every rule for that line.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Dict, List, Optional, Tuple

DEFAULT_ROOTS = (
    "zeebe_tpu", "tests", "benchmarks", "tools", "__graft_entry__.py",
)
BASELINE_PATH = os.path.join("tools", "zblint_baseline.json")
DOCS_DIR = "docs"
STATESER_PATH = os.path.join("zeebe_tpu", "log", "stateser.py")

_SUPPRESS_RE = re.compile(r"#\s*zblint:\s*disable=([A-Za-z0-9_,\-]+)")


@dataclasses.dataclass
class Finding:
    rule: str
    path: str
    line: int
    message: str

    @property
    def key(self) -> str:
        return f"{self.path}::{self.rule}::{self.message}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class FileCtx:
    """One parsed source file, shared by every rule."""

    def __init__(self, path: str, src: str):
        self.path = path
        self.src = src
        self.lines = src.splitlines()
        self.tree: Optional[ast.AST] = None
        self.parse_error: Optional[SyntaxError] = None
        try:
            self.tree = ast.parse(src, filename=path)
        except SyntaxError as e:
            self.parse_error = e
        norm = path.replace(os.sep, "/")
        base = os.path.basename(path)
        self.is_test = (
            norm.startswith("tests/") or "/tests/" in norm
            or base.startswith("test_")
        )
        self.in_package = norm.startswith("zeebe_tpu/")

    def suppressed_rules(self, line: int) -> set:
        """Rules disabled for a 1-indexed physical line (inline comment on
        the line itself, or on a comment-only line directly above)."""
        rules: set = set()
        for lineno in (line, line - 1):
            if not (1 <= lineno <= len(self.lines)):
                continue
            text = self.lines[lineno - 1]
            if lineno != line and not text.lstrip().startswith("#"):
                continue
            m = _SUPPRESS_RE.search(text)
            if m:
                rules.update(r.strip() for r in m.group(1).split(","))
        return rules


class Project:
    """Repo-level context handed to every rule."""

    def __init__(self, root: str, files: List[FileCtx]):
        self.root = root
        self.files = files
        self.docs_dir = os.path.join(root, DOCS_DIR)
        self._host_tables: Optional[Tuple[str, ...]] = None

    def host_table_attrs(self) -> Tuple[str, ...]:
        """Engine-state table attribute names, extracted from the
        HOST_FAMILIES literal in log/stateser.py (no import: stateser
        must stay loadable without pulling the analyzer into jax)."""
        if self._host_tables is not None:
            return self._host_tables
        names: set = set()
        path = os.path.join(self.root, STATESER_PATH)
        try:
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                # plain or annotated assignment (the literal is annotated
                # `HOST_FAMILIES: Dict[...] = {...}` in stateser)
                if isinstance(node, ast.Assign):
                    targets = [
                        t.id for t in node.targets if isinstance(t, ast.Name)
                    ]
                    value = node.value
                elif isinstance(node, ast.AnnAssign):
                    targets = (
                        [node.target.id]
                        if isinstance(node.target, ast.Name) else []
                    )
                    value = node.value
                else:
                    continue
                if "HOST_FAMILIES" not in targets or value is None:
                    continue
                literal = ast.literal_eval(value)
                for keys in literal.values():
                    for key in keys:
                        # snapshot keys map to `self.<key>` or the
                        # private `self._<key>` spelling
                        names.add(key)
                        names.add("_" + key)
        except (OSError, SyntaxError, ValueError):
            pass
        self._host_tables = tuple(sorted(names))
        return self._host_tables


def collect_files(root: str, roots=DEFAULT_ROOTS) -> List[FileCtx]:
    paths: List[str] = []
    for entry in roots:
        full = os.path.join(root, entry)
        if os.path.isfile(full):
            paths.append(entry)
            continue
        for dirpath, _dirs, filenames in os.walk(full):
            for name in filenames:
                if name.endswith(".py") and not name.endswith("_pb2.py"):
                    rel = os.path.relpath(os.path.join(dirpath, name), root)
                    paths.append(rel.replace(os.sep, "/"))
    ctxs = []
    for rel in sorted(paths):
        with open(os.path.join(root, rel), encoding="utf-8") as f:
            ctxs.append(FileCtx(rel, f.read()))
    return ctxs


# -- baseline ----------------------------------------------------------------

def load_baseline(path: str) -> Dict[str, int]:
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    entries = doc.get("entries", {})
    return {str(k): int(v) for k, v in entries.items()}


def write_baseline(path: str, findings: List[Finding]) -> Dict[str, int]:
    entries: Dict[str, int] = {}
    for f in findings:
        entries[f.key] = entries.get(f.key, 0) + 1
    doc = {
        "version": 1,
        "comment": (
            "Grandfathered zblint findings. This file only ratchets DOWN: "
            "fix a finding, then `python -m tools.zblint --write-baseline` "
            "to shrink it. New code must lint clean or carry an inline "
            "`# zblint: disable=<rule>` with a justification."
        ),
        "entries": {k: entries[k] for k in sorted(entries)},
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")
    return entries


def apply_baseline(
    findings: List[Finding], baseline: Dict[str, int]
) -> Tuple[List[Finding], int]:
    """Split findings into (surfaced, baselined_count). The first N
    findings sharing a baseline key are grandfathered; extras surface."""
    budget = dict(baseline)
    surfaced, baselined = [], 0
    for f in findings:
        if budget.get(f.key, 0) > 0:
            budget[f.key] -= 1
            baselined += 1
        else:
            surfaced.append(f)
    return surfaced, baselined


# -- run loop ----------------------------------------------------------------

def run_rules(project: Project, rules) -> List[Finding]:
    """Run `rules` (mapping rule_id -> rule module; one module may host
    several rule ids) over the project, returning suppression-filtered
    findings sorted by location."""
    modules = list(dict.fromkeys(rules.values()))
    selected = set(rules)
    findings: List[Finding] = []
    by_path = {ctx.path: ctx for ctx in project.files}
    for ctx in project.files:
        if ctx.parse_error is not None:
            e = ctx.parse_error
            findings.append(Finding(
                "parse-error", ctx.path, e.lineno or 1,
                f"syntax error: {e.msg}",
            ))
            continue
        for mod in modules:
            check = getattr(mod, "check", None)
            if check is None:
                continue
            if getattr(mod, "PACKAGE_ONLY", False) and not ctx.in_package:
                continue
            if getattr(mod, "SKIP_TESTS", False) and ctx.is_test:
                continue
            findings.extend(check(ctx, project))
    for mod in modules:
        check_repo = getattr(mod, "check_repo", None)
        if check_repo is not None:
            findings.extend(check_repo(project))
    findings = [f for f in findings if f.rule in selected or f.rule == "parse-error"]
    kept = []
    for f in findings:
        ctx = by_path.get(f.path)
        if ctx is not None:
            disabled = ctx.suppressed_rules(f.line)
            if f.rule in disabled or "all" in disabled:
                continue
        kept.append(f)
    kept.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return kept


# -- shared AST helpers ------------------------------------------------------

def attr_chain(node: ast.AST) -> Optional[List[str]]:
    """`a.b.c` -> ["a", "b", "c"]; None when the chain bottoms out in a
    call/subscript/literal."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        parts.reverse()
        return parts
    return None


def call_name(node: ast.Call) -> str:
    chain = attr_chain(node.func)
    if chain:
        return ".".join(chain)
    if isinstance(node.func, ast.Attribute):
        return "<expr>." + node.func.attr
    return "<expr>"
