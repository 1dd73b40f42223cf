"""mxtpu-lint rule engine: AST-based, framework-aware static analysis.

The PR-7 telemetry-coverage gate proved the shape — a small static pass
run as a tier-1 test permanently retires a whole bug class. This module
generalizes it into ONE analysis framework: a rule registry, per-rule
severity, findings keyed (file, rule, message), inline suppressions,
and a checked-in baseline (``tools/lint_baseline.json``) that freezes
pre-existing findings so only NEW violations fail the gate.

Pure stdlib, no jax import: usable anywhere, runs in well under a
second over the whole tree.

Suppression directives (source comments)::

    x = arr.item()          # mxtpu-lint: disable=host-sync-in-hot-path
    g = float(jnp.sqrt(t))  # mxtpu-lint: host-sync-ok   (same rule, the
                            #   idiomatic spelling for a DOCUMENTED sync)
    def feed(self):         # mxtpu-lint: hot-path  (opt a function INTO
        ...                 #   host-sync analysis)
    # mxtpu-lint: disable-file=thread-guard   (whole file, any line)

A directive on its own comment line suppresses the line directly below
it. Baseline workflow: ``python -m tools.mxtpu_lint --update-baseline``
rewrites ``tools/lint_baseline.json`` as sorted, stable JSON so churn
is reviewable in diffs; the default run subtracts it and exits 0 when
nothing new appeared. See docs/static_analysis.md for the rule catalog.
"""

from __future__ import annotations

import ast
import json
import os
import re

#: what a repo-wide run scans, relative to the root (directories walk
#: recursively; plain files are linted as-is)
DEFAULT_TARGETS = ("mxnet_tpu", "tools")

_SKIP_DIRS = {"__pycache__", ".git", ".baseline_wt"}

_DIRECTIVE_RE = re.compile(r"#\s*mxtpu-lint:\s*([^#\n]+)")

#: directive aliases: short annotations that read as intent at the call
#: site but resolve to a plain rule suppression / marker
_ALIASES = {
    "host-sync-ok": "disable=host-sync-in-hot-path",
    "donation-ok": "disable=donation-after-use",
    "overlap-barrier-ok": "disable=overlap-window-sync",
    "lock-order-ok": "disable=lock-order",
}


class Finding:
    """One rule violation. Baseline identity is (file, rule, message) —
    deliberately NOT the line number, so unrelated edits above a frozen
    finding do not unfreeze it."""

    __slots__ = ("rule", "file", "line", "message", "severity")

    def __init__(self, rule, file, line, message, severity="error"):
        self.rule = rule
        self.file = file.replace(os.sep, "/")
        self.line = int(line)
        self.message = message
        self.severity = severity

    def key(self):
        return (self.file, self.rule, self.message)

    def to_dict(self):
        return {"file": self.file, "line": self.line, "rule": self.rule,
                "message": self.message, "severity": self.severity}

    def __repr__(self):
        return (f"{self.file}:{self.line}: [{self.rule}] {self.message}")


class PyFile:
    """A parsed source file plus its directive index."""

    def __init__(self, path, relpath, text):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=path)
        #: line -> set of rule names disabled on that line
        self.suppressions = {}
        #: rules disabled for the whole file
        self.file_suppressions = set()
        #: lines carrying a ``hot-path`` marker (host-sync rule opt-in)
        self.hot_lines = set()
        #: lines carrying an ``overlap-window`` marker (overlap rule
        #: opt-in — the def line of a function issuing bucket comm)
        self.window_lines = set()
        self._index_directives()

    def _index_directives(self):
        for i, line in enumerate(self.lines, start=1):
            m = _DIRECTIVE_RE.search(line)
            if not m:
                continue
            for part in m.group(1).split(";"):
                part = part.strip()
                part = _ALIASES.get(part, part)
                if part.startswith("disable-file="):
                    self.file_suppressions.update(
                        r.strip() for r in part[len("disable-file="):]
                        .split(",") if r.strip())
                elif part.startswith("disable="):
                    self.suppressions.setdefault(i, set()).update(
                        r.strip() for r in part[len("disable="):]
                        .split(",") if r.strip())
                elif part == "hot-path":
                    self.hot_lines.add(i)
                elif part == "overlap-window":
                    self.window_lines.add(i)

    def suppressed(self, finding: Finding) -> bool:
        if finding.rule in self.file_suppressions or \
                "all" in self.file_suppressions:
            return True
        for ln in (finding.line, finding.line - 1):
            rules = self.suppressions.get(ln)
            if rules and (finding.rule in rules or "all" in rules):
                if ln == finding.line:
                    return True
                # the line above counts only when it is a pure comment
                # (a directive on a CODE line governs that line alone)
                above = self.lines[ln - 1].strip() if ln >= 1 and \
                    ln <= len(self.lines) else ""
                if above.startswith("#"):
                    return True
        return False


class LintContext:
    """Shared state for one run: root, scanned files, cross-file rule
    scratch space (rules stash per-file facts here for finalize())."""

    def __init__(self, root):
        self.root = os.path.abspath(root)
        self.files = []  # PyFile, in scan order
        self.scratch = {}  # rule name -> anything

    def read_doc(self, relpath):
        """Text of a docs file (empty string when absent)."""
        p = os.path.join(self.root, relpath)
        try:
            with open(p, encoding="utf-8") as f:
                return f.read()
        except OSError:
            return ""


class Rule:
    """Base rule: subclass, set ``name``/``doc``, implement
    ``check_file`` (per parsed file) and/or ``finalize`` (cross-file,
    runs once after every file was visited)."""

    name = "abstract"
    severity = "error"
    doc = ""

    def check_file(self, pf: PyFile, ctx: LintContext):
        return []

    def finalize(self, ctx: LintContext):
        return []


#: rule registry: name -> class (register via decorator)
REGISTRY = {}


def register(cls):
    if cls.name in REGISTRY:
        raise ValueError(f"duplicate rule name {cls.name!r}")
    REGISTRY[cls.name] = cls
    return cls


# ---------------------------------------------------------------------------
# shared AST helpers (used by several rules)
# ---------------------------------------------------------------------------

def call_name(node):
    """Dotted name of a Call's callee: ``a.b.c(...)`` -> ``"a.b.c"``,
    ``f(...)`` -> ``"f"``; None for computed callees."""
    return dotted_name(node.func) if isinstance(node, ast.Call) else None


def dotted_name(node):
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def module_aliases(tree, module):
    """Names a module is bound to in this file: ``import numpy as _np``
    -> ``{"_np"}`` (plus ``numpy`` itself for a bare import)."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == module:
                    out.add(a.asname or a.name)
    return out


def func_qualnames(tree):
    """Yield ``(qualname, FunctionDef)`` for every function in the file,
    with class nesting encoded (``Trainer.step``, ``Superstep.step``,
    ``outer.<locals>.inner`` collapses to ``outer.inner``)."""
    out = []

    def visit(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}{child.name}"
                out.append((q, child))
                visit(child, q + ".")
            elif isinstance(child, ast.ClassDef):
                visit(child, f"{prefix}{child.name}.")
            else:
                visit(child, prefix)

    visit(tree, "")
    return out


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def iter_source_files(root, targets=DEFAULT_TARGETS):
    for t in targets:
        p = os.path.join(root, t)
        if os.path.isfile(p):
            yield p
        elif os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = sorted(d for d in dirnames
                                     if d not in _SKIP_DIRS)
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)


def run(root, targets=DEFAULT_TARGETS, rules=None, files=None):
    """Lint the tree. Returns ``(findings, ctx)`` with suppressions
    already applied (baseline is the caller's concern). ``rules`` is an
    iterable of rule NAMES (default: all registered); ``files`` an
    explicit file list overriding ``targets``."""
    ctx = LintContext(root)
    active = [REGISTRY[n]() for n in (rules or sorted(REGISTRY))]
    findings = []
    paths = files if files is not None else iter_source_files(root, targets)
    for path in paths:
        rel = os.path.relpath(path, ctx.root)
        try:
            with open(path, encoding="utf-8") as f:
                text = f.read()
            pf = PyFile(path, rel, text)
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            findings.append(Finding(
                "parse-error", rel, getattr(e, "lineno", 1) or 1,
                f"cannot analyze: {type(e).__name__}: {e}"))
            continue
        ctx.files.append(pf)
        for rule in active:
            for f in rule.check_file(pf, ctx):
                if not pf.suppressed(f):
                    findings.append(f)
    byfile = {pf.relpath: pf for pf in ctx.files}
    for rule in active:
        for f in rule.finalize(ctx):
            pf = byfile.get(f.file)
            if pf is None or not pf.suppressed(f):
                findings.append(f)
    findings.sort(key=lambda f: (f.file, f.line, f.rule, f.message))
    return findings, ctx


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

BASELINE_RELPATH = os.path.join("tools", "lint_baseline.json")


def load_baseline(path):
    """-> list of finding dicts ([] when the file does not exist)."""
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except OSError:
        return []
    return data.get("findings", [])


def baseline_keys(entries):
    return {(e["file"], e["rule"], e["message"]) for e in entries}


def apply_baseline(findings, entries):
    """-> ``(new, frozen, stale)``: findings not in the baseline, the
    ones it absorbed, and baseline entries that no longer fire (candidates
    for ``--update-baseline`` garbage collection)."""
    keys = baseline_keys(entries)
    new = [f for f in findings if f.key() not in keys]
    frozen = [f for f in findings if f.key() in keys]
    live = {f.key() for f in findings}
    stale = [e for e in entries
             if (e["file"], e["rule"], e["message"]) not in live]
    return new, frozen, stale


def write_baseline(path, findings):
    """Sorted, stable JSON (one finding per line via indent) so baseline
    churn is reviewable as a plain diff."""
    entries = [f.to_dict() for f in
               sorted(findings, key=lambda f: f.key() + (f.line,))]
    payload = {
        "comment": "frozen pre-existing mxtpu-lint findings; only NEW "
                   "violations fail the gate. Regenerate with "
                   "`python -m tools.mxtpu_lint --update-baseline`.",
        "version": 1,
        "findings": entries,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return entries
