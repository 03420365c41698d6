#!/usr/bin/env python
"""Stdlib fallback for the ruff gate (scripts/check_graph.sh).

The CI container does not ship ruff and bakes its own toolchain, so the
lint half of the graph gate needs a zero-dependency implementation of the
conservative subset of ruff.toml's rule set that works without scope
analysis:

  F401-ish  unused imports (module-wide usage check, conservative)
  F541      f-string without any placeholder
  F632      `is` / `is not` comparison against a str/int literal
  F821-ish  names that are loaded but bound NOWHERE in the file
            (module-coarse: any binding anywhere in the file counts, so
            scope bugs slip through but typos and deleted helpers are
            caught with near-zero false positives)
  F841-ish  locals assigned but never used (function-coarse: a plain
            `name = ...` / `name: T = ...` / walrus target inside a
            function whose name is LOADED nowhere in that function's
            whole subtree, nested defs included. Underscore-leading
            names, global/nonlocal declarations, augmented assigns,
            tuple unpacking, and assign-then-`del` (Del counts as a
            use, matching pyflakes) are exempt — the scope-free slice
            of the rule; ruff's scope-aware F841 additionally sees
            shadowing and unpacking cases)

ruff.toml additionally selects F811/F823 — scope-aware rules a coarse
checker would false-positive on (this repo lazily re-imports the same
names inside functions by design), so they run only where ruff exists.
`ruff check` passing is strictly stronger than repolint passing.

Files using wildcard imports are skipped for the undefined-name rule
(anything could be bound), and a trailing `# noqa` silences a line.

  python tools/repolint.py [paths...]     # default: the repo's code dirs

Exit 1 when any finding is printed, 0 clean — same contract as
`ruff check`.
"""

from __future__ import annotations

import ast
import builtins
import os
import sys
from typing import List, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_TARGETS = ("bert_pytorch_tpu", "tools", "scripts", "tests", "data",
                   "run_pretraining.py", "run_squad.py",
                   "run_ner.py", "__graft_entry__.py")

# names the interpreter/jax inject that a module-coarse pass cannot see
_IMPLICIT = {
    "__file__", "__name__", "__doc__", "__package__", "__spec__",
    "__loader__", "__builtins__", "__debug__", "__path__", "__all__",
    "__version__", "__class__",
}


class _Binder(ast.NodeVisitor):
    """Collect every name BOUND anywhere in the file, any scope."""

    def __init__(self) -> None:
        self.bound: Set[str] = set()
        self.star_import = False

    def _bind_target(self, node: ast.AST) -> None:
        for n in ast.walk(node):
            if isinstance(n, ast.Name):
                self.bound.add(n.id)
            elif isinstance(n, (ast.MatchAs, ast.MatchStar)) \
                    and getattr(n, "name", None):
                self.bound.add(n.name)

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self.bound.add((a.asname or a.name).split(".")[0])

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        for a in node.names:
            if a.name == "*":
                self.star_import = True
            else:
                self.bound.add(a.asname or a.name)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.bound.add(node.name)
        args = node.args
        for a in (args.posonlyargs + args.args + args.kwonlyargs
                  + ([args.vararg] if args.vararg else [])
                  + ([args.kwarg] if args.kwarg else [])):
            self.bound.add(a.arg)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Lambda(self, node: ast.Lambda) -> None:
        args = node.args
        for a in (args.posonlyargs + args.args + args.kwonlyargs
                  + ([args.vararg] if args.vararg else [])
                  + ([args.kwarg] if args.kwarg else [])):
            self.bound.add(a.arg)
        self.generic_visit(node)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.bound.add(node.name)
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._bind_target(t)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_NamedExpr(self, node) -> None:
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_For(self, node: ast.For) -> None:
        self._bind_target(node.target)
        self.generic_visit(node)

    visit_AsyncFor = visit_For  # type: ignore[assignment]

    def visit_comprehension_target(self, comp: ast.comprehension) -> None:
        self._bind_target(comp.target)

    def visit_ListComp(self, node) -> None:
        for c in node.generators:
            self.visit_comprehension_target(c)
        self.generic_visit(node)

    visit_SetComp = visit_DictComp = visit_GeneratorExp = visit_ListComp

    def visit_With(self, node: ast.With) -> None:
        for item in node.items:
            if item.optional_vars is not None:
                self._bind_target(item.optional_vars)
        self.generic_visit(node)

    visit_AsyncWith = visit_With  # type: ignore[assignment]

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if node.name:
            self.bound.add(node.name)
        self.generic_visit(node)

    def visit_Global(self, node: ast.Global) -> None:
        self.bound.update(node.names)

    def visit_Nonlocal(self, node: ast.Nonlocal) -> None:
        self.bound.update(node.names)

    def visit_MatchAs(self, node) -> None:
        if node.name:
            self.bound.add(node.name)
        self.generic_visit(node)


def _noqa_lines(src: str) -> Set[int]:
    return {i for i, line in enumerate(src.splitlines(), 1)
            if "# noqa" in line}


def lint_file(path: str) -> List[Tuple[int, str, str]]:
    """[(line, code, message)] findings for one file."""
    try:
        with open(path, encoding="utf-8") as f:
            src = f.read()
    except OSError as e:
        return [(0, "E000", f"unreadable: {e}")]
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [(e.lineno or 0, "E999", f"syntax error: {e.msg}")]
    noqa = _noqa_lines(src)
    binder = _Binder()
    binder.visit(tree)
    loads: Set[str] = set()
    findings: List[Tuple[int, str, str]] = []

    # a FormattedValue's format spec (`f"{x:.2f}"`) is itself a nested
    # JoinedStr with no placeholders — never a finding
    spec_ids = {id(n.format_spec) for n in ast.walk(tree)
                if isinstance(n, ast.FormattedValue)
                and n.format_spec is not None}

    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            loads.add(node.id)
        elif isinstance(node, ast.JoinedStr) and id(node) not in spec_ids:
            if not any(isinstance(v, ast.FormattedValue)
                       for v in node.values) and node.lineno not in noqa:
                findings.append((node.lineno, "F541",
                                 "f-string without any placeholders"))
        elif isinstance(node, ast.Compare):
            for op, comp in zip(node.ops, node.comparators):
                # True/False/None are singletons — `is` against them is
                # correct and NOT flagged (matches ruff F632)
                if isinstance(op, (ast.Is, ast.IsNot)) and isinstance(
                        comp, ast.Constant) and isinstance(
                        comp.value, (str, bytes, int, float, tuple)) \
                        and not isinstance(comp.value, bool) \
                        and node.lineno not in noqa:
                    findings.append((node.lineno, "F632",
                                     "`is` comparison with a literal — "
                                     "use =="))

    # F401: imports whose bound name is never loaded anywhere else.
    # __init__.py re-exports on purpose (mirrors ruff.toml's ignore).
    if os.path.basename(path) != "__init__.py":
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [(a, (a.asname or a.name).split(".")[0])
                         for a in node.names]
            elif isinstance(node, ast.ImportFrom) \
                    and node.module != "__future__":
                names = [(a, a.asname or a.name) for a in node.names
                         if a.name != "*"]
            for alias, bound in names:
                if bound not in loads and bound != "_" \
                        and node.lineno not in noqa:
                    findings.append((node.lineno, "F401",
                                     f"'{bound}' imported but unused"))

    # F841 (function-coarse): plain-assignment locals loaded nowhere in
    # the function subtree. Walked per top-level-reachable function so a
    # name used only in a sibling function still counts as unused.
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        fn_loads: Set[str] = set()
        declared: Set[str] = set()
        for sub in ast.walk(node):
            # `del x` counts as a use (ast.Del ctx), and an augmented
            # assignment implicitly LOADS its target before storing:
            # pyflakes/ruff F841 flag neither assign-then-del nor
            # assign-then-augment, and ruff must stay strictly stronger
            # than this fallback, never weaker
            if isinstance(sub, ast.Name) and isinstance(
                    sub.ctx, (ast.Load, ast.Del)):
                fn_loads.add(sub.id)
            elif isinstance(sub, ast.AugAssign) \
                    and isinstance(sub.target, ast.Name):
                fn_loads.add(sub.target.id)
            elif isinstance(sub, (ast.Global, ast.Nonlocal)):
                declared.update(sub.names)
        for sub in node.body:  # direct statements only: nested defs get
            # their own walk, and a name assigned in an inner scope is
            # that scope's local, not this one's
            for stmt in ast.walk(sub):
                targets = []
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name):
                    targets = [stmt.targets[0]]
                elif isinstance(stmt, ast.AnnAssign) \
                        and stmt.value is not None \
                        and isinstance(stmt.target, ast.Name):
                    targets = [stmt.target]
                elif isinstance(stmt, ast.NamedExpr) \
                        and isinstance(stmt.target, ast.Name):
                    targets = [stmt.target]
                elif isinstance(stmt, (ast.FunctionDef,
                                       ast.AsyncFunctionDef, ast.Lambda,
                                       ast.ClassDef)):
                    break  # don't descend: inner scopes own their locals
                    # (conservative — walk order may skip later nodes of
                    # this statement too; missed findings, never false
                    # positives)
                for t in targets:
                    if t.id.startswith("_") or t.id in fn_loads \
                            or t.id in declared \
                            or t.lineno in noqa:
                        continue
                    findings.append((t.lineno, "F841",
                                     f"local variable '{t.id}' is "
                                     "assigned to but never used"))

    # F821 (module-coarse): loaded names bound nowhere in the file
    if not binder.star_import:
        known = binder.bound | set(dir(builtins)) | _IMPLICIT
        for node in ast.walk(tree):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) \
                    and node.id not in known and node.lineno not in noqa:
                findings.append((node.lineno, "F821",
                                 f"undefined name '{node.id}'"))

    return sorted(set(findings))


def iter_py_files(targets) -> List[str]:
    out = []
    for t in targets:
        path = t if os.path.isabs(t) else os.path.join(REPO, t)
        if os.path.isfile(path) and path.endswith(".py"):
            out.append(path)
        elif os.path.isdir(path):
            for root, dirs, files in os.walk(path):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                out.extend(os.path.join(root, f) for f in files
                           if f.endswith(".py"))
    return sorted(set(out))


def main(argv=None) -> int:
    targets = (argv if argv else sys.argv[1:]) or list(DEFAULT_TARGETS)
    n = 0
    files = iter_py_files(targets)
    for path in files:
        for line, code, msg in lint_file(path):
            rel = os.path.relpath(path, REPO)
            print(f"{rel}:{line}: {code} {msg}")
            n += 1
    if n:
        print(f"repolint: {n} finding(s) in {len(files)} files")
        return 1
    print(f"repolint: {len(files)} files clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
