"""Every path a document, a script or a comment names exists in the tree.

One case per document (README.md, PERF.md, the verify skill, docs/*.md,
scripts/*.sh) and one each for the docstrings and comments of the package,
of tools/ with scripts/*.py, and of the root entry points. CHANGES.md and
ROADMAP.md are history and name what is gone; they are not read.

What counts as naming a path (rules, not lists):

- a token whose first component is a top-level directory of the repo that
  holds tracked files (`bert_pytorch_tpu/`, `benchmark/`, `tools/`, ...), or
  a directory directly under `bert_pytorch_tpu/` or `benchmark/` (the
  package-relative `ops/pallas/layernorm.py`, `harness/corpus.py`);
- a bare `name.py` or `name.md`, and a bare `NAME.json` / `NAME.jsonl` in
  capitals (the root's records); a bare lower-case `*.json` is a file the
  reader supplies or a run writes.

A token resolves against the root, the document's own directory (docs/
siblings), `bert_pytorch_tpu/` and `benchmark/`; a bare name also against
the basenames of all tracked files and the names SURVEY.md gives the
reference's files. `tests/test_x.py::test_y` is read up to the `::`,
`tools/loadtest.validate_serve` as `tools/loadtest.py`, `ops/moe` as
`ops/moe.py`. Skipped: words joined by slashes that start at no top-level
directory, end in no slash and carry no extension (prose); globs,
`<placeholders>`, `$VARIABLES` and `{a,b}` sets; absolute paths and URLs
(the token follows a `/`, `~`, `:` or `.`); and anything under a directory
the same document gives to an `--output_dir`-like flag.
"""

import ast
import io
import os
import re
import subprocess
import tokenize

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tracked():
    try:
        out = subprocess.run(["git", "ls-files"], cwd=REPO, text=True,
                             capture_output=True, check=True).stdout
        files = [f for f in out.splitlines()
                 if os.path.exists(os.path.join(REPO, f))]
    except (OSError, subprocess.CalledProcessError):
        files = []
    if not files:       # not a git checkout (an archive of one): walk it
        for base, dirs, names in os.walk(REPO):
            dirs[:] = [d for d in dirs
                       if not d.startswith(".") or d == ".claude"]
            files += [os.path.relpath(os.path.join(base, n), REPO)
                      for n in names]
    return files


TRACKED = _tracked()
BASENAMES = {os.path.basename(f) for f in TRACKED}
# the reference's own files, as SURVEY.md names them ("modeling.py:303")
with open(os.path.join(REPO, "SURVEY.md"), encoding="utf-8") as _f:
    REFERENCE_NAMES = set(re.findall(r"[\w-]+\.(?:py|md|json)", _f.read()))
TOP_DIRS = {f.split("/")[0] for f in TRACKED if "/" in f
            and not f.startswith(".")}
NESTED_ROOTS = ("bert_pytorch_tpu", "benchmark")
NESTED_DIRS = {f.split("/")[1] for f in TRACKED
               if f.count("/") >= 2 and f.split("/")[0] in NESTED_ROOTS}

_PATH = re.compile(r"(?<![\w/~:.$>*-])((?:[\w.-]+/)+[\w.*<>{},$-]*)")
_BARE = re.compile(r"(?<![\w/~:.$>*<-])([A-Za-z_][\w-]*\.(?:py|md|jsonl?))"
                   r"(?![\w/])")
_OUT_FLAG = re.compile(
    r"--(?:output_dir|out_dir|work_dir|log_dir|out)[ =]+[\"']?([\w./-]+)")
_SKIP_CHARS = set("*<>{}$,")


def _exists(token, doc_dir, bare):
    roots = ["", doc_dir] + list(NESTED_ROOTS)
    candidates = [token, token + ".py"]     # a module named without .py
    stem = token
    while "." in os.path.basename(stem):    # module.function tails
        stem = stem.rsplit(".", 1)[0]
        candidates += [stem, stem + ".py"]
    for root in roots:
        for c in candidates:
            if os.path.exists(os.path.join(REPO, root, c)):
                return True
    return bare and (token in BASENAMES or token in REFERENCE_NAMES)


def dangling(text, doc_dir=""):
    """The path tokens of text that name nothing in the tree."""
    run_outputs = tuple(m.group(1).rstrip("/") + "/"
                        for m in _OUT_FLAG.finditer(text))
    found = set()
    for m in _PATH.finditer(text):
        token = m.group(1).split("::")[0].rstrip(".,:;")
        first = token.split("/")[0]
        if first not in TOP_DIRS and first not in NESTED_DIRS:
            continue
        if _SKIP_CHARS & set(token) or (token.rstrip("/") + "/").startswith(
                run_outputs):
            continue
        if _exists(token.rstrip("/"), doc_dir, bare=False):
            continue
        # words joined by slashes ("data/seed/schedule") are prose, not a
        # path, unless they start at a top-level directory, end in a slash
        # or carry a file extension
        if (first in TOP_DIRS or token.endswith("/")
                or "." in os.path.basename(token)):
            found.add(token)
    for m in _BARE.finditer(text):
        token = m.group(1)
        if token.endswith((".json", ".jsonl")) and not token[0].isupper():
            continue
        if not _exists(token, doc_dir, bare=True):
            found.add(token)
    return sorted(found)


def _comments_and_docstrings(path):
    with open(path, encoding="utf-8") as f:
        source = f.read()
    parts = [tok.string for tok in
             tokenize.generate_tokens(io.StringIO(source).readline)
             if tok.type == tokenize.COMMENT]
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            parts.append(ast.get_docstring(node, clean=False) or "")
    return "\n".join(parts)


def _python_files(*patterns):
    return sorted(f for f in TRACKED if f.endswith(".py")
                  and any(re.match(p, f) for p in patterns))


DOCUMENTS = (["README.md", "PERF.md", ".claude/skills/verify/SKILL.md"]
             + sorted(f for f in TRACKED if re.match(r"docs/[^/]+\.md$", f))
             + sorted(f for f in TRACKED
                      if re.match(r"scripts/[^/]+\.sh$", f)))
CODE = {
    "bert_pytorch_tpu/": _python_files(r"bert_pytorch_tpu/"),
    "tools/ and scripts/*.py": _python_files(r"tools/", r"scripts/"),
    "root entry points": _python_files(r"[^/]+\.py$"),
}


@pytest.mark.parametrize("case", DOCUMENTS + sorted(CODE))
def test_every_path_named_exists(case):
    if case in CODE:
        assert CODE[case], case
        missing = {f: dangling(_comments_and_docstrings(
            os.path.join(REPO, f))) for f in CODE[case]}
        missing = {f: m for f, m in missing.items() if m}
    else:
        with open(os.path.join(REPO, case), encoding="utf-8") as f:
            missing = dangling(f.read(), os.path.dirname(case))
    assert not missing, f"{case} names paths that do not exist: {missing}"


def test_the_rule_finds_a_dangling_name_and_skips_what_it_should():
    text = ("see docs/GONE.md and tools/gone.py::test_x, `gone_tool.py`, "
            "ops/pallas/gone.py; fine: tools/loadtest.validate_serve, "
            "docs/*.md, results/<run>/x.json, $WORK/tools/x.py, "
            "/tmp/tools/y.py, cfg.json, tests/test_docs_paths.py::test_a, "
            "--output_dir results/run1 then results/run1/ckpt, "
            "ops/pallas/layernorm.py, harness/corpus.py")
    assert dangling(text) == ["docs/GONE.md", "gone_tool.py",
                              "ops/pallas/gone.py", "tools/gone.py"]
