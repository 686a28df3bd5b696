"""Documents point at files that exist (ROADMAP C7).

One case a document. Every `*.py` path the document names, and every
script or module of ours it tells the reader to run with `python` /
`python3`, must resolve to a file in the tree:

  - a `:line` or `:line-line` suffix is dropped;
  - a path right after `python` / `python3` is a command run from the
    root of the repo, so it must exist exactly there; `python -m a.b`
    must name a module of this tree when `a` is a directory of it;
  - any other path with a directory part must exist from the root, or be
    the tail of a file's path at a directory boundary (`serving/engine.py`
    for `paddle_tpu/serving/engine.py`: the documents' shorthand);
  - a bare file name passes if a file of that name exists anywhere;
  - absolute paths (`/root/reference/...`, `/opt/skills/...`) name other
    trees and patterns (`*`, `<cell>`) name no one file: not checked.

History is exempt, because it must keep naming what it is the history
of: text struck through (`~~...~~`), and everything between a line
holding `<!-- history -->` and the next line holding `<!-- /history -->`
(ROADMAP.md's "Recent" section and re-anchor notes, PERF.md's Findings
of earlier PRs). `CHANGES.md` is history from end to end and is not a
case.
"""
import glob
import os
import re

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SKIP_DIRS = {".git", ".bench_tmp", "chiprun_out", "_export", ".jax_cache",
              "__pycache__", "postmortem", ".pytest_cache"}

DOCS = ["README.md", "PERF.md", "ROADMAP.md",
        ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, _ROOT)
    for p in glob.glob(os.path.join(_ROOT, "docs", "*.md")))

_PY_PATH = re.compile(r"(?<![\w./*<>-])([\w.-]+(?:/[\w.-]+)*\.py)\b")
_PY_CMD = re.compile(r"\bpython3?\s+(?:-[A-Za-z]\s+)*([\w./-]+\.py)\b")
_PY_MOD = re.compile(r"\bpython3?\s+(?:-[A-Za-z]\s+)*-m\s+([\w.]+)")


def _tree():
    files = []
    for dirpath, dirs, names in os.walk(_ROOT):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        files += [os.path.relpath(os.path.join(dirpath, n), _ROOT)
                  for n in names]
    return files


def _without_history(text):
    text = re.sub(r"<!-- history -->.*?<!-- /history -->", "", text,
                  flags=re.S)
    return re.sub(r"~~.*?~~", "", text, flags=re.S)


def _unresolved(text, files):
    names = {os.path.basename(f) for f in files}
    exact = set(files)
    bad = []
    commands = {c for c in _PY_CMD.findall(text) if not c.startswith("/")}
    for path in sorted(set(_PY_PATH.findall(text)) | commands):
        if path in commands:
            ok = path in exact
        elif "/" in path:
            ok = path in exact or any(f.endswith("/" + path) for f in files)
        else:
            ok = path in names
        if not ok:
            bad.append(path)
    for mod in sorted(set(_PY_MOD.findall(text))):
        top = mod.split(".")[0]
        if not os.path.isdir(os.path.join(_ROOT, top)):
            continue                    # pytest, pip: not this tree's
        rel = mod.replace(".", "/")
        if rel + ".py" not in exact and rel + "/__init__.py" not in exact:
            bad.append(f"-m {mod}")
    return bad


@pytest.fixture(scope="module")
def tree():
    return _tree()


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_files_that_exist(doc, tree):
    with open(os.path.join(_ROOT, doc)) as f:
        text = _without_history(f.read())
    bad = _unresolved(text, tree)
    assert not bad, f"{doc} names files that are not in the tree: {bad}"
