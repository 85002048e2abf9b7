"""README.md and the pages of docs/ name only files that exist.

Two rules, over every backticked span and fenced block of a page:

* a path that starts with ``ray_tpu/``, ``tests/``, ``benchmark/``,
  ``docs/``, ``src/`` or ``examples/`` is a file git knows (or a
  directory or a glob that holds one); what follows a ``:`` (a line, a
  function, ``::test_name``) is not part of the path;
* a ``*.py`` run by ``python`` / ``python3``, or handed to the chip tool
  after its ``--``, is a file git knows.

A page that sends its reader to a script that was deleted fails here.
"""

import fnmatch
import os
import re
import subprocess

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGES = ["README.md"] + sorted(
    f"docs/{name}" for name in os.listdir(os.path.join(REPO_ROOT, "docs"))
    if name.endswith(".md"))

CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
PATH = re.compile(
    r"(?<![\w./-])((?:ray_tpu|tests|benchmark|docs|src|examples)/"
    r"[\w./*\[\]-]*)")
RUN = re.compile(r"(?:\bpython3?|\bchiprun\b[^\n`]*?\s--)\s+(?:-\w+\s+)*"
                 r"([\w./-]+\.py)\b")


@pytest.fixture(scope="module")
def tracked():
    """The files git would commit; in a checkout that is not a git
    repository (it then holds nothing else), the files that are there."""
    try:
        out = subprocess.run(
            ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=60,
            check=True).stdout.split("\n")
        files = {f for f in out
                 if f and os.path.exists(os.path.join(REPO_ROOT, f))}
    except (OSError, subprocess.SubprocessError):
        files = set()
    if not files:
        for d, _dirs, names in os.walk(REPO_ROOT):
            files.update(os.path.relpath(os.path.join(d, n), REPO_ROOT)
                         for n in names)
    return files


def _known(path, tracked):
    path = os.path.normpath(path.rstrip(".,;:)"))
    if any(c in path for c in "*["):
        return bool(fnmatch.filter(tracked, path))
    if path in tracked:
        return True
    return any(f.startswith(path + "/") for f in tracked)


@pytest.mark.parametrize("page", PAGES)
def test_a_page_names_only_files_that_exist(page, tracked):
    with open(os.path.join(REPO_ROOT, page), encoding="utf-8") as f:
        text = f.read()
    missing = set()
    for code in CODE.findall(text):
        named = PATH.findall(code) + RUN.findall(code)
        missing.update(p for p in named if not _known(p, tracked))
    assert not missing, f"{page} names files that are not there: " \
        f"{sorted(missing)}"
