"""The documents name files that exist.

A deleted file that ``README.md`` still describes reads as a feature the
repository has. This holds every plain file name the README cites in
backticks to a file of the checkout.
"""

import fnmatch
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CITED_SUFFIXES = (".py", ".md", ".json", ".cc", ".h", ".sh", ".toml")


def _checkout_files():
    """Every file of the checkout, relative to its root, outside ``.git``
    and the directories ``.gitignore`` lists (build outputs, caches, the
    parent copies under ``dist/``): what git would commit, read off the
    disk because the driver's checkout has no ``.git`` to ask."""
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        ignored_dirs = [
            line.strip().rstrip("/")
            for line in f
            if line.strip().endswith("/")
        ] + [".git"]
    files = []
    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [
            d
            for d in dirnames
            if not any(fnmatch.fnmatch(d, pattern) for pattern in ignored_dirs)
        ]
        files.extend(
            os.path.relpath(os.path.join(dirpath, name), REPO)
            for name in filenames
        )
    return files


def cited_files(text):
    """The backticked tokens of ``text`` that are plain file names: a
    cited suffix and nothing but name characters, dots and slashes (no
    braces, no ``*``, no spaces)."""
    return sorted(
        token
        for token in set(re.findall(r"`([^`\n]+)`", text))
        if token.endswith(CITED_SUFFIXES) and re.fullmatch(r"[\w./-]+", token)
    )


def test_readme_cites_files_that_exist():
    """A token with a ``/`` exists relative to the root or to
    ``client_tpu/``; one without is the base name of some file."""
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        cited = cited_files(f.read())
    files = set(_checkout_files())
    base_names = {os.path.basename(path) for path in files}
    assert len(cited) > 40, "the rule found too few tokens to mean anything"
    missing = [
        token
        for token in cited
        if not (
            token in files or f"client_tpu/{token}" in files
            if "/" in token
            else token in base_names
        )
    ]
    assert missing == [], (
        f"README.md cites files the checkout does not have: {missing}"
    )


def test_cited_files_rule():
    text = (
        "`gone.py` and `tools/lint.py`, not `tools/gone_{a,b}.py`, "
        "`GONE_r*.json`, `python3 benchmark/run.py` or `PERF_LEDGER.jsonl`"
    )
    assert cited_files(text) == ["gone.py", "tools/lint.py"]
