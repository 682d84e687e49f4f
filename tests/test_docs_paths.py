"""Every path of this repo that a document names in back-ticks exists.

A document that sends its reader to a file that is gone is worse than one
that says nothing; this holds ``README.md`` and ``docs/*.md`` to the tree.
No jax: it reads text and the file system.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md"] + sorted(
    os.path.join("docs", n) for n in os.listdir(os.path.join(REPO, "docs"))
    if n.endswith(".md"))

TREES = ("bluefog_tpu/", "benchmarks/", "chipbench/", "tests/", "examples/",
         "docs/")
# `path`, `path::test`, `path:12`, `path --flag`; a path followed by [U] is
# the upstream project's, not ours
TICKED = re.compile(r"`([^`\n]+)`(\s*\[U\])?")
PATH = re.compile(r"^[\w./*-]+")


@functools.cache
def _module_names():
    """Base names of every python file in the trees: a bare `optim.py` in
    running text means the module of that name, wherever it lives."""
    names = set(n for n in os.listdir(REPO) if n.endswith(".py"))
    for tree in TREES:
        for _, _, files in os.walk(os.path.join(REPO, tree)):
            names.update(n for n in files if n.endswith(".py"))
    return names


def _named_paths(text):
    for ticked, upstream in TICKED.findall(text):
        m = PATH.match(ticked)
        if upstream or not m:
            continue
        path = m.group(0).rstrip(".:")
        if path.startswith(TREES):
            yield path, False
        elif "/" not in path and path.endswith(".py"):
            yield path, True


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_repo_path_a_document_names_exists(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    modules = _module_names()
    missing = []
    for path, bare in _named_paths(text):
        if bare:
            found = path in modules
        else:
            found = bool(glob.glob(os.path.join(REPO, path)))
        if not found:
            missing.append(path)
    assert not missing, f"{document} names files that are not there: {missing}"
