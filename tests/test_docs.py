"""The docs name only paths, symbols and graphs that exist.

Runs ``tools/check_doc_refs.py`` on each maintained document: every
path-like and dotted ``repro.*`` reference resolves, and every fenced
``dot`` block byte-matches a graph the code builds.  ``ROADMAP.md`` names
files still to come and ``CHANGES.md`` is history, so neither is checked.
"""

import functools
import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "PERF.md", "docs/ARCHITECTURE.md", "docs/GLOSSARY.md",
        "docs/AUTHORING.md", "docs/TUNING.md"]


def _load_checker():
    path = os.path.join(REPO, "tools", "check_doc_refs.py")
    spec = importlib.util.spec_from_file_location("check_doc_refs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


checker = _load_checker()
# the reference graphs (mined ones included) are built once per module
buildable_dots = functools.cache(checker._buildable_dots)


@pytest.mark.parametrize("doc", DOCS)
def test_doc_references_resolve(doc):
    full = os.path.join(REPO, doc)
    assert checker.check(full) == [], "dangling references"
    assert checker.check_dot_blocks(full, buildable_dots) == []
