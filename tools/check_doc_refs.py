#!/usr/bin/env python3
"""Fail if a doc references a repository path that no longer exists, or
embeds a ``dot`` graph that no buildable graph renders.

Two checks per markdown file:

1. **Path/module references** — path-like references (``src/...``,
   ``tests/...``, ...) must exist in the working tree, and dotted
   references (``repro.core.engine``, ``repro.core.engine.SpecSession``,
   ``repro.store.staging.StagingTxn.finalize``) must resolve against the
   *importable* tree: the longest filesystem prefix is imported and the
   remaining components are walked with ``getattr`` — a renamed class or
   deleted method dangles even though its module file survives.
2. **Fenced ``dot`` blocks** — every ```` ```dot ```` block must parse
   against the ``to_dot()`` line grammar *and* byte-for-byte match the
   ``to_dot()`` output of a buildable graph (the hand-written plugin
   graphs, the reusable patterns, or the mined reference graphs from
   ``repro.store.plugins.mine_reference_graphs``).  Docs cannot drift from
   the graphs the code actually builds.

Usage: python tools/check_doc_refs.py docs/ARCHITECTURE.md README.md ...
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PATH_RE = re.compile(
    r"\b(?:src|tests|benchmarks|tpubench|docs|examples|tools)/[\w./-]+"
    r"\.(?:py|md|json|yml)\b"
)
MODULE_RE = re.compile(r"\brepro(?:\.\w+)+\b")
DOT_BLOCK_RE = re.compile(r"^```dot\n(.*?)^```", re.MULTILINE | re.DOTALL)

#: the exact line shapes ForeactionGraph.to_dot() can emit
DOT_LINE_RES = [
    re.compile(r'^digraph "[^"]+" \{$'),
    re.compile(r"^  rankdir=LR;$"),
    re.compile(r"^  [SE] \[shape=(?:double)?circle\];$"),
    re.compile(r'^  "[^"]+" \[shape=(?:box, label="[^"]*"|diamond)\];$'),
    re.compile(r'^  (?:S|"[^"]+") -> (?:E|"[^"]+")'
               r'(?: \[(?:style=dashed)?(?:, )?(?:label="loop \d+")?\])?;$'),
    re.compile(r"^\}$"),
]

def _buildable_dots() -> dict:
    """to_dot() renderings of every graph the repo can build, keyed by a
    human-readable origin (for error messages)."""
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.core.patterns import PATTERNS
    from repro.store import plugins

    dots = {}
    for name, builder in (
        ("plugins.build_du_graph", plugins.build_du_graph),
        ("plugins.build_cp_graph", plugins.build_cp_graph),
        ("plugins.build_bptree_scan_graph", plugins.build_bptree_scan_graph),
        ("plugins.build_bptree_load_graph", plugins.build_bptree_load_graph),
        ("plugins.build_lsm_get_graph", plugins.build_lsm_get_graph),
    ):
        dots[name] = builder().to_dot()
    for name, builder in PATTERNS.items():
        dots[f"patterns.{name}"] = builder().to_dot()
    for name, mined in plugins.mine_reference_graphs().items():
        dots[f"mined.{name}"] = mined.graph.to_dot()
    return dots


def check_dot_blocks(path: str, get_dots) -> list:
    """Problems with the fenced dot blocks of one markdown file.
    ``get_dots`` is called lazily on the first block found, so files
    without dot blocks never pay the graph-building (or numpy) cost."""
    with open(path) as f:
        text = f.read()
    problems = []
    dots = None
    for i, m in enumerate(DOT_BLOCK_RE.finditer(text)):
        if dots is None:
            dots = get_dots()
        block = m.group(1).rstrip("\n")
        label = f"dot block #{i + 1}"
        for line in block.split("\n"):
            if not any(r.match(line) for r in DOT_LINE_RES):
                problems.append(f"{label}: unparseable line: {line!r}")
        if block not in dots.values():
            problems.append(
                f"{label}: matches no buildable graph's to_dot() "
                f"(known: {', '.join(sorted(dots))})"
            )
    return problems


def _fs_exists(parts) -> bool:
    base = os.path.join(REPO, "src", *parts)
    return os.path.isfile(base + ".py") or os.path.isdir(base)


def module_exists(dotted: str) -> bool:
    """Resolve ``repro[.module]*[.Symbol[.attr]*]`` against the importable
    tree: find the longest prefix that is a module/package on disk, import
    it, then getattr-walk the remainder.  ``repro.core.engine.SpecSession``
    dangles if the class is renamed; ``repro.core.api.io.pwrite`` dangles if
    the method is dropped — not just when whole files disappear."""
    parts = dotted.split(".")
    k = len(parts)
    while k > 1 and not _fs_exists(parts[:k]):
        k -= 1
    if not _fs_exists(parts[:k]):
        return False
    if k == len(parts):
        return True
    import importlib

    src = os.path.join(REPO, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        obj = importlib.import_module(".".join(parts[:k]))
    except Exception as e:  # import failure = the reference cannot resolve
        print(f"  (import {'.'.join(parts[:k])} failed: {e!r})")
        return False
    for attr in parts[k:]:
        if not hasattr(obj, attr):
            return False
        obj = getattr(obj, attr)
    return True


def check(path: str) -> list:
    with open(path) as f:
        text = f.read()
    missing = []
    for ref in sorted(set(PATH_RE.findall(text))):
        if not os.path.exists(os.path.join(REPO, ref)):
            missing.append(ref)
    for ref in sorted(set(MODULE_RE.findall(text))):
        if not module_exists(ref):
            missing.append(ref)
    return missing


def main(argv) -> int:
    files = argv or ["docs/ARCHITECTURE.md"]
    cache: dict = {}

    def get_dots() -> dict:
        if not cache:
            cache.update(_buildable_dots())
        return cache

    bad = 0
    for f in files:
        full = os.path.join(REPO, f)
        missing = check(full)
        for ref in missing:
            print(f"{f}: dangling reference: {ref}")
        problems = check_dot_blocks(full, get_dots)
        for p in problems:
            print(f"{f}: {p}")
        bad += len(missing) + len(problems)
    if bad:
        print(f"{bad} problem(s)")
        return 1
    print(f"ok: {len(files)} file(s), no dangling references, "
          f"all dot blocks match buildable graphs")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
