"""What the tree promises about itself: the package imports nothing
from beside or above it, and the documents name only files that exist."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_package_imports_nothing_from_the_repo_root():
    """No module under tendermint_tpu/ imports a root-level script, the
    benchmark, scripts/ or tests/: the arrows point into the package."""
    from tendermint_tpu.analysis.flowgraph import FlowGraph

    outside = {name[:-3] for name in os.listdir(REPO)
               if name.endswith(".py")}
    outside |= {name for name in os.listdir(REPO)
                if name != "tendermint_tpu" and
                glob.glob(os.path.join(REPO, name, "*.py"))}
    assert {"bench_util", "chip_smoke", "benchmark"} <= outside
    graph = FlowGraph.build(REPO, paths=["tendermint_tpu"])
    assert len(graph.modules) > 100 and not graph.parse_errors
    wrong = sorted((mod.rel, target)
                   for mod in graph.modules.values()
                   for target in mod.imports.values()
                   if target.split(".")[0] in outside)
    assert wrong == []


DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
_QUOTED = re.compile(r"`([^`\n]+)`|\]\(([^)\s]+)\)")
# a placeholder, a pattern, a URL or a path outside the checkout
_NOT_A_REPO_PATH = re.compile(r"[<>*${}~]|\.\.\.|^https?:|^/")


def quoted_paths(text: str):
    """Every word in backticks or in a link's target that ends like a
    path: `a/b.py:12-40`, `python a.py --flag`, [x](docs/a.md#part)."""
    for m in _QUOTED.finditer(text):
        for word in (m.group(1) or m.group(2)).split():
            if _NOT_A_REPO_PATH.search(word):
                continue
            word = word.split("#")[0].split("::")[0]
            word = re.sub(r":[\d,:-]+$", "", word.rstrip(",;:."))
            if word.endswith((".py", ".json", ".md", "/")):
                yield word


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_that_exist(document):
    """A path may be written from the root of the checkout, from the
    package (`ops/ed25519.py`) or from the document's own directory."""
    roots = (REPO, os.path.join(REPO, "tendermint_tpu"),
             os.path.dirname(os.path.join(REPO, document)))
    with open(os.path.join(REPO, document)) as f:
        named = set(quoted_paths(f.read()))
    missing = sorted(p for p in named if not any(
        os.path.exists(os.path.join(root, p)) for root in roots))
    assert missing == []
