"""Documents cite files that exist.

Every backticked token in README.md and docs/*.md that looks like a path
into this repo must name a file or directory of the checkout: a document
that points at a deleted benchmark, record or script misleads the next
reader (PERF.md, ROADMAP.md and CHANGES.md narrate deleted files by
design and are not scanned).
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

SOURCE_DIRS = ("apex_tpu", "benchmarks", "tests", "scripts", "examples",
               "docs")

#: a path into this repo: under one of its directories, or a bare file
#: name; a ``:line`` or ``::test`` suffix is stripped first
_INTO_REPO = re.compile(
    r"^(?:(?:" + "|".join(SOURCE_DIRS) + r")/[\w./-]+"
    r"|[\w.-]+\.(?:py|json|md|sh))$")


def cited_paths(text):
    for token in re.findall(r"`([^`\s]+)`", text):
        token = re.sub(r":[:\w\[\],.-]*$", "", token)
        if "*" not in token and _INTO_REPO.match(token):
            yield token.rstrip("/.")


@pytest.fixture(scope="module")
def names():
    """Every file name under the source directories: a bare name in a
    document is the root's, or shorthand for a module the text around it
    places (``overlap.py``), and must exist somewhere."""
    return {f for d in SOURCE_DIRS
            for _, _, files in os.walk(os.path.join(ROOT, d)) for f in files}


@pytest.mark.parametrize("document", DOCUMENTS)
def test_cited_paths_exist(document, names):
    with open(os.path.join(ROOT, document)) as f:
        cited = sorted(set(cited_paths(f.read())))
    missing = [p for p in cited
               if not os.path.exists(os.path.join(ROOT, p))
               and ("/" in p or p not in names)]
    assert not missing, f"{document} cites files that are gone: {missing}"
