"""Every file a document names exists.

A back-quoted token that contains a ``/`` and ends in a source or record
suffix is a path; it must resolve against the repo root or the package
directory.  ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are left out:
they name files of past PRs on purpose.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "COVERAGE.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

# `<dir>/...` is a placeholder for a file a run writes, not a path
_PATH = re.compile(
    r"`([^`\s<>]*/[^`\s<>]*\.(?:py|json|jsonl|md|sh))(?::\d+)?`")


def named_paths(text):
    return sorted(set(_PATH.findall(text)))


def resolves(path):
    roots = (REPO, os.path.join(REPO, "deepspeed_tpu"))
    # `launcher/{runner,launch}.py` names one file per alternative
    m = re.search(r"\{([^{}]*)\}", path)
    if m:
        return all(resolves(path[:m.start()] + alt + path[m.end():])
                   for alt in m.group(1).split(","))
    return any(os.path.exists(os.path.join(r, path)) for r in roots)


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        paths = named_paths(f.read())
    missing = [p for p in paths if not resolves(p)]
    assert not missing, f"{doc} names files that do not exist: {missing}"
