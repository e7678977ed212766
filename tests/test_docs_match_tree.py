"""The documents name files that exist, and none that this repo deleted.

One case a document. Every backticked token that ends in ``.py``, ``.md``
or ``.json`` and holds no ``<``, ``*`` or ``{`` must be the tail of some
path ``git ls-files`` prints for the working tree (a suffix match on whole path components, so
``models/serving.py`` resolves to ``paddle_tpu/models/serving.py``). A path
a document names that does not exist is corrected in the document, never
excused here. ``CHANGES.md``, ``PERF.md`` and ``ROADMAP.md`` are records with
history in them and are not cases.
"""
import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md", "BASELINE.md", "MIGRATION.md",
             ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir(os.path.join(ROOT, "docs"))
    if f.endswith(".md"))

# the pre-chip bench stack and what only it kept alive (deleted in PR 34)
GONE = re.compile(r"\bbench\.py\b|bench_common|bench_suite|StaticBatchEngine"
                  r"|prefill_buckets|BENCH_[A-Z]")

_TOKEN = re.compile(r"`([^`\s]+\.(?:py|md|json))`")


@pytest.fixture(scope="module")
def tracked():
    """What git tracks or would track (a PR's new files are not in the
    index before ``git add``), less what the working tree has deleted."""
    out = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
        cwd=ROOT, capture_output=True, text=True, check=True).stdout
    return ["/" + p for p in out.splitlines()
            if os.path.exists(os.path.join(ROOT, p))]


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_files_that_exist(doc, tracked):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        text = f.read()
    gone = sorted({m.group(0) for m in GONE.finditer(text)})
    assert not gone, f"{doc} names what PR 34 deleted: {gone}"
    missing = sorted(
        tok for tok in {m.group(1) for m in _TOKEN.finditer(text)}
        if not set("<*{") & set(tok)
        and not any(p.endswith("/" + tok.removeprefix("./"))
                    for p in tracked))
    assert not missing, f"{doc} names files git does not track: {missing}"
