"""Every repo path a document names exists.

The documents are what a new session reads first; a path that no longer
exists sends it to code that is gone.  ``ROADMAP.md`` and ``CHANGES.md``
are history and are not scanned, nor are files under ``benchmark/``.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md", "PERF.md", "PARITY.md",
             ".claude/skills/verify/SKILL.md"] + sorted(
    glob.glob("docs/*.md", root_dir=REPO))

# a path under one of the repo's directories, or one of the root's
# measuring files (gone since PR 29, so naming one is always stale)
_PATH = re.compile(
    r"(?<![\w./-])("
    r"(?:byteps_tpu|benchmark|tests|scripts|docs|examples|csrc|docker)"
    r"/[\w./-]*\.(?:py|md|json|cc|h|sh|textproto)"
    r"|bench\w*\.py|chip_smoke\.py|BENCH_\w+\.json"
    r")(?![\w/-])")


def named_paths(text):
    return sorted({m.group(1) for m in _PATH.finditer(text)})


def test_pattern_finds_what_it_should():
    text = ("see `byteps_tpu/training/step.py:12`, docs/env.md and "
            "bench_old.py; BENCH_OLD1.json; `benchmark/run.py --x`; "
            "not workbench.py, not https://x.org/docs/a.md.")
    assert named_paths(text) == [
        "BENCH_OLD1.json", "bench_old.py", "benchmark/run.py",
        "byteps_tpu/training/step.py", "docs/env.md"]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_named_paths_exist(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        paths = named_paths(f.read())
    missing = [p for p in paths
               if not os.path.exists(os.path.join(REPO, p))]
    assert not missing, f"{document} names paths that do not exist: {missing}"
