"""What the repository records about itself.

How fast the system is stands in one place: ``BENCHMARK.json`` and
``perfbench/`` measure it on a TPU, the driver's ledger keeps the numbers,
``PERF.md`` accounts for them. A CPU run gives correctness and counts, so
no file in the tree records one as a speed; and the two documents that
describe the system as it is name only files that are there.
"""

import fnmatch
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a back-quoted token is read as a path when it ends in one of these or
# names a directory with a trailing slash
_SUFFIXES = (".py", ".md", ".json", ".jsonl", ".toml", ".ini", ".cc", ".sh")
_NOT_THE_TREE = {".git", "build", "chiprun_out", "__pycache__",
                 ".pytest_cache"}


def _quoted_paths(text):
    for token in re.findall(r"`([^`\n]+)`", text):
        path = token.split("::")[0].split(" ")[0]
        path = re.sub(r":\d+(-\d+)?$", "", path)
        if not re.fullmatch(r"[\w./*-]+", path):
            continue
        if path.startswith(("/", ".")):
            continue            # a URL route, a relative import
        if path.endswith(_SUFFIXES) or path.endswith("/") and "/" in path:
            yield path


@pytest.fixture(scope="module")
def tree():
    """Every file and directory of the checkout under each of its short
    forms: ``serving/scheduler.py`` and ``scheduler.py`` both name
    ``paddle_tpu/serving/scheduler.py``."""
    names = set()
    for top, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _NOT_THE_TREE]
        rel = os.path.relpath(top, REPO).split(os.sep)
        rel = [] if rel == ["."] else rel
        for i in range(len(rel)):
            names.add("/".join(rel[i:]) + "/")
        for f in files:
            parts = rel + [f]
            for i in range(len(parts)):
                names.add("/".join(parts[i:]))
    return names


def test_no_cpu_speed_records_tracked():
    assert not glob.glob(os.path.join(REPO, "BENCH_*"))


@pytest.mark.parametrize("doc", ["README.md", "COMPONENTS.md"])
def test_system_documents_name_files_that_exist(doc, tree):
    with open(os.path.join(REPO, doc)) as f:
        named = sorted(set(_quoted_paths(f.read())))
    assert len(named) > 20, named
    missing = [p for p in named
               if p not in tree and not fnmatch.filter(tree, p)]
    assert not missing, f"{doc} names files that are not in the tree"
