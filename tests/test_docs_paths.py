"""The documents name files that exist: every back-ticked path in
``README.md`` and ``docs/*.md`` that starts with a directory of this
repo, or names a ``*.py``/``*.json``/``*.md`` file without one, is
there. A document that sends its reader to a deleted benchmark, record
or tool fails here."""

import fnmatch
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs",
                                                             "*.md")))


def _tree():
    """Every file and directory under the root, relative to it; hidden
    and generated directories left out (a walk, not ``git ls-files``: a
    checkout need not hold .git)."""
    present = set()
    for d, subs, fs in os.walk(ROOT):
        subs[:] = [x for x in subs if not x.startswith(".")
                   and x not in ("__pycache__", "chiprun_out")]
        present.update(os.path.relpath(os.path.join(d, x), ROOT)
                       for x in subs + fs)
    return present


PRESENT = _tree()
TOP_DIRS = {p for p in PRESENT if os.path.isdir(os.path.join(ROOT, p))
            and "/" not in p}
BASENAMES = {os.path.basename(p) for p in PRESENT}

#: paths of upstream MXNet's tree (the reference this repo mirrors), and
#: the library that ``make -C cxx`` builds and git ignores
ELSEWHERE = {
    "benchmark/opperf", "docs/static_site/src/pages/api/faq/env_var.md",
    "cxx/libmxtpu.so",
}

_TICK = re.compile(r"`([^`\s]+)`")
_FILE = re.compile(r"[\w.*-]+\.(py|json|md)")


def _named_paths(text):
    for tok in _TICK.findall(text):
        tok = re.sub(r":\d+(-\d+)?$", "", tok.split("::")[0].rstrip(".,:;)"))
        if any(c in tok for c in "<>{}$=") or tok in ELSEWHERE:
            continue
        if ("/" in tok and tok.split("/")[0] in TOP_DIRS) \
                or _FILE.fullmatch(tok):
            yield tok


def _exists(tok):
    if "*" in tok:
        return any(fnmatch.fnmatch(f, tok) for f in PRESENT)
    if "/" not in tok:  # a bare file name: at the root, or some module's
        return tok in BASENAMES
    return tok.rstrip("/") in PRESENT


@pytest.mark.parametrize("doc", DOCS)
def test_backticked_paths_exist(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        missing = sorted({t for t in _named_paths(f.read())
                          if not _exists(t)})
    assert not missing, f"{doc} names paths that do not exist: {missing}"
