"""Every repo file a document names is there.

The README, ``docs/*.md`` and the verify skill name files in code spans and
fenced commands; a file that was deleted or renamed leaves them pointing at
nothing, and nothing else notices.  A named path passes when it is a repo
path or the tail of one (``serve/engine.py`` for
``relora_tpu/serve/engine.py``); placeholders (``<cell>``, ``*``, ``{a,b}``)
match as wildcards.  Stdlib only.
"""

import fnmatch
import functools
import os
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
DOCS = sorted(
    str(p.relative_to(REPO))
    for p in [REPO / "README.md", *(REPO / "docs").glob("*.md"), REPO / ".claude/skills/verify/SKILL.md"]
    if p.exists()
)

# by file name: what a run writes or a user supplies, the reference
# implementation's own entry point, and a placeholder
NOT_REPO_FILES = (
    "flight_*.json",
    "training_state.json",
    "relora_config.json",
    "config.json",
    "manifest.json",
    "prune_meta.json",
    "canary.json",
    "peers.json",
    "slo.json",
    "OUT.json",
    "torchrun_main.py",
    "file.py",
)

_SPAN = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_PATH = re.compile(r"(?<![\w./*<>{}-])((?:[\w.*<>{},-]+/)*[\w.*<>{},-]+\.(?:py|sh|json|md))(?![\w/])")


@functools.lru_cache(maxsize=None)
def _repo_files():
    files = []
    for root, dirs, names in os.walk(REPO):
        rel = os.path.relpath(root, REPO)
        dirs[:] = [
            d for d in dirs
            if d not in ("__pycache__", "chiprun_out") and (not d.startswith(".") or d == ".claude")
        ]
        files += [os.path.normpath(os.path.join(rel, n)) for n in names]
    return tuple(files)


def _as_glob(path: str) -> str:
    path = re.sub(r"<[^>]*>", "*", path)
    return re.sub(r"\{[^}]*\}", "*", path)


def named_paths(text: str):
    for span in _SPAN.findall(text):
        for m in _PATH.finditer(span):
            yield m.group(1)


@pytest.mark.parametrize("doc", DOCS)
def test_every_named_file_exists(doc):
    files = _repo_files()
    missing = []
    for path in sorted(set(named_paths((REPO / doc).read_text()))):
        pattern = _as_glob(path)
        if path.startswith((".bench_work/", "chiprun_out/")):
            continue
        if any(fnmatch.fnmatch(os.path.basename(pattern), name) for name in NOT_REPO_FILES):
            continue
        if not any(fnmatch.fnmatch(f, pattern) or fnmatch.fnmatch(f, "*/" + pattern) for f in files):
            missing.append(path)
    assert not missing, f"{doc} names files that are not in the repo: {missing}"


def test_the_documents_are_found():
    assert "README.md" in DOCS and ".claude/skills/verify/SKILL.md" in DOCS
    assert sum(d.startswith("docs/") for d in DOCS) >= 8
