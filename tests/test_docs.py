"""The documents name files that exist.

A deletion that leaves a mention behind, or a module that moved, sends a
reader to nothing: every file a document names in backticks must be found
under the repo root, ``torrent_tpu/``, ``benchmark/`` or ``tests/``.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASES = ("", "torrent_tpu", "benchmark", "tests")
# a backticked token that ends in a source or record suffix, with an
# optional `:line`, `::test` or ` args` tail inside the same backticks
_NAMED = re.compile(r"`([\w./-]+\.(?:py|json|jsonl|md|cpp|toml|sh))(?:[: ][^`\n]*)?`")


def _named_files(text: str) -> set[str]:
    return {m.group(1) for m in _NAMED.finditer(text) if not os.path.isabs(m.group(1))}


@pytest.mark.parametrize("doc", ["README.md", "ARCHITECTURE.md", "BASELINE.md"])
def test_every_file_a_document_names_exists(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        names = _named_files(f.read())
    assert names, f"{doc} names no file: the pattern no longer matches"
    missing = sorted(
        n for n in names
        if not any(os.path.exists(os.path.join(ROOT, base, n)) for base in BASES)
    )
    assert not missing, f"{doc} names files that do not exist: {missing}"
