"""The documents a newcomer reads first name only files that exist."""

import functools
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).parent.parent

# paths a document names on purpose though they are not in the tree
NAMED_BUT_ABSENT = {
    "README.md": {
        # "Tests": the file PR 34 deleted, named to date the 859 s run
        "tests/test_bench_configs.py",
        # a user's own output files, in the dump CLI's examples
        "serve_trace.json", "fleet_trace.json", "timeline.json",
    },
    "MAPPING.md": set(),
}


def _named_paths(text: str) -> set:
    """What looks like a path into this repository: any word ending in
    ``.py``, ``.md`` or ``.json`` (a ``:line`` or ``::test`` suffix
    dropped), backticked or not, alone or inside a command; and a
    backticked or linked directory ending in ``/``."""
    paths = set()
    for word in re.findall(r"[\w./-]+", text):
        word = re.sub(r"(\.(?:py|md|json)):.*$", r"\1", word.rstrip("."))
        if re.fullmatch(r"[\w./-]+\.(py|md|json)", word):
            paths.add(word)
    spans = re.findall(r"`([^`\s]+)`", text)
    spans += re.findall(r"\]\(([^)#\s]+)\)", text)
    paths |= {t for t in spans if re.fullmatch(r"[\w.-]+(/[\w.-]+)*/", t)}
    return paths


# where a bare file name may live (not build outputs, not a second
# copy of the repo unpacked for a chip run)
TREES = ("paddle_tpu", "tests", "benchmarks", "chipbench", "examples")


@functools.cache
def _source_file_names() -> frozenset:
    return frozenset(f.name for t in TREES for f in (ROOT / t).rglob("*.*"))


def _exists(path: str) -> bool:
    """From the root or from the package (``kernels/ssd.py``); a bare
    file name (``serving.py``) anywhere in the source trees."""
    if "/" not in path:
        return (ROOT / path).exists() or path in _source_file_names()
    return (ROOT / path).exists() or (ROOT / "paddle_tpu" / path).exists()


@pytest.mark.parametrize("doc", sorted(NAMED_BUT_ABSENT))
def test_document_names_files_that_exist(doc):
    named = _named_paths((ROOT / doc).read_text())
    assert len(named) > 20, "the pattern finds the document's paths"
    absent = {p for p in named if not _exists(p)}
    assert absent == NAMED_BUT_ABSENT[doc], absent ^ NAMED_BUT_ABSENT[doc]
