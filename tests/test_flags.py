"""The flag registry carries no flag that nothing reads."""

import pathlib
import re

PACKAGE = pathlib.Path(__file__).parent.parent / "paddle_tpu"

# flags found without a reader, each with the reason it is kept
# (ROADMAP.md D6 names them); empty as of PR 34
NO_READER_ALLOWED: dict = {}


def test_every_flag_has_a_reader():
    """Each ``define_flag`` name is the argument of a ``flag(...)`` read
    somewhere in the package outside ``flags.py``."""
    sources = {f: f.read_text() for f in PACKAGE.rglob("*.py")}
    defined = [n for s in sources.values()
               for n in re.findall(r'define_flag\(\s*"(\w+)"', s)]
    assert len(defined) > 40 and len(set(defined)) == len(defined)
    read = {n for f, s in sources.items() if f != PACKAGE / "flags.py"
            for n in re.findall(r'\bflag\(\s*["\'](\w+)["\']', s)}
    unread = sorted(set(defined) - read - set(NO_READER_ALLOWED))
    assert not unread, unread
    assert not set(NO_READER_ALLOWED) & read, "allow-list entry is read"
