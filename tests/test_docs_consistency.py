"""The documents name what the tree holds: every flag has its README row
and every row its flag; every file README, Makefile and
tools/build_and_test.sh point at exists; and the two documents a session
must read before it writes a line stay of a size it can read. (Reads
sources only: flags other tests define at run time do not count.)"""
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", "Makefile", os.path.join("tools", "build_and_test.sh"))


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


def _defined_flags():
    return set(re.findall(r'^define_flag\(\s*"(\w+)"',
                          _read(os.path.join("paddle_tpu", "flags.py")),
                          re.M))


def _readme_flag_rows():
    text = _read("README.md")
    start = text.index("## Runtime flags")
    section = text[start:text.index("\n## ", start + 1)]
    return re.findall(r"^\| `(\w+)` \|", section, re.M)


def test_every_flag_has_a_readme_row():
    missing = sorted(_defined_flags() - set(_readme_flag_rows()))
    assert not missing, f"no row under README '## Runtime flags': {missing}"


def test_every_readme_flag_row_names_a_defined_flag():
    rows = _readme_flag_rows()
    assert len(rows) == len(set(rows)), "a flag has two rows"
    stale = sorted(set(rows) - _defined_flags())
    assert not stale, f"README rows for flags flags.py does not define: {stale}"


@pytest.mark.parametrize("doc", DOCS)
def test_every_file_a_document_names_exists(doc):
    text = _read(doc)
    # tools/x.py, tests/x.py::node, examples/x.py and `python <file>`;
    # a glob (tests/test_*.py) or a word with no extension is not a path
    named = set(re.findall(r"\b((?:tools|tests|examples)/[\w./-]*\.\w+)", text))
    named |= set(re.findall(r"\bpython3? ([\w./-]+\.py)\b", text))
    assert named, f"{doc}: the pattern found no path at all"
    missing = sorted(p for p in named
                     if not os.path.exists(os.path.join(REPO, p)))
    assert not missing, f"{doc} names files that do not exist: {missing}"


def test_perf_md_stays_readable_in_one_session():
    """PERF.md is read whole by every session: at PR 47 it was 268,945
    bytes in lines of up to 14.5 KB and the file tool refused it. When
    Findings outgrows this, merge the oldest entries and leave the text
    to git (`git show <commit>:PERF.md`)."""
    raw = _read("PERF.md")
    assert len(raw.encode("utf-8")) <= 200_000
    longest = max(raw.split("\n"), key=len)
    assert len(longest) <= 4_000, longest[:120]


def test_verify_skill_stays_a_procedure():
    """.claude/skills/verify/SKILL.md is a procedure, not a log that
    every PR appends to (904 lines at PR 47)."""
    lines = _read(os.path.join(".claude", "skills", "verify",
                               "SKILL.md")).splitlines()
    assert len(lines) <= 400
