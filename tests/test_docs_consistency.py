"""The documents name what the tree holds: every flag has its README row
and every row its flag; every file README, Makefile and
tools/build_and_test.sh point at exists. (Reads sources only: flags other
tests define at run time do not count.)"""
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
