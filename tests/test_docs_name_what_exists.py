"""What the repo says of itself names files that exist: every
``tools/<name>.py`` a text mentions, and every bare name of the forms
``bench*.py``, ``chip_smoke.py``, ``BENCH_*.json`` / ``.jsonl``, is in
the tree.  A tool that is deleted takes its mentions with it, or this
fails and names them.  ``CHANGES.md``, ``ROADMAP.md`` and ``PERF.md``
are history and are not read."""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCANNED = {
    "README.md": ["README.md"],
    "mxnet_tpu": ["mxnet_tpu/**/*.py"],
    "tests": ["tests/*.py"],
    "examples": ["examples/*.py"],
    "tools": ["tools/*.py"],
    "chip_smoke.py": ["chip_smoke.py"],
}

TOOL = re.compile(r"(?<![\w/])tools/(\w+)\.py\b")
# a bare name (no directory before it) of one of the measuring forms
BARE = re.compile(
    r"(?<![\w/.])(bench\w*\.py|chip_smoke\.py|BENCH_\w+\.jsonl?)\b")
# where a bare name may live: the root, or beside the other scripts
BARE_HOMES = ("", "tools", "examples")


def missing(text):
    """The names ``text`` mentions that the tree does not hold."""
    out = set()
    for name in TOOL.findall(text):
        if not os.path.isfile(os.path.join(ROOT, "tools", name + ".py")):
            out.add(f"tools/{name}.py")
    for name in BARE.findall(text):
        if not any(os.path.isfile(os.path.join(ROOT, home, name))
                   for home in BARE_HOMES):
            out.add(name)
    return out


@pytest.mark.parametrize("group", sorted(SCANNED))
def test_every_named_tool_and_record_exists(group):
    paths = sorted(p for pattern in SCANNED[group] for p in glob.glob(
        os.path.join(ROOT, pattern), recursive=True))
    assert paths, f"nothing to read for {group}"
    gone = {}
    for path in paths:
        with open(path, encoding="utf-8") as f:
            names = missing(f.read())
        if names:
            gone[os.path.relpath(path, ROOT)] = sorted(names)
    assert not gone, f"names of files that are not in the tree: {gone}"


def test_the_check_sees_a_name_that_is_gone():
    gone_tool = "tools/" + "no_such_tool.py"
    gone_bench = "bench" + "_gone.py"
    gone_record = "BENCH" + "_GONE.jsonl"
    text = (f"run `python {gone_tool}`, then {gone_bench}; see "
            f"{gone_record}, tools/launch.py, chip_smoke.py, "
            f"benchmark/run.py and the reference's src/tools/im2rec.py")
    assert missing(text) == {gone_tool, gone_bench, gone_record}


def test_every_test_file_builds_its_engines_through_the_shared_helper():
    """The rule that keeps tier-1 inside its clock (``tests/_engines.py``):
    no ``tests/test_*.py`` constructs a ``DecodeEngine`` itself or
    defines an engine helper of its own — it goes through ``build`` (or
    ``dense_engine`` / ``Family.engine``, which do), so that conftest's
    ``engines`` can share what is built and closes what is left open."""
    built = re.compile(r"\bDecodeEngine\(")
    own = re.compile(r"^def (make_engine|served_gap|watch_slots)\(", re.M)
    at_fault = {}
    for path in sorted(glob.glob(os.path.join(ROOT, "tests", "test_*.py"))):
        if os.path.basename(path) == os.path.basename(__file__):
            continue
        with open(path, encoding="utf-8") as f:
            text = f.read()
        found = built.findall(text) + own.findall(text)
        if found:
            at_fault[os.path.relpath(path, ROOT)] = found
    assert not at_fault, (
        f"engines built outside tests/_engines.py: {at_fault}")
    with open(os.path.join(ROOT, "tests", "_engines.py"),
              encoding="utf-8") as f:
        assert len(built.findall(f.read())) == 1     # ``build`` alone
