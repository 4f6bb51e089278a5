"""The two readers of PR 36 on a small trace recorded on the chip with
the tables of the programs in it (``data/scope_trace_small.json.gz``:
the doc cell, one prefill and the decode steps round it)."""

import copy
import gzip
import json
import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.reducers import program_share, scope_time

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "scope_trace_small.json.gz")
CLASSES = ("kernel", "collective", "matmul", "relayout", "other")


@pytest.fixture
def recorded(monkeypatch):
    """(sources, tables): the trace, and ``profiler.program_scopes``
    answering from the recorded tables."""
    with gzip.open(DATA, "rt") as f:
        data = json.load(f)
    from mxnet_tpu import profiler

    tables = data["tables"]
    monkeypatch.setattr(profiler, "program_scopes", lambda: tables,
                        raising=False)
    scope_time._booked.clear()
    return {"trace": tr.Trace(copy.deepcopy(data["planes"]))}, tables


def test_classes_and_gaps_sum_to_the_whole_executions(recorded, capfd):
    sources, tables = recorded
    for program in ("jit_prefill", "jit_step_decode", "jit_"):
        shares = [scope_time.read(sources, program, klass=k)
                  for k in CLASSES]
        assert all(s is not None and s >= 0 for s in shares)
        rows = [r for n, r in scope_time.book(sources["trace"]).items()
                if program in n]
        covered = 100.0 * sum(r["covered"] for r in rows) / \
            sum(r["seconds"] for r in rows)
        assert sum(shares) == pytest.approx(covered, abs=1e-6)
        assert 90.0 < covered <= 100.0
    assert scope_time.read(sources, "jit_prefill", klass="matmul") > 30
    assert scope_time.read(sources, "jit_prefill", klass="kernel") > 1
    assert scope_time.read(sources, "jit_", unnamed=True) < 20
    assert scope_time.read(sources, "jit_prefill", optimizer=True) == 0
    # one line a program, once a run, with the longest groups
    lines = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()
             if ln.startswith('{"scope_time"')]
    assert sorted(ln["scope_time"] for ln in lines) == sorted(
        scope_time.book(sources["trace"]))
    for ln in lines:
        assert 0 < len(ln["by_group"]) <= scope_time.TOP
        assert sum(ln["by_class"].values()) == pytest.approx(
            ln["ms_per_execution"], rel=1e-3)
        assert ln["by_group"] == sorted(ln["by_group"],
                                        key=lambda row: -row[2])
        # the longest operations outside the kernels, with what each
        # is made of: the heavy opcodes first
        assert 0 < len(ln["by_op"]) <= scope_time.TOP
        for key, group, klass, ms, opcodes in ln["by_op"]:
            assert klass != "kernel" and 0 < len(opcodes) <= 5
    prefill = next(ln for ln in lines if "jit_prefill" in ln["scope_time"])
    assert any(row[4][0] == "convolution" and row[2] == "matmul"
               for row in prefill["by_op"])
    # a group's row says what most of its time is made of: doc's
    # prefill writes its K/V pages by a scatter under layer*_attn
    assert ["layer*_attn", "other"] in [r[:2] for r in prefill["by_group"]]
    assert all(r[3][0] == "scatter" for r in prefill["by_group"]
               if r[:2] == ["layer*_attn", "other"])


def test_prefill_share_and_the_rest_are_the_whole(recorded):
    sources, _ = recorded
    prefill = program_share.read(sources, ["jit_prefill"])
    rest = program_share.read(sources, ["jit_step_decode",
                                        "jit_next_tokens"])
    assert 0 < prefill < 100
    assert prefill + rest == pytest.approx(100.0)
    assert program_share.read({"trace": None}, ["jit_prefill"]) is None


def test_operation_outside_every_whole_execution_is_not_booked(recorded):
    sources, _ = recorded
    before = scope_time.read(sources, "jit_", klass="matmul")
    plane = sources["trace"].device_planes()[0]
    lines = sources["trace"].planes[plane]
    first = min(s for _, s, _ in lines[tr.MODULES_LINE])
    name = next(n for n, _, _ in lines[tr.OPS_LINE] if "fusion" in n)
    # planted before the first program: inside the window, in no run
    lines[tr.OPS_LINE].append([name, first - 900.0, 800.0])
    scope_time._booked.clear()
    assert scope_time.read(sources, "jit_", klass="matmul") == \
        pytest.approx(before, abs=1e-9)
    # a program cut by the window's end is no whole execution either
    s, d = max((s, d) for _, s, d in lines[tr.MODULES_LINE])
    lines[tr.MODULES_LINE].append(["jit_step_train(1)", s + d + 10.0,
                                   1e9])
    scope_time._booked.clear()
    assert "jit_step_train" not in scope_time.book(sources["trace"])


def test_program_without_a_table_reads_wholly_unnamed(recorded):
    sources, tables = recorded
    del tables[next(n for n in tables if n.startswith("jit_prefill"))]
    assert scope_time.read(sources, "jit_prefill", unnamed=True) == \
        pytest.approx(100.0, abs=1.0)
    assert scope_time.read(sources, "jit_prefill", klass="matmul") == 0
    assert scope_time.read(sources, "jit_step_decode", unnamed=True) < 20


def test_parent_without_program_scopes_reads_nothing(recorded,
                                                      monkeypatch):
    sources, _ = recorded
    from mxnet_tpu import profiler

    monkeypatch.delattr(profiler, "program_scopes")
    scope_time._booked.clear()
    assert scope_time.read(sources, "jit_prefill", klass="matmul") is None
    assert scope_time.read(sources, "jit_", unnamed=True) is None
    assert scope_time.read({"trace": None}, "jit_") is None
    # the share of programs needs no table
    assert program_share.read(sources, ["jit_prefill"]) is not None


@pytest.mark.parametrize("events, own", [
    ([("a", 0, 10), ("b", 2, 5), ("c", 6, 8)], {"a": 5, "b": 3, "c": 2}),
    ([("a", 0, 10), ("b", 10, 20)], {"a": 10, "b": 10}),
    ([("w", 0, 100), ("f", 10, 60), ("g", 20, 30)],
     {"w": 50, "f": 40, "g": 10}),
])
def test_self_seconds_of_nested_events(events, own):
    got = dict(scope_time.self_seconds(events))
    assert got == pytest.approx({k: v / 1e9 for k, v in own.items()})


def test_event_name_to_table_key():
    assert scope_time.op_key("%fusion.229") == "fusion.229"
    assert scope_time.op_key(
        "%flash_fwd_packed.3 [tpu_custom_call bf16[1,1024,3840]]") == \
        "flash_fwd_packed.3"
    assert scope_time.program_name("jit_prefill_t1024(928504)") == \
        "jit_prefill_t1024"
