"""A program's device time by the names the program gave its
operations: the share of whole executions spent in operations of one
class, in optimizer updates, or in operations nobody named.

Clock: every ``XLA Ops`` event of the first chip is booked to the WHOLE
``XLA Modules`` execution that contains it (the walk
``spec_kernel_roofline.kernel_seconds_in`` does, for every operation at
once), at its self time — an operation that encloses others (a
``while`` round its body's) keeps what they leave.  Names:
``mxnet_tpu.profiler.program_scopes()``, {program: {instruction:
record}} built from the text of the executables the engine or the
module holds when this asks (``mxnet_tpu.hlo.scope_table``: ``scope``,
``group``, ``opcodes``, ``klass``, ``optimizer``); an event's key is its
name without ``%`` and without the kernel tag.

``read`` = 100 x (device time of the operations that match) / (device
time of the whole executions of the programs whose name holds
``program``).  ``klass`` picks a class (``kernel``, ``collective``,
``matmul``, ``relayout``, ``other``), ``optimizer`` the records under
``optimizer_update``, ``unnamed`` the operations whose record has no
scope or that the table lacks (a program without a table reads 100).
One line a program is logged, once a run: what PERF.md's tables of
"where the time goes" are made from — ``by_class`` (ms an execution;
``optimizer_by_class``: the part of it in operations that hold an
optimizer update), ``by_group`` ([group, class, ms, the opcodes most of
it is made of]: the 15 longest) and ``by_op`` ([instruction, group,
class, ms, opcodes]: the 15 longest operations outside the kernels).
``None`` without a trace, without tables (a program from before
``program_scopes``) or without a whole execution."""

from benchmark import harness
from benchmark import trace_reduce as tr

TOP = 15
# what an operation is made of, the opcodes that cost first
_HEAVY = ("convolution", "dot", "scatter", "gather", "sort",
          "dynamic-update-slice", "dynamic-slice", "reduce",
          "reduce-window", "select-and-scatter", "transpose", "copy",
          "reshape", "concatenate", "pad", "slice", "custom-call")
_booked = []    # [trace, {program: booking}, tables] of the last trace


def op_key(event_name):
    return event_name.split(tr.KERNEL_TAG, 1)[0].lstrip("%")


def program_name(module_event):
    return module_event.split("(", 1)[0]


def self_seconds(events):
    """[(name, seconds)] of (name, start, end) events on one line: an
    event's length less the events nested in it."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [(b - a) / 1e9 for _, a, b in events]
    open_ = []
    for i, (_, a, b) in enumerate(events):
        while open_ and events[open_[-1]][2] <= a:
            open_.pop()
        if open_ and b <= events[open_[-1]][2]:
            own[open_[-1]] -= (b - a) / 1e9
        open_.append(i)
    return [(e[0], max(0.0, t)) for e, t in zip(events, own)]


def book(trace):
    """{program: {"executions", "seconds", "covered", "ops": {key:
    seconds}}} over the whole executions of the first chip."""
    plane = trace.device_planes()[0]
    lo, hi = trace.window()
    runs = sorted((s, s + d, program_name(name)) for name, s, d in
                  trace.planes[plane].get(tr.MODULES_LINE, [])
                  if s >= lo and s + d <= hi)
    out, inside, at = {}, [[] for _ in runs], 0
    for name, a, b in sorted(trace.ops(plane), key=lambda e: e[1]):
        while at < len(runs) and runs[at][1] < a:
            at += 1
        if at < len(runs) and runs[at][0] <= a and b <= runs[at][1]:
            inside[at].append((name, a, b))
    for (s, e, program), events in zip(runs, inside):
        row = out.setdefault(program, {"executions": 0, "seconds": 0.0,
                                       "covered": 0.0, "ops": {}})
        row["executions"] += 1
        row["seconds"] += (e - s) / 1e9
        row["covered"] += tr.union_seconds(
            [(a, b) for _, a, b in events]) / 1e9
        for name, t in self_seconds(events):
            key = op_key(name)
            row["ops"][key] = row["ops"].get(key, 0.0) + t
    return out


def label(key, record):
    """A row of ``by_group``: the record's group, or — where nobody
    named the operation — its own name without the number."""
    if record is not None and record["group"]:
        return record["group"]
    return "(" + key.split(".", 1)[0] + ")"


def made_of(record, most=5):
    """The opcodes a row of ``by_op`` names: the heavy ones a record
    has, then the rest of it, ``most`` in all."""
    if record is None:
        return []
    ops = record["opcodes"]
    return ([o for o in _HEAVY if o in ops]
            + [o for o in ops if o not in _HEAVY])[:most]


def log_program(program, row, table):
    n, records = row["executions"], table or {}
    ms = lambda t: round(1e3 * t / n, 4)  # noqa: E731
    klass_of = lambda key: (records.get(key) or {}).get(  # noqa: E731
        "klass", "unknown")
    by_class, by_group, made, updates, unscoped = {}, {}, {}, {}, 0.0
    for key, t in row["ops"].items():
        rec, klass = records.get(key), klass_of(key)
        by_class[klass] = by_class.get(klass, 0.0) + t
        pair = (label(key, rec), klass)
        by_group[pair] = by_group.get(pair, 0.0) + t
        # what the group's time is made of: the kind that took most
        kinds = made.setdefault(pair, {})
        kind = tuple(made_of(rec))
        kinds[kind] = kinds.get(kind, 0.0) + t
        if rec is None or not rec["scope"]:
            unscoped += t
        elif rec["optimizer"]:  # an update, or what one is fused into
            updates[klass] = updates.get(klass, 0.0) + t
    by_class["no_operation"] = max(0.0, row["seconds"] - row["covered"])
    # the kernels have names of their own: the longest of the rest
    longest = sorted(((key, t) for key, t in row["ops"].items()
                      if klass_of(key) != "kernel"),
                     key=lambda kv: -kv[1])[:TOP]
    harness.log(
        scope_time=program, executions=n,
        ms_per_execution=ms(row["seconds"]),
        by_class={k: ms(t) for k, t in sorted(by_class.items())},
        by_group=[[g, k, ms(t), list(max(made[g, k], key=made[g, k].get))]
                  for (g, k), t in sorted(
                      by_group.items(), key=lambda kv: -kv[1])[:TOP]],
        by_op=[[key, label(key, records.get(key)), klass_of(key), ms(t),
                made_of(records.get(key))] for key, t in longest],
        optimizer_by_class={k: ms(t) for k, t in sorted(updates.items())},
        unscoped_ms=ms(unscoped),
        table_s=None if table is None else round(
            getattr(table, "seconds", 0.0), 3),
        text_bytes=None if table is None else getattr(
            table, "text_bytes", None))


def booked(trace):
    """The booking and the tables of one trace, made and logged once."""
    if _booked and _booked[0] is trace:
        return _booked[1], _booked[2]
    try:
        from mxnet_tpu import profiler
    except ImportError:
        return None, None
    ask = getattr(profiler, "program_scopes", None)
    if ask is None:       # a program from before the tables
        return None, None
    tables, rows = ask(), book(trace)
    for program, row in sorted(rows.items()):
        log_program(program, row, tables.get(program))
    _booked[:] = [trace, rows, tables]
    return rows, tables


def read(sources, program, klass=None, optimizer=None, unnamed=None):
    trace = sources.get("trace")
    if trace is None or not trace.device_planes():
        return None
    rows, tables = booked(trace)
    if not rows or not tables:
        return None
    total = spent = 0.0
    for name, row in rows.items():
        if program not in name:
            continue
        total += row["seconds"]
        table = tables.get(name)
        for key, t in row["ops"].items():
            rec = None if table is None else table.get(key)
            if unnamed:
                hit = rec is None or not rec["scope"]
            elif rec is None:
                hit = False
            else:
                hit = (klass is None or rec["klass"] == klass) and (
                    optimizer is None
                    or rec["optimizer"] == bool(optimizer))
            if hit:
                spent += t
    if total <= 0:
        return None
    return 100.0 * spent / total
