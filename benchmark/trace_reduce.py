"""From the profiler's trace to numbers: the one reduction every PR is
measured by.

A trace is read once into plain lists (``Trace.planes``: name ->
{line name -> [(event name, start_ns, duration_ns), ...]}), so that the
reductions below run the same on a trace from the chip and on the small
recorded one under ``tests/data/``.

What the TPU's trace holds (looked at by hand, PR 23): one plane per
chip, ``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per
executed program) and a line ``XLA Ops`` (one per executed operation,
kernels among them); host threads are lines of ``/host:CPU``, where the
benchmark's own ``TraceAnnotation``s show as ``bench:<name>``.  The
traced window is the benchmark's ``bench:window`` span.
"""

import glob
import gzip
import json
import os

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
KERNEL_TAG = " [tpu_custom_call"


def short_name(full):
    """A device operation's event name is its whole HLO line.  Kept:
    the name before `` = ``; for a Mosaic kernel also the tag
    ``[tpu_custom_call <first operand's shape>]``, which is how the
    kernels are found and sized (no ``name=`` on any ``pallas_call``
    yet: PERF.md, Open questions)."""
    name = full.split(" = ", 1)[0]
    if KERNEL_TARGET in full:
        operand = full.split("custom-call(", 1)[1].split("{", 1)[0]
        name += f"{KERNEL_TAG} {operand}]"
    return name


def union_seconds(intervals):
    """Total length of the union of (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals, lo, hi):
    """The stretches of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    def __init__(self, planes):
        self.planes = planes

    # -- reading ------------------------------------------------------
    @classmethod
    def from_dir(cls, trace_dir):
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise SystemExit(f"benchmark: the profiler left no trace "
                             f"under {trace_dir}")
        return cls.from_xplane(max(paths, key=os.path.getmtime))

    @classmethod
    def from_xplane(cls, path):
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        planes = {}
        for plane in data.planes:
            device = plane.name.startswith(DEVICE_PLANE)
            if not (device or plane.name == HOST_PLANE):
                continue
            lines = planes.setdefault(plane.name, {})
            for line in plane.lines:
                if device and line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = lines.setdefault(line.name, [])
                for ev in line.events:
                    # of the host, only the benchmark's own spans
                    if device:
                        name = ev.name if line.name == MODULES_LINE \
                            else short_name(ev.name)
                        events.append((name, float(ev.start_ns),
                                       float(ev.duration_ns)))
                    elif ev.name.startswith(SPAN_PREFIX):
                        events.append((ev.name, float(ev.start_ns),
                                       float(ev.duration_ns)))
        return cls(planes)

    @classmethod
    def from_json(cls, path):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return cls(json.load(f))

    def to_json(self, path, max_events=None):
        planes = {p: {ln: ev[:max_events] for ln, ev in lines.items()}
                  for p, lines in self.planes.items()}
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "wt") as f:
            json.dump(planes, f)

    # -- pieces -------------------------------------------------------
    def device_planes(self):
        return sorted(p for p in self.planes if p.startswith(DEVICE_PLANE))

    def spans(self):
        """The benchmark's own host spans: (name, start, end)."""
        out = []
        for line in self.planes.get(HOST_PLANE, {}).values():
            out += [(n[len(SPAN_PREFIX):], s, s + d) for n, s, d in line
                    if n.startswith(SPAN_PREFIX)]
        return sorted(out, key=lambda x: x[1])

    def window(self):
        """(start, end) of the traced window in ns: the ``bench:window``
        span; without one, from the first device event to the last."""
        for name, s, e in self.spans():
            if SPAN_PREFIX + name == WINDOW_SPAN:
                return s, e
        evs = [ev for p in self.device_planes()
               for ev in self.planes[p].get(OPS_LINE, [])]
        return (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))

    def ops(self, plane):
        """Device operations of one chip inside the window, clipped."""
        lo, hi = self.window()
        out = []
        for name, s, d in self.planes[plane].get(OPS_LINE, []):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                out.append((name, a, b))
        return out

    # -- reductions ---------------------------------------------------
    def busy_and_window(self):
        """Seconds in which an operation ran on the device (the union of
        the operations' intervals, averaged over the chips), and the
        length of the traced window."""
        lo, hi = self.window()
        planes = self.device_planes()
        if not planes:
            return 0.0, (hi - lo) / 1e9
        busy = [union_seconds([(a, b) for _, a, b in self.ops(p)])
                for p in planes]
        return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9

    def op_seconds(self):
        """Device seconds by operation name (summed over events,
        averaged over chips)."""
        planes = self.device_planes()
        out = {}
        for p in planes:
            for name, a, b in self.ops(p):
                out[name] = out.get(name, 0.0) + (b - a) / 1e9
        return {k: v / len(planes) for k, v in out.items()}

    def kernels(self):
        """The Mosaic kernels' events on the first chip: (name, first
        operand's shape as a list of ints, seconds)."""
        planes = self.device_planes()
        out = []
        for name, a, b in (self.ops(planes[0]) if planes else []):
            if KERNEL_TAG in name:
                dims = name.split(KERNEL_TAG, 1)[1].split("[", 1)[1]
                shape = [int(x) for x in dims.split("]", 1)[0].split(",")
                         if x.strip().isdigit()]
                out.append((name, shape, (b - a) / 1e9))
        return out

    def modules(self):
        """{program name: (executions, seconds)} on the first chip."""
        planes = self.device_planes()
        if not planes:
            return {}
        lo, hi = self.window()
        out = {}
        for name, s, d in self.planes[planes[0]].get(MODULES_LINE, []):
            if s >= lo and s + d <= hi:  # whole executions only
                n, t = out.get(name, (0, 0.0))
                out[name] = (n + 1, t + d / 1e9)
        return out

    def idle_gaps(self, top=5):
        """The longest stretches with no operation on the first chip,
        each named by the benchmark's span that covers most of it."""
        planes = self.device_planes()
        if not planes:
            return []
        lo, hi = self.window()
        holes = gaps([(a, b) for _, a, b in self.ops(planes[0])], lo, hi)
        holes.sort(key=lambda g: g[0] - g[1])
        spans = [s for s in self.spans()
                 if SPAN_PREFIX + s[0] != WINDOW_SPAN]
        out = []
        for s, e in holes[:top]:
            best, cover = "unattributed", 0.0
            for name, a, b in spans:
                c = min(e, b) - max(s, a)
                if c > cover:
                    best, cover = name, c
            out.append([best, (e - s) / 1e9])
        return out

    def breakdown(self):
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:10]],
                "idle_gaps": self.idle_gaps(5)}
