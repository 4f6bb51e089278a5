"""Minimal pure-Python reader for XPlane profiler protobufs.

``jax.profiler.trace`` writes a ``*.xplane.pb`` (an ``XSpace`` proto —
the public schema from tsl/profiler/protobuf/xplane.proto).  The
installed tensorboard_plugin_profile's generated protos are
incompatible with this image's protobuf runtime, so this module decodes
the wire format directly: protobuf wire encoding is stable and the
subset needed (planes -> lines -> events + metadata maps) is small.

Field numbers (from the public xplane.proto):
  XSpace:   planes=1
  XPlane:   id=1 name=2 lines=3 event_metadata=4(map) stat_metadata=5(map)
  XLine:    id=1 name=2 timestamp_ns=3 events=4 display_name=11
  XEvent:   metadata_id=1 offset_ps=2 duration_ps=3 stats=4
  XEventMetadata: id=1 name=2
  XStat:    metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7
  XStatMetadata:  id=1 name=2
"""

import struct


def _read_varint(buf, i):
    shift = 0
    out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """Yield (field_number, wire_type, value) for every field in buf.
    Length-delimited values are memoryview slices; varints are ints."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _read_varint(buf, i)
        fn, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _read_varint(buf, i)
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 2:
            ln, i = _read_varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fn, wt, v


def _parse_metadata_map(buf, name_field=2):
    """Parse map<int64, X*Metadata> entries -> {id: name}."""
    out = {}
    for fn, wt, v in _fields(buf):
        if fn == 1 and wt == 0:
            pass
        elif fn == 2 and wt == 2:
            mid, name = 0, ""
            for f2, w2, v2 in _fields(v):
                if f2 == 1 and w2 == 0:
                    mid = v2
                elif f2 == name_field and w2 == 2:
                    name = bytes(v2).decode("utf-8", "replace")
            out[mid] = name
    return out


class XStat:
    __slots__ = ("metadata_id", "value")

    def __init__(self, buf):
        self.metadata_id = 0
        self.value = None
        for fn, wt, v in _fields(buf):
            if fn == 1 and wt == 0:
                self.metadata_id = v
            elif fn == 2 and wt == 1:
                self.value = struct.unpack("<d", v)[0]
            elif fn in (3, 4, 7) and wt == 0:
                self.value = v
            elif fn in (5, 6) and wt == 2:
                self.value = bytes(v).decode("utf-8", "replace")


class XEvent:
    __slots__ = ("metadata_id", "offset_ps", "duration_ps", "stats")

    def __init__(self, buf):
        self.metadata_id = 0
        self.offset_ps = 0
        self.duration_ps = 0
        self.stats = []
        for fn, wt, v in _fields(buf):
            if fn == 1 and wt == 0:
                self.metadata_id = v
            elif fn == 2 and wt == 0:
                self.offset_ps = v
            elif fn == 3 and wt == 0:
                self.duration_ps = v
            elif fn == 4 and wt == 2:
                self.stats.append(XStat(v))


class XLine:
    __slots__ = ("name", "timestamp_ns", "events")

    def __init__(self, buf):
        self.name = ""
        self.timestamp_ns = 0
        self.events = []
        for fn, wt, v in _fields(buf):
            if fn == 2 and wt == 2:
                self.name = bytes(v).decode("utf-8", "replace")
            elif fn == 3 and wt == 0:
                self.timestamp_ns = v
            elif fn == 4 and wt == 2:
                self.events.append(XEvent(v))


class XPlane:
    __slots__ = ("name", "lines", "event_names", "stat_names")

    def __init__(self, buf):
        self.name = ""
        self.lines = []
        em_bufs, sm_bufs = [], []
        for fn, wt, v in _fields(buf):
            if fn == 2 and wt == 2:
                self.name = bytes(v).decode("utf-8", "replace")
            elif fn == 3 and wt == 2:
                self.lines.append(XLine(v))
            elif fn == 4 and wt == 2:
                em_bufs.append(v)
            elif fn == 5 and wt == 2:
                sm_bufs.append(v)
        self.event_names = {}
        self.stat_names = {}
        for b in em_bufs:
            self.event_names.update(_parse_metadata_map(b))
        for b in sm_bufs:
            self.stat_names.update(_parse_metadata_map(b))


def load_xspace(path):
    """Parse an .xplane.pb file -> list of XPlane."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    planes = []
    for fn, wt, v in _fields(data):
        if fn == 1 and wt == 2:
            planes.append(XPlane(v))
    return planes


def dominant_module_ms(trace_dir):
    """Find the newest .xplane.pb under trace_dir and return the
    dominant XLA executable's (ms_per_execution, n_executions) from the
    device plane — the shared helper behind bench.py's step_ms_device,
    tools/device_time.py and tools/profile_step.py."""
    import glob
    import os

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None, 0
    planes = load_xspace(max(paths, key=os.path.getmtime))
    dev = None
    for p in planes:
        if "/device:TPU" in p.name:
            dev = p
            break
    if dev is None:
        for p in planes:
            if "/device:" in p.name and "CUSTOM" not in p.name:
                dev = p
                break
    if dev is None:
        return None, 0
    mods = {}
    for line in dev.lines:
        if line.name == "XLA Modules":
            for ev in line.events:
                nm = dev.event_names.get(ev.metadata_id, "?")
                tot, cnt = mods.get(nm, (0.0, 0))
                mods[nm] = (tot + ev.duration_ps / 1e9, cnt + 1)
    if not mods:
        return None, 0
    _, (tot, cnt) = max(mods.items(), key=lambda kv: kv[1][0])
    return tot / max(cnt, 1), cnt


def traced_module_ms(run, prefix="trace_"):
    """Run ``run()`` (which must block until its device work is done)
    under ``jax.profiler.trace`` in a throw-away directory and return
    the dominant XLA executable's on-device ms per execution.  A trace
    that cannot be taken or read fails the run — a null under the name
    of a device metric would read as measured."""
    import shutil
    import tempfile

    import jax

    tdir = tempfile.mkdtemp(prefix=prefix)
    try:
        with jax.profiler.trace(tdir):
            run()
        ms, _ = dominant_module_ms(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    if ms is None:
        raise SystemExit("no XLA module on a device plane of the profiler "
                         "trace — device time cannot be reported")
    return ms
