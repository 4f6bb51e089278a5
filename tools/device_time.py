"""Device-side timing helper: run a jitted fn under jax.profiler.trace
and return the XLA executable's on-device ms/execution, parsed from the
XPlane trace (tools/xplane_parse).  Host dispatch latency is not in it —
this is the time the chip actually spends.
"""

import glob
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax

from xplane_parse import load_xspace


def _device_plane(planes):
    for p in planes:
        if "/device:TPU" in p.name:
            return p
    for p in planes:
        if "/device:" in p.name and "CUSTOM" not in p.name:
            return p
    raise RuntimeError(f"no device plane: {[p.name for p in planes]}")


def device_ms(fn, *args, iters=10, per_op=False, warmup=2):
    """Time `fn(*args)` on device.  Returns ms/exec (float), or
    (ms/exec, [(op_name, ms_per_exec), ...]) when per_op=True.

    fn should be jitted; all iterations run inside one trace so the
    XLA Modules line carries `iters` executions of the compiled
    program (plus any helper executables, which are filtered by taking
    the dominant module name).
    """
    for _ in range(warmup):
        r = fn(*args)
    jax.block_until_ready(r)
    tmp = tempfile.mkdtemp(prefix="devtime_")
    try:
        with jax.profiler.trace(tmp):
            for _ in range(iters):
                r = fn(*args)
            jax.block_until_ready(r)
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("no xplane.pb produced")
        dev = _device_plane(load_xspace(max(paths, key=os.path.getmtime)))
        mods = {}
        for line in dev.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    nm = dev.event_names.get(ev.metadata_id, "?")
                    tot, cnt = mods.get(nm, (0.0, 0))
                    mods[nm] = (tot + ev.duration_ps / 1e9, cnt + 1)
        if not mods:
            raise RuntimeError("no XLA Modules events in trace")
        _, (tot, n) = max(mods.items(), key=lambda kv: kv[1][0])
        ms = tot / max(n, 1)
        if not per_op:
            return ms
        ops = {}
        for line in dev.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    oname = dev.event_names.get(ev.metadata_id, "?")
                    ops[oname] = ops.get(oname, 0.0) + ev.duration_ps / 1e9
        rows = sorted(((o, t / max(n, 1)) for o, t in ops.items()),
                      key=lambda r: -r[1])
        return ms, rows
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
