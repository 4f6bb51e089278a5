"""The measuring code the ledger's numbers come through, held in tier-1:
``benchmark/trace_reduce.py`` (interval arithmetic, HLO names, reading a
trace ``jax.profiler`` wrote), ``benchmark/stats.py`` and
``benchmark/peaks.py``.  ``benchmark/tests/`` (run by hand) holds the
cells; these are the readers every cell's reducers stand on.  Imports
only: nothing under ``benchmark/`` is steered or patched."""

import math

import numpy as np
import pytest

from benchmark import peaks, stats
from benchmark import trace_reduce as tr
from mxnet_tpu import profiler


@pytest.mark.parametrize("intervals, total", [
    ([], 0.0),
    ([(3, 7)], 4),
    ([(0, 4), (4, 9)], 9),                      # touching
    ([(0, 10), (2, 3), (5, 12)], 12),           # nested and overlapping
    ([(40, 45), (0, 1), (0.5, 2), (44, 50)], 12),  # unsorted, two islands
])
def test_union_seconds_by_hand(intervals, total):
    assert tr.union_seconds(intervals) == total
    # the union is no longer than the sum, no shorter than the longest
    lens = [e - s for s, e in intervals]
    assert max(lens, default=0) <= total <= sum(lens)


@pytest.mark.parametrize("intervals, lo, hi, holes", [
    ([], 2, 9, [(2, 9)]),
    ([(0, 10)], 2, 9, []),                      # covered whole
    ([(3, 4), (6, 8)], 0, 10, [(0, 3), (4, 6), (8, 10)]),
    ([(3, 6), (5, 8)], 4, 7, []),               # overlap spans the range
    ([(1, 2), (20, 30)], 5, 25, [(5, 20)]),     # clipped at both ends
])
def test_gaps_by_hand(intervals, lo, hi, holes):
    assert tr.gaps(intervals, lo, hi) == holes
    # busy + idle = the range, for what lies inside it
    inside = [(max(s, lo), min(e, hi)) for s, e in intervals
              if min(e, hi) > max(s, lo)]
    assert tr.union_seconds(inside) + sum(e - s for s, e in holes) \
        == hi - lo


KERNEL = ('%decode.7 = bf16[48,1280]{1,0:T(8,128)(2,1)} '
          'custom-call(bf16[48,3840]{1,0:T(8,128)(2,1)} %qkv, s32[48]{0} %n), '
          'custom_call_target="tpu_custom_call", operand_layout={}')
META = (', metadata={op_name="jit(step)/layer_3/attend/pallas_call" '
        'source_file="ops/pallas_kernels.py" source_line=1}')


@pytest.mark.parametrize("full, short", [
    ("%fusion.12 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p0), "
     "kind=kLoop", "%fusion.12"),
    ("%fusion.12 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p0), "
     "kind=kLoop" + META, "%fusion.12"),
    (KERNEL, "%decode.7 [tpu_custom_call bf16[48,3840]]"),
    (KERNEL + META, "%decode.7 [tpu_custom_call bf16[48,3840]]"),
])
def test_short_name_with_and_without_metadata(full, short):
    assert tr.short_name(full) == short
    if tr.KERNEL_TAG in short:
        t = tr.Trace({"/device:TPU:0": {"XLA Ops": [[short, 0.0, 250.0]]}})
        assert t.kernels() == [(short, [48, 3840], 250e-9)]


def test_from_dir_reads_a_trace_jax_wrote(tmp_path):
    """A ``bench:`` span round one jit call comes back, with its window:
    the path every traced run of a cell takes (``--trace 1``), on the
    trace the CPU backend writes."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64), jnp.float32)
    step(x).block_until_ready()  # compiled before the window opens
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench:step"):
                step(x).block_until_ready()
        with jax.profiler.TraceAnnotation("not_the_benchmarks"):
            pass
    finally:
        jax.profiler.stop_trace()
    t = tr.Trace.from_dir(str(tmp_path))
    spans = {name: (s, e) for name, s, e in t.spans()}
    assert set(spans) == {"window", "step"}  # the benchmark's own only
    lo, hi = t.window()
    assert (lo, hi) == spans["window"] and hi > lo
    assert lo <= spans["step"][0] < spans["step"][1] <= hi
    # no chip, no device plane: nothing is busy, and the window's length
    # is still the span's
    assert t.device_planes() == []
    assert t.busy_and_window() == (0.0, (hi - lo) / 1e9)
    assert t.modules() == {} and t.idle_gaps() == []


def test_from_dir_without_a_trace_exits_by_name(tmp_path):
    with pytest.raises(SystemExit) as e:
        tr.Trace.from_dir(str(tmp_path))
    assert "the profiler left no trace" in str(e.value)
    assert str(tmp_path) in str(e.value)


@pytest.mark.parametrize("q", [0, 1, 25, 50, 90, 99, 100])
def test_percentile_is_numpys(q):
    rng = np.random.RandomState(q)
    for n in (1, 2, 7, 100):
        xs = list(rng.lognormal(3.0, 1.0, n))
        assert stats.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12)


def test_percentile_of_nothing_and_of_failures():
    assert stats.percentile([], 50) is None
    # a failed request is an infinite latency: it sorts last, and the
    # percentile that reaches it is infinite, never an interpolation
    xs = [1.0, 2.0, 3.0, math.inf]
    assert stats.percentile(xs, 50) == 2.5
    assert stats.percentile(xs, 99) == math.inf
    assert stats.percentile(xs, 100) == math.inf


@pytest.mark.parametrize(
    "kind", sorted(set(peaks.PEAKS) | set(profiler.PEAK_BY_DEVICE_KIND)))
def test_the_two_peak_tables_agree(kind):
    """The benchmark's peaks and the package's (the live MFU gauge's)
    sit on two sides of the benchmark's ``paths`` wall and cannot be
    one table from this side: every kind either holds, both hold, with
    the same rates."""
    mine, theirs = profiler.PEAK_BY_DEVICE_KIND[kind], peaks.lookup(kind)
    for rate in ("bf16_flops", "hbm_bytes_per_s"):
        assert mine[rate] == theirs[rate]
    assert profiler.peak_flops(kind) == theirs["bf16_flops"]


def test_peaks_unknown_kind_exits_by_name():
    with pytest.raises(SystemExit) as e:
        peaks.lookup("TPU v9000")
    assert "TPU v9000" in str(e.value) and "TPU v5 lite" in str(e.value)


# what `reducers/family_kernel_roofline.py` asks of the newest family:
# planted counters in, (operations, bytes) an execution out
_BRUMBY = {"num_hidden_layers": 10, "num_attention_heads": 40,
           "num_key_value_heads": 8, "head_dim": 128}


@pytest.mark.parametrize("kernel, stats, per_layer", [
    ("retention_step", {"steps": 50, "stream_steps": 600},
     lambda f: f.retention_step(12.0, _BRUMBY)),
    ("retention_step", {"steps": 50, "stream_steps": 425},
     lambda f: f.retention_step(8.5, _BRUMBY)),
    ("retention_chunk", {"prefills": 8, "prefill_tokens": 10240},
     lambda f: f.retention_chunk(1280.0, 1, _BRUMBY, 2)),
])
def test_brumby_need_reads_the_planted_counters(kernel, stats, per_layer):
    from benchmark.flops import brumby

    ops, nbytes = brumby.need(kernel, stats, _BRUMBY, 2)
    want_ops, want_bytes = per_layer(brumby)
    assert (ops, nbytes) == (10 * want_ops, 10 * want_bytes)
    assert ops > 0 and nbytes > 0


@pytest.mark.parametrize("kernel, stats", [
    ("retention_step", {}), ("retention_step", {"steps": 3}),
    ("retention_chunk", {"prefills": 0, "prefill_tokens": 0})])
def test_brumby_need_without_counters_reads_nothing(kernel, stats):
    from benchmark.flops import brumby

    assert brumby.need(kernel, stats, _BRUMBY, 2) is None


def test_brumby_step_is_the_states_bytes_and_a_prompt_is_compute():
    """The decode kernel's least time is its bytes (the state at the
    NEEDED 8,256 rows, read and written), a prompt's its operations."""
    from benchmark.flops import brumby

    peak = peaks.lookup("TPU v5 lite")
    ops, nbytes = brumby.retention_step(12, _BRUMBY)
    assert nbytes / peak["hbm_bytes_per_s"] > ops / peak["bf16_flops"]
    assert 12 * 2 * 8 * 8256 * 128 * 4 <= nbytes <= 12 * 2 * 8 * 8320 * 129 * 4
    ops, nbytes = brumby.retention_chunk(1280, 1, _BRUMBY, 2)
    assert ops / peak["bf16_flops"] > nbytes / peak["hbm_bytes_per_s"]
