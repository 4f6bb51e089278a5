"""Runner ``serve_lm``: a language model served through
``mx.DecodeEngine`` (continuous batching over the paged cache), one
chip, open or closed loop.

One thread offers the load (the engine runs its own): an open loop
sends each request when it is due, whatever the engine does; a closed
loop's callers each send their next request when the last resolves, and
its window opens on a loop that is already turning (``closed_loop``).
A request's time runs from when it was DUE to when its future resolved.
After the window and the drain the engine is freed, and a seeded sample
of the finished requests is teacher-forced through the plain reference.
"""

import gc
import queue
import time

import numpy as np

from benchmark import harness, stats

KERNEL = "tpu_custom_call"
# a request not resolved this long after the window has failed (a
# 256-token answer takes 32 s at the 124 ms a decode step takes today)
DRAIN_S = 60.0
# a generator starved of the CPU reads as a fast server: a request sent
# later than this share of the window after it was due fails the run
LATE_LIMIT_SHARE = 0.01


def build_engine(run, weights):
    import mxnet_tpu as mx

    cfg, wl = run.cell.config, run.cell.workload
    ctx = mx.tpu(0) if run.devices[0].platform == "tpu" else mx.cpu()
    eng = wl["engine"]
    return mx.DecodeEngine(
        weights, vocab_size=cfg["vocab_size"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        d_model=cfg["n_embd"], max_len=eng["max_len"],
        kv_block=eng["kv_block"], max_streams=eng["max_streams"],
        decode_buckets=tuple(eng["decode_buckets"]),
        cache_buckets=tuple(eng["cache_buckets"]),
        prefill_buckets=tuple(eng["prefill_buckets"]),
        prefix_cache=eng.get("prefix_cache", 0), temperature=0.0,
        ctx=ctx, dtype=wl["dtype"])


def warm_up(run, eng, vocab):
    """Every executable compiled (or fetched), then driven once: one
    prompt per prefill bucket, long enough answers to run the pipelined
    decode path, and streams long enough to reach every cache bucket."""
    wl = run.cell.workload["engine"]
    eng.warmup()
    run.mark("executables_built")
    rng = np.random.default_rng(run.seed ^ 0x5EED)
    block = wl["kv_block"]
    futures = []
    for cb in wl["cache_buckets"]:
        # a stream whose pages reach this table width
        n = min(cb * block, wl["max_len"]) - 8
        p = min(n - 8, wl["prefill_buckets"][-1])
        futures.append(eng.submit(
            rng.integers(1, vocab, p).astype(np.int32),
            max_new_tokens=n - p + 8 - 1))
    for pb in wl["prefill_buckets"]:
        futures.append(eng.submit(
            rng.integers(1, vocab, min(pb - 3, wl["max_len"] - 8))
            .astype(np.int32), max_new_tokens=6))
    for f in futures:
        f.result(timeout=600)
    time.sleep(0.05)  # the loop books the last step before the reset
    eng.reset_stats()


def served_tokens(future):
    """What the engine served for one request."""
    return np.asarray(future.result())


class Load:
    """The requests of one run and what became of each."""

    def __init__(self, run, eng, reqs):
        self.run, self.eng, self.reqs = run, eng, reqs
        n = len(reqs["prompts"])
        self.due = [None] * n
        self.sent = [None] * n
        self.done = [None] * n
        self.out = [None] * n
        self.error = [None] * n
        self.stats = None   # the engine's, when the window closed

    def send(self, i, due, on_done=None):
        self.due[i] = due
        with self.run.span("submit"):
            fut = self.eng.submit(self.reqs["prompts"][i],
                                  max_new_tokens=self.reqs["max_new"][i])
        self.sent[i] = time.perf_counter()

        def resolved(f, i=i):
            self.done[i] = time.perf_counter()
            try:
                self.out[i] = served_tokens(f)
            except Exception as exc:  # the engine failed the request
                self.error[i] = repr(exc)
            if on_done is not None:
                on_done(i)

        fut.add_done_callback(resolved)

    def window_closed(self):
        """The engine's counters over the window, and the profiler
        stopped where it runs."""
        self.stats = self.eng.stats()
        self.run.end_window()

    def unresolved(self, t_end):
        """Requests due before the window closed and not resolved yet."""
        return [i for i, d in enumerate(self.due)
                if d is not None and d < t_end and self.done[i] is None]

    def open_loop(self):
        """Sends each request when it is due, then waits for the replies
        (at most ``DRAIN_S`` past the window).  Returns the window's
        start."""
        t0 = self.run.start_window()
        t_end = t0 + self.run.seconds
        due = self.reqs["due"]
        for i in range(len(due)):
            self.run.tick()
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                with self.run.span("wait_until_due"):
                    time.sleep(wait)
            self.send(i, t0 + due[i])
        while True:
            self.run.tick()
            left = t_end - time.perf_counter()
            if left <= 0:
                break
            with self.run.span("wait_window_end"):
                time.sleep(min(left, 0.05))
        self.window_closed()
        while self.unresolved(t_end) and \
                time.perf_counter() < t_end + DRAIN_S:
            time.sleep(0.02)
        return t0

    def closed_loop(self):
        """All callers send at once, and the window opens when each has
        its first reply: the loop has turned over once, the engine is
        full and the callers are out of phase (the engine prefills one
        prompt at a time, and answers differ in length).  That first
        turn is set-up; it lasts what the engine makes it last, so a
        faster engine shortens it.  Past the window the loop keeps
        turning until every request due inside it has its reply (at most
        ``DRAIN_S``), so that those finish under the load they were sent
        under.  Returns the window's start."""
        finished = queue.SimpleQueue()
        n, clients = len(self.reqs["prompts"]), self.reqs["clients"]
        for nxt in range(clients):
            self.send(nxt, time.perf_counter(), finished.put)
        nxt, first_replies, t0, t_end = clients, 0, None, None
        while nxt < n:
            wait = 0.05
            if t0 is not None and self.stats is None:   # in the window
                self.run.tick()
                wait = min(wait, t_end - time.perf_counter())
                if wait <= 0:
                    self.window_closed()
            if self.stats is not None and (               # past it
                    not self.unresolved(t_end)
                    or time.perf_counter() >= t_end + DRAIN_S):
                return t0
            try:
                with self.run.span("wait_result"):
                    i = finished.get(timeout=max(wait, 0.0))
            except queue.Empty:
                continue
            first_replies += i < clients
            if t0 is None and first_replies == clients:
                self.run.mark("loop_turned_once")
                self.eng.reset_stats()  # the counters cover the window
                t0 = self.run.start_window()
                t_end = t0 + self.run.seconds
            self.send(nxt, time.perf_counter(), finished.put)
            nxt += 1
        harness.fail(f"the closed loop used all {n} requests of its pool: "
                     f"enlarge `pool`")


def pick_sample(run, load, sent):
    """A seeded sample of the finished requests, the longest among
    them: (prompt, served tokens) pairs."""
    limits = run.cell.workload["limits"]
    good = [i for i in sent if load.out[i] is not None]
    if not good:
        return []
    total = lambda i: len(load.reqs["prompts"][i]) + len(load.out[i])
    rng = np.random.default_rng(run.seed ^ 0xC0FFEE)
    k = min(int(limits["sample_requests"]), len(good))
    first = max(good, key=total)
    rest = [i for i in good if i != first]
    picked = [first] + [int(i) for i in
                        rng.choice(rest, k - 1, replace=False)]
    return [(load.reqs["prompts"][i], load.out[i]) for i in picked]


def sample_gaps(run, sample, ref, precision="float32"):
    """The sample teacher-forced through the float32 reference.  Per
    served position: how far below the reference's best logit the
    served token's logit lies and, for a control, how far below it
    lies the token that ``precision`` puts first."""
    import jax.numpy as jnp

    cfg, wl = run.cell.config, run.cell.workload
    T = int(wl["engine"]["max_len"])
    n_out = int(run.cell.traffic["output_tokens"].get("max")
                or run.cell.traffic["output_tokens"]["value"])
    w = ref.to_float32(ref.draw(cfg, run.seed, embed_dtype=wl["dtype"],
                                dtype=wl["dtype"]))
    served_gaps, low_gaps = [], []
    for prompt, served in sample:
        row = np.zeros((1, T), np.int32)
        row[0, :len(prompt)] = prompt
        row[0, len(prompt):len(prompt) + len(served)] = served
        pad = np.zeros(n_out, np.int32)
        pad[:len(served)] = served
        g, low = ref.served_gaps(
            w, jnp.asarray(row), len(prompt) - 1, jnp.asarray(pad),
            heads=int(cfg["n_head"]), precision=precision, n_out=n_out)
        served_gaps.append(np.asarray(g)[:len(served)])
        low_gaps.append(np.asarray(low)[:len(served)])
    return np.concatenate(served_gaps), np.concatenate(low_gaps)


def serve_check(run, sample, ref):
    """Each number compared, beside its limit."""
    limits = run.cell.workload["limits"]
    if not sample:
        harness.log(error="no request finished: nothing to compare")
        return False
    flat, _ = sample_gaps(run, sample, ref)
    results = []
    harness.log(compared_requests=len(sample), compared_tokens=len(flat),
                tokens_equal_reference_best=int(np.sum(flat == 0.0)))
    harness.check("served_logit_gap_widest", float(flat.max()),
                  limits["logit_gap_widest"], results)
    harness.check("served_logit_gap_mean", float(flat.mean()),
                  limits["logit_gap_mean"], results)
    return all(results)


def serve_window(run, eng, reqs):
    """The window and the wait for its replies: what was sent and what
    became of it."""
    load = Load(run, eng, reqs)
    closed = run.cell.traffic["arrivals"]["process"] == "closed"
    t0 = (load.closed_loop if closed else load.open_loop)()
    sent = [i for i, s in enumerate(load.sent) if s is not None]
    return load, sent, load.stats, t0


def run(run):
    ref = harness.plugin("reference", run.cell.config["family"])
    generate = harness.plugin("traffic", run.cell.traffic["generator"])
    cfg, wl, mix = run.cell.config, run.cell.workload, run.cell.traffic
    vocab = cfg["vocab_size"]

    weights = ref.program_names(ref.draw(
        cfg, run.seed, embed_dtype=wl["dtype"], dtype=wl["dtype"]))
    run.mark("weights_drawn")
    eng = build_engine(run, weights)
    del weights
    run.mark("engine_built")
    try:
        reqs = generate.requests(mix, run.seed, run.seconds, vocab)
        warm_up(run, eng, vocab)
        run.mark("warmed_up")
        load, sent, st, t0 = serve_window(run, eng, reqs)
    except BaseException:
        eng.close()  # the engine's thread must not outlive a failure
        raise
    t_end = t0 + run.seconds
    peak = run.memory_peak()

    # ---- what became of the requests ---------------------------------
    # A request counts where its life, from due to resolved, overlaps
    # the window; its tokens count by the share of that life inside the
    # window.  (By whole requests resolved inside the window the rate
    # moves in steps of a request, 1.4% of a window here: a gain of 1%
    # would read as nothing or as one step.  PERF.md section 2.)
    worse = 1e3 * (run.seconds + DRAIN_S)  # worse than any latency
    attempted = failed = whole_requests = 0
    lat_ms, tokens, whole_tokens = [], 0.0, 0
    for i in sent:
        due, done = load.due[i], load.done[i]
        if due >= t_end or (done is not None and done <= t0):
            continue                     # the first turn of the loop
        attempted += 1
        ok = (load.out[i] is not None
              and load.out[i].shape == (load.reqs["max_new"][i],))
        failed += not ok
        if due >= t0:
            lat_ms.append(1e3 * (done - due) if ok else worse)
        if ok:
            inside = min(done, t_end) - max(due, t0)
            tokens += len(load.out[i]) * inside / (done - due)
            if done <= t_end:
                whole_requests += 1
                whole_tokens += len(load.out[i])
    late = [load.sent[i] - load.due[i] for i in sent]
    harness.log(attempted=attempted, failed=failed, sent_in_all=len(sent),
                resolved_in_window=whole_requests,
                tokens_per_s_by_whole_requests=whole_tokens / run.seconds,
                errors=[load.error[i] for i in sent if load.error[i]
                        and load.due[i] < t_end][:3],
                lateness_ms_p50=1e3 * stats.percentile(late, 50),
                lateness_ms_max=1e3 * max(late),
                request_ms_p50=stats.percentile(lat_ms, 50),
                request_ms_p95=stats.percentile(lat_ms, 95),
                engine={k: st[k] for k in (
                    "requests", "tokens", "prefills", "steps",
                    "stream_steps", "preempted", "d2h_syncs",
                    "d2h_syncs_saved", "ttft_p50_ms", "p50_ms", "p99_ms",
                    "active_streams", "pending")})
    correct = True
    # (a traced run is exempt: the profiler stalls the host)
    late_limit = LATE_LIMIT_SHARE * run.seconds
    if not run.trace and max(late) > late_limit:
        harness.log(error="the generator ran late", max_s=max(late),
                    limit_s=late_limit)
        correct = False
    if failed:
        correct = False
    run.extras["engine_stats"] = st
    run.extras["engine"] = dict(wl["engine"])

    kernel_ok = True
    if run.devices[0].platform == "tpu":
        for key in [k for k in eng.compiles if k[0] == "decode"]:
            kernel_ok = kernel_ok and KERNEL in eng.executable_text(key)
        harness.log(check="kernel_in_decode_executables", marker=KERNEL,
                    ok=kernel_ok)
    eng.close()
    del eng, load.eng
    gc.collect()
    t_ref = time.perf_counter()
    sample = pick_sample(run, load, sent)
    correct = serve_check(run, sample, ref) and correct
    harness.log(reference_s=time.perf_counter() - t_ref)
    metrics = {
        "serve_out_tokens_per_s": tokens / run.seconds,
        "serve_request_p95_ms": stats.percentile(lat_ms, 95)}
    return {"correct": correct and kernel_ok, "attempted": attempted,
            "failed": failed, "metrics": metrics,
            "memory_peak_bytes": peak}
