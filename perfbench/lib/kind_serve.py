"""A serving cell, in one process that holds the chip: the program's
``InferenceEngine`` and ``Scheduler`` built as ``inference/serve.py`` builds
them, driven through ``Scheduler.submit`` / ``.step`` by the traffic file's
loop (open: Poisson arrivals on the wall clock, each request timed from
when it was due; closed: sessions that send their next turn when the last
one completes).

Settings that are *capacity* (slots, max_len, pool size, prefill ladder) come
from the traffic file; settings that are *implementation* (paged kernel,
decode burst, prefill batch, speculative decoding, attention impl) stay at
the program's defaults, so a PR that changes a default is seen.
"""

import gc
import os
import sys
import time

import numpy as np

from . import program_records, result, stats, traffic, weights, window_notes
from .peaks import peaks_of

WARM_NEW_TOKENS = 2
DRAIN_SECONDS = 60.0


class Spans:
    """Harness spans around calls into the program, on the host clock; in a
    traced run also written into the profiler's trace."""

    def __init__(self):
        import jax

        self.records = {}    # name -> list of (start, end, extra)
        self.annotate = False
        self.annotation = jax.profiler.TraceAnnotation

    def wrap(self, obj, attr: str, name: str, extra=None):
        inner = getattr(obj, attr)
        spans = self

        def wrapped(*a, **k):
            info = extra(*a, **k) if extra else None
            ctx = None
            if spans.annotate:
                ctx = spans.annotation("pb:" + name)
                ctx.__enter__()
            t = time.monotonic()
            try:
                return inner(*a, **k)
            finally:
                spans.records.setdefault(name, []).append(
                    (t, time.monotonic(), info))
                if ctx is not None:
                    ctx.__exit__(None, None, None)

        setattr(obj, attr, wrapped)


def build_server(cell, seed: int, spans: Spans):
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine,
    )
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler
    from fault_tolerant_llm_training_tpu.models import configs as mc
    from fault_tolerant_llm_training_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache(None)
    d = weights.dims_of(cell.config)
    mc.PRESETS[cell.config_name] = weights.family_of(d).preset(cell.config)
    # as inference/serve.py does: preset by name, vocab, layer_impl default
    cfg = mc.get_config(cell.config_name, vocab_size=d["vocab"],
                        layer_impl="loop")
    server = cell.traffic["server"]
    dtype = jnp.float32 if server.get("dtype") == "fp32" else jnp.bfloat16
    if dtype == jnp.float32:
        cfg = cfg.replace(dtype=jnp.float32, param_dtype=jnp.float32)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(lambda k: weights.make_param_tree(k, d, dtype))(key)
    block = 16  # the program's default kv_block_size
    engine = InferenceEngine(
        cfg, params, slots=server["slots"], max_len=server["max_len"],
        prefill_buckets=tuple(server["prefill_buckets"]),
        kv_num_blocks=server["pool_tokens"] // block + 1)
    del params
    sched = Scheduler(engine, eos_token_id=None)
    return engine, sched, d


def instrument(engine, sched, spans: Spans, counters: dict):
    def decode_info(*a, **k):
        live = sum(len(st.request.prompt) + len(st.tokens) - 1
                   for st in sched.active.values())
        counters["decode_tokens"] += len(sched.active)
        counters["decode_ctx"] += live + len(sched.active)
        return live

    def prefill_info(slot, token_ids, *a, **k):
        n = int(np.asarray(token_ids).size)
        start = int(k.get("start_pos", 0))
        new = n - start
        counters["prefill_tokens"] += new
        # token i (absolute) attends to i + 1 positions
        counters["prefill_ctx"] += (n * (n + 1) - start * (start + 1)) // 2
        return new

    spans.wrap(engine, "decode_step", "decode", decode_info)
    spans.wrap(engine, "prefill", "prefill", prefill_info)
    spans.wrap(sched, "step", "sched_step")


class Tracker:
    """Per-request record, from the scheduler's own commit stamps."""

    def __init__(self):
        self.reqs = {}

    def submitted(self, rid, t_ref, prompt, want, meta=None):
        self.reqs[rid] = {"id": rid, "t_ref": t_ref, "prompt": prompt,
                          "want": want, "done": False, "meta": meta}

    def completed(self, c):
        r = self.reqs[c.request_id]
        r.update(done=True, tokens=list(c.tokens),
                 first_token_at=c.first_token_at, finished_at=c.finished_at)
        return r


def committed_tokens(sched, tracker) -> int:
    """Output tokens committed so far: of finished requests and of those
    still in their slots."""
    done = sum(len(r["tokens"]) for r in tracker.reqs.values() if r["done"])
    return done + sum(len(st.tokens) for st in sched.active.values()
                      if st.request.id in tracker.reqs)


def run_open(sched, reqs, tracker, t_start, horizon, Request, clock,
             queue_log=None, marks=()):
    """Open loop: submit what is due, step; never waits for the server.
    ``marks`` are (seconds since start, callable) run once when reached
    (the window opens after the pre-roll); ``queue_log`` collects (seconds
    since start, requests waiting + active) for the knee sweep."""
    i, late = 0, []
    completed = []
    marks = sorted(marks, key=lambda m: m[0])
    while True:
        now = clock()
        while marks and now - t_start >= marks[0][0]:
            marks.pop(0)[1]()
        if now - t_start >= horizon:
            break
        if queue_log is not None:
            queue_log.append((now - t_start,
                              len(sched.queue) + len(sched.active)))
        while i < len(reqs) and t_start + reqs[i]["t_due"] <= now:
            r = reqs[i]
            late.append(now - (t_start + r["t_due"]))
            tracker.submitted(r["id"], t_start + r["t_due"], r["prompt"],
                              r["max_new_tokens"])
            sched.submit(Request(id=r["id"], prompt=r["prompt"],
                                 max_new_tokens=r["max_new_tokens"]))
            i += 1
        if sched.pending():
            for c in sched.step():
                completed.append(tracker.completed(c))
        elif i < len(reqs):
            time.sleep(max(0.0, min(0.002,
                                    t_start + reqs[i]["t_due"] - clock())))
        else:
            time.sleep(0.002)
    return completed, late, i


class Sessions:
    """Closed loop: a session's next turn goes in when its last came back."""

    def __init__(self, sessions, tracker, Request):
        self.sessions = sessions
        self.tracker = tracker
        self.Request = Request
        self.turn = {s["id"]: 0 for s in sessions}
        self.history = {s["id"]: s["base"] for s in sessions}
        self.by_id = {s["id"]: s for s in sessions}
        self.count = 0

    def send(self, sched, sid, clock):
        s = self.by_id[sid]
        t = self.turn[sid]
        if t >= len(s["turn_new"]):
            t = self.turn[sid] = 0
            self.history[sid] = s["base"]
        prompt = np.concatenate([self.history[sid], s["turn_new"][t]])
        rid = f"{sid}.{self.count}"
        self.count += 1
        self.tracker.submitted(rid, clock(), prompt, s["turn_out"][t],
                               meta=sid)
        sched.submit(self.Request(id=rid, prompt=prompt,
                                  max_new_tokens=s["turn_out"][t]))

    def came_back(self, rec):
        sid = rec["meta"]
        self.history[sid] = np.concatenate(
            [rec["prompt"], np.asarray(rec["tokens"], np.int32)])
        self.turn[sid] += 1
        return sid


def run_closed(sched, sess: Sessions, tracker, t_open, seconds, clock,
               marks=()):
    completed = []
    marks = sorted(marks, key=lambda m: m[0])
    for s in sess.sessions:
        sess.send(sched, s["id"], clock)
    while clock() - t_open < seconds:
        while marks and clock() - t_open >= marks[0][0]:
            marks.pop(0)[1]()
        for c in sched.step():
            rec = tracker.completed(c)
            completed.append(rec)
            sess.send(sched, sess.came_back(rec), clock)
    return completed


def drain(sched, tracker, clock, limit=DRAIN_SECONDS):
    """After the close: no new arrivals; what is in flight may finish, up
    to a minute past the close (late work, not in the window's metrics);
    what has not finished by then never came: it is failed."""
    t = clock()
    while sched.pending() and clock() - t < limit:
        for c in sched.step():
            tracker.completed(c)


def run(cell, args, t0: float) -> int:
    sys.path.insert(0, cell.program_root)
    os.chdir(cell.program_root)
    import jax

    dev = jax.devices()
    if not args.rehearsal and (dev[0].platform != "tpu"
                               or len(dev) < cell.chips):
        print(f"perfbench: no chip for this cell (platform "
              f"{dev[0].platform}, {len(dev)} devices)", file=sys.stderr)
        return 3
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request

    mix = cell.traffic
    seed = args.seed % (2 ** 31 - 1)
    spans, tracker = Spans(), Tracker()
    counters = {"decode_tokens": 0, "decode_ctx": 0, "prefill_tokens": 0,
                "prefill_ctx": 0}
    engine, sched, d = build_server(cell, seed, spans)
    instrument(engine, sched, spans, counters)
    clock = time.monotonic
    fault = args.fault
    if fault == "token_altered":
        inner = engine.decode_step

        def altered(*a, **k):
            out = np.array(inner(*a, **k))
            out[:] = (out + 7) % d["vocab"]
            return out

        engine.decode_step = altered

    # ---- set-up: every program the traffic uses runs once ---------------
    vocab = d["vocab"]
    warm_rng = np.random.default_rng([seed, 0x3A73])
    sess = None
    if mix["loop"] == "closed":
        sessions = traffic.closed_loop(mix, seed, vocab)
        sess = Sessions(sessions, tracker, Request)
        # the contexts: one request per session over its base context fills
        # the cache the turns will read (the traffic needs it: set-up)
        for s in sessions:
            sched.submit(Request(id="ctx." + s["id"], prompt=s["base"],
                                 max_new_tokens=WARM_NEW_TOKENS))
    for b in mix["server"]["prefill_buckets"]:
        n = min(b, mix["server"]["max_len"] - WARM_NEW_TOKENS)
        sched.submit(Request(
            id=f"warm.{b}", max_new_tokens=WARM_NEW_TOKENS,
            prompt=warm_rng.integers(3, vocab, size=n).astype(np.int32)))
    t_warm = clock()
    while sched.pending():
        sched.step()
        if clock() - t_warm > 600.0:
            print("perfbench: set-up requests never finished",
                  file=sys.stderr)
            return 1
    sched.completed.clear()
    sched.step_seconds.clear()
    for v in spans.records.values():
        v.clear()
    for k in counters:
        counters[k] = 0

    # ---- the window -------------------------------------------------------
    # An open loop first runs its arrival process for ``preroll_s`` (set-up:
    # the server has to be in its steady state, with requests of every age
    # in flight, when the window opens); a closed loop is steady at once.
    preroll = float(mix.get("preroll_s", 0.0)) if mix["loop"] == "open" \
        else 0.0
    reqs = None
    if mix["loop"] == "open":
        reqs = traffic.open_loop(mix, seed, preroll + args.seconds, vocab)
    trace_dir = os.path.join(cell.work_dir(), "trace")
    trace_seconds = float(mix.get("trace_seconds", 5.0))
    tracing = bool(args.trace)
    box = {"tracing": False}
    gc_watch = window_notes.GcWatch()

    def open_window():
        gc_watch.start()
        box["tokens0"] = committed_tokens(sched, tracker)
        box["counters0"] = dict(counters)
        box["program_counters0"] = program_records.counters()
        for v in spans.records.values():
            v.clear()
        box["setup_s"] = time.time() - t0
        box["t_open"] = clock()

    # A traced run reads its host-clock numbers (spans, counters, request
    # stamps) from the part of the window BEFORE the profiler starts — its
    # start stalls the loop for seconds — and its device numbers from the
    # ``trace_seconds`` after it.
    def start_trace():
        import shutil

        box["counters1"] = dict(counters)
        box["program_counters1"] = program_records.counters()
        box["t_cut"] = clock()
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        program_records.write_scopes(cell.work_dir())
        jax.profiler.start_trace(trace_dir)
        spans.annotate = True
        box["tracing"] = True
        box["t_trace"] = clock()

    def stop_trace():
        if box["tracing"]:
            box["tracing"] = False
            spans.annotate = False
            jax.profiler.stop_trace()

    trace_marks = []
    if tracing:
        trace_marks = [(max(1.0, args.seconds - trace_seconds - 5.0),
                        start_trace)]
        inner_step = sched.step

        def step_and_stop(*a, **k):
            out = inner_step(*a, **k)
            if box["tracing"] and clock() - box["t_trace"] >= trace_seconds:
                stop_trace()
            return out

        sched.step = step_and_stop
    late, offered = [], 0
    if mix["loop"] == "open":
        t_start = clock()
        completed, late, offered = run_open(
            sched, reqs, tracker, t_start, preroll + args.seconds, Request,
            clock, marks=[(preroll, open_window)] + [
                (preroll + t, fn) for t, fn in trace_marks])
    else:
        open_window()
        completed = run_closed(sched, sess, tracker, box["t_open"],
                               args.seconds, clock, marks=trace_marks)
    t_close = clock()
    gc_watch.stop()
    stop_trace()
    t_open, setup_s = box["t_open"], box["setup_s"]
    window_s = t_close - t_open
    out_tokens = committed_tokens(sched, tracker) - box["tokens0"]
    in_window = [r for r in completed
                 if t_open <= r["finished_at"] <= t_close]
    window_counters = {k: counters[k] - box["counters0"][k]
                       for k in counters}
    in_flight = len(sched.active) + len(sched.queue)
    # what the per-layer readers see: the whole window, or in a traced run
    # its untraced part
    t_cut = box.get("t_cut", t_close)
    layer_counters = {k: box.get("counters1", counters)[k]
                      - box["counters0"][k] for k in counters}
    layer_reqs = [r for r in in_window if r["finished_at"] <= t_cut]
    program_counters = program_records.change(
        box["program_counters0"],
        box.get("program_counters1") or program_records.counters())
    drain(sched, tracker, clock)
    peak = result.memory_peak_bytes()

    # ---- end-to-end numbers, over all the work and all the time ----------
    ttft = [r["first_token_at"] - r["t_ref"] for r in in_window]
    # (last - first token commit) / (tokens - 1), the scheduler's stamps
    tpot = [(r["finished_at"] - r["first_token_at"]) / (len(r["tokens"]) - 1)
            for r in in_window if len(r["tokens"]) > 1]
    # ``serve_tok_s`` is printed where the manifest lists the cell: a closed
    # loop, or an open one at or above its knee. Under the knee the count is
    # the schedule's own tokens plus the backlog carried over the window's
    # two edges, which a faster server shrinks (tools/schedule_model.py).
    e2e = {"serve_tok_s": out_tokens / window_s, "setup_s": setup_s}
    if tpot:
        e2e["tpot_p95_ms"] = stats.percentile(tpot, 95) * 1e3
    # attempted: requests due in the window; failed: finished with another
    # length than asked, or not finished a minute past the close (an answer
    # that never came enters no tail as fast: it fails the run)
    attempted = sum(1 for r in tracker.reqs.values() if r["t_ref"] >= t_open)
    bad = [r for r in tracker.reqs.values()
           if r["done"] and len(r["tokens"]) != r["want"]]
    unfinished = sum(1 for r in tracker.reqs.values() if not r["done"])
    failed = len(bad) + unfinished
    wanted = {m["name"]: m["unit"] for m in cell.end_to_end()}
    metrics = {k: (v, wanted[k]) for k, v in e2e.items() if k in wanted}

    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": peak}
    notes = {"requests_in_window": len(in_window), "attempted": attempted,
             "offered": offered, "window_s": window_s,
             "out_tokens": out_tokens,
             "ttft_p50_ms": (stats.percentile(ttft, 50) or 0) * 1e3,
             "ttft_p95_ms": (stats.percentile(ttft, 95) or 0) * 1e3,
             "tpot_p50_ms": (stats.percentile(tpot, 50) or 0) * 1e3,
             "generator_late_p95_ms":
                 (stats.percentile(late, 95) or 0) * 1e3,
             "generator_late_max_ms": (max(late) if late else 0) * 1e3,
             "in_flight_at_close": in_flight,
             "unfinished_after_drain": unfinished,
             "counters": window_counters,
             "where_the_window_went": window_notes.of(
                 spans.records, box["t_open"], t_close, gc_watch.pauses)}

    # ---- per-layer, from spans, counters and the trace --------------------
    breakdown = None
    if tracing:
        from . import trace_reduce

        try:
            peaks = peaks_of(dev[0].device_kind)
        except KeyError:
            peaks = None
        try:
            trace = trace_reduce.reduce_dir(trace_dir)
        except FileNotFoundError:
            trace = None
        ctx = {"cell": cell, "dims": d, "traffic": mix, "chips": cell.chips,
               "peaks": peaks, "e2e": e2e, "trace": trace,
               "serve": {
                   "spans": {k: [x for x in v if x[1] <= t_cut]
                             for k, v in spans.records.items()},
                   "counters": layer_counters,
                   "program_counters": program_counters,
                   "window_s": t_cut - t_open,
                   "ttft_s": [r["first_token_at"] - r["t_ref"]
                              for r in layer_reqs],
                   "tpot_s": [(r["finished_at"] - r["first_token_at"])
                              / (len(r["tokens"]) - 1) for r in layer_reqs
                              if len(r["tokens"]) > 1]}}
        metrics = result.read_per_layer(cell, ctx)
        if trace and trace.get("busy_s") is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            breakdown = trace_reduce.breakdown(trace)

    # ---- correct: the served tokens against the plain reference -----------
    finished = [r for r in tracker.reqs.values() if r["done"]]
    del engine, sched, sess
    gc.collect()
    for arr in jax.live_arrays():
        arr.delete()
    jax.clear_caches()
    gc.collect()
    from . import serve_compare

    t_ref = time.time()
    cmp = serve_compare.run(cell, seed, d, finished,
                            control=getattr(args, "control", ""))
    notes["reference_s"] = time.time() - t_ref
    notes.update(cmp.get("notes", {}))
    compared = cmp["compared"]
    compared["requests_failed"] = result.compared_entry(failed, 0,
                                                        exact=True)
    correct = all(c["ok"] for c in compared.values())
    result.emit(correct, attempted, failed, metrics, device, compared,
                breakdown, notes=notes)
    return 0
