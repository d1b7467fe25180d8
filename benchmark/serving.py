"""One serving cell's run: weights from the seed, the engine as the
configuration's file builds it, a warm-up worked out from the traffic
file, a fill that brings the engine to its steady state, then the window.

The window drives ``ServeEngine.submit`` and ``ServeEngine.step`` and
nothing else of the program. Every time comes from the engine's
FlightRecorder events (monotonic stamps) or from this loop's own table;
none from the program's log-bucketed histograms.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import check, traffic
from benchmark.family import seed_key

#: the tail of the window that a ``--trace 1`` run traces
TRACE_SECONDS = 5.0
STEP_SPAN = "bench.engine_step"


def build_weights(fam, seed: int):
    """The program's variables, made on the device in one jitted call
    from the seed (the reference makes the same numbers for itself)."""
    import jax

    make = jax.jit(lambda key: fam.adapter.to_program(
        fam.reference.init_params(key, fam.sz), fam.sz))
    return jax.block_until_ready(make(seed_key(seed)))


def build_graph(fam):
    from mmlspark_tpu.models import build_model

    return build_model(fam.builder, **fam.cfg["program"]["model"])


def build_engine(fam, variables, max_queue: int):
    from mmlspark_tpu.core.telemetry import FlightRecorder
    from mmlspark_tpu.serve.engine import ServeEngine

    recorder = FlightRecorder(capacity=1 << 21)
    return ServeEngine(build_graph(fam), variables, recorder=recorder,
                       max_queue=max_queue, **fam.cfg["program"]["engine"])


def warm_up(engine, mix: dict, vocab: int, slots: int) -> None:
    """Every program the mix can reach, each once, worked out from the
    file: one request walks the whole decode-block ladder alone (a budget
    of twice the largest block leaves 32+16+8+4+2+1 after its first
    token), then one short request for every prompt length the file's
    grid holds (its prefill bucket, and the pool write the program
    compiles anew for every distinct length)."""
    rng = np.random.default_rng(0)
    lens = traffic.prompt_lengths(mix)

    def prompt(n):
        return rng.integers(0, vocab, size=n).astype(np.int32)

    engine.submit(prompt(lens[0]), 2 * engine.decode_block)
    engine.run()
    for lo in range(0, len(lens), slots):
        for n in lens[lo:lo + slots]:
            engine.submit(prompt(n), 2)
        engine.run()


class Loop:
    """The load generator and the engine's loop, one thread: arrivals are
    read off a table made before the window."""

    def __init__(self, engine, reqs: list, mix: dict):
        self.engine, self.reqs, self.mix = engine, reqs, mix
        self.rows: list[dict] = []      # one per request sent
        self.by_id: dict[int, dict] = {}
        self.results: dict[int, object] = {}
        self.failed = 0
        self.tick_ends: list[float] = []   # monotonic end of every tick

    def send(self, req, due: float | None, timed: bool,
             max_new: int | None = None) -> None:
        row = {"due": due, "timed": timed, "prompt": req.prompt,
               "max_new": max_new or req.max_new, "sent": time.monotonic()}
        try:
            row["id"] = self.engine.submit(req.prompt, row["max_new"])
            self.by_id[row["id"]] = row
        except Exception as e:   # a refused request is a failed one
            row["id"], row["error"] = None, repr(e)
            self.failed += 1
        self.rows.append(row)

    def step(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation(STEP_SPAN):
            for res in self.engine.step():
                self.results[res.id] = res
        self.tick_ends.append(time.monotonic())


def run_backlog(loop: Loop, slots: int, seconds: float, tracer) -> tuple:
    mix, reqs = loop.mix, loop.reqs
    budgets = (traffic.first_slotful_budgets(reqs, slots)
               if mix.get("stagger_first_slotful") else [])
    target = slots + int(mix["queued"])
    sent = 0

    def top_up():
        nonlocal sent
        while loop.engine.queue_depth < target:
            req = reqs[sent % len(reqs)]
            cut = budgets[sent] if sent < len(budgets) else None
            loop.send(req, None, True, cut)
            sent += 1

    # both edges of the window lie on the end of an engine tick, so no
    # block of tokens is cut by an edge: the window is the first whole
    # ticks that cover ``seconds``, and the rate is taken over their span
    fill_until = time.monotonic() + float(mix["fill_s"])
    t_open = None
    while True:
        top_up()
        loop.step()
        now = time.monotonic()
        if t_open is None:
            if now >= fill_until:
                t_open = now
        else:
            tracer.poll(t_open + seconds)
            if now >= t_open + seconds:
                return t_open, now


def run_open_loop(loop: Loop, seconds: float, tracer) -> tuple:
    mix, reqs, engine = loop.mix, loop.reqs, loop.engine
    zero = time.monotonic()
    t_open = zero + float(mix["fill_s"])
    t_close = t_open + seconds
    give_up = t_close + float(mix["drain_s"])
    nxt = 0
    while True:
        now = time.monotonic()
        tracer.poll(t_close)
        while nxt < len(reqs) and zero + reqs[nxt].due <= now:
            loop.send(reqs[nxt], zero + reqs[nxt].due, reqs[nxt].timed)
            nxt += 1
        if engine.busy:
            loop.step()
        elif nxt < len(reqs):
            time.sleep(max(0.0, min(zero + reqs[nxt].due - now, 0.001)))
        if now >= t_close and nxt >= len(reqs) and (
                not engine.busy or now >= give_up):
            break
    return t_open, t_close


class Tracer:
    """Traces the last ``TRACE_SECONDS`` of the window, so the traced
    state is the steady one and the trace stays small."""

    def __init__(self, directory: str | None):
        self.directory = directory
        self.t0 = self.t1 = None

    def poll(self, t_close: float) -> None:
        if self.directory is None or self.t0 is not None:
            return
        if time.monotonic() >= t_close - TRACE_SECONDS:
            import jax

            # the Python tracer would log every call of the host loop: it
            # slows the loop and makes most of the trace's bytes
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            self.t0 = time.monotonic()

    def stop(self) -> None:
        if self.t0 is not None and self.t1 is None:
            import jax

            self.t1 = time.monotonic()
            jax.profiler.stop_trace()


def request_table(events: list, loop: Loop) -> list[dict]:
    """Per request sent: when it was due and sent (this loop's table),
    when it was admitted, gave its first token and finished (the
    recorder's stamps), and what it served."""
    span_of, stamps = {}, {}
    for ev in events:
        span = ev.get("span")
        if span is None or ev.get("span_name") != "request":
            continue
        name = ev["name"]
        if name == "start":
            span_of[span] = ev["attrs"]["id"]
            continue
        rid = span_of.get(span)
        if rid is None:
            continue
        st = stamps.setdefault(rid, {})
        if name == "admitted":
            st["admitted"] = ev["t"]
        elif name == "prefill":
            st["first_token"] = ev["t"]
            st["bucket"] = ev["attrs"]["bucket"]
        elif name in ("completed", "failed", "expired", "stalled"):
            st["finished"], st["status"] = ev["t"], name
    for row in loop.rows:
        row.update(stamps.get(row["id"], {}))
        res = loop.results.get(row["id"])
        if res is not None:
            row["served"] = np.asarray(res.tokens[res.prompt_len:], np.int32)
    return loop.rows


def prepare(fam, mix: dict, seed: int, max_queue: int, clock):
    """Weights, engine and warm-up: the engine ready for its traffic."""
    variables = build_weights(fam, seed)
    clock.mark("weights")
    engine = build_engine(fam, variables, max_queue)
    clock.mark("engine")
    warm_up(engine, mix, fam.sz["v"],
            int(fam.cfg["program"]["engine"]["slots"]))
    clock.mark("warmup")
    return engine


def drive(engine, cfg: dict, mix: dict, reqs: list, seconds: float,
          tracer) -> tuple:
    """The fill and the window, with Python's cyclic collector frozen and
    off. Returns the loop's table and the window's two edges."""
    loop = Loop(engine, reqs, mix)
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        if mix["kind"] == "backlog":
            slots = int(cfg["program"]["engine"]["slots"])
            t_open, t_close = run_backlog(loop, slots, seconds, tracer)
        else:
            t_open, t_close = run_open_loop(loop, seconds, tracer)
    finally:
        tracer.stop()
        gc.enable()
    return loop, t_open, t_close


def requests_for(fam, mix: dict, seed: int, seconds: float) -> tuple:
    """The mix's requests and the queue they need."""
    vocab = fam.sz["v"]
    if mix["kind"] == "backlog":
        slots = int(fam.cfg["program"]["engine"]["slots"])
        return (traffic.backlog_requests(mix, vocab, seed),
                slots + int(mix["queued"]) + 1)
    reqs = traffic.open_loop_requests(mix, vocab, seed, seconds)
    return reqs, len(reqs)


def run(fam, mix: dict, seed: int, seconds: float, trace_dir,
        clock, log, control_modes: tuple = ()) -> dict:
    import jax

    ref, sz = fam.reference, fam.sz
    reqs, max_queue = requests_for(fam, mix, seed, seconds)
    engine = prepare(fam, mix, seed, max_queue, clock)
    tracer = Tracer(trace_dir)
    loop, t_open, t_close = drive(engine, fam.cfg, mix, reqs, seconds,
                                  tracer)
    clock.mark("fill", at=t_open)
    events = engine.recorder.events()
    rows = request_table(events, loop)
    device = jax.devices()[0]
    peak = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))
    cache_len = engine.cache_len
    # the program's state goes before the reference comes
    del engine, loop.engine
    gc.collect()

    bad = ("failed", "expired", "stalled")
    if mix["kind"] == "backlog":
        # a request still decoding when the window closes is not late
        judged = rows
        unanswered = 0
    else:
        judged = [r for r in rows if r["timed"]]
        unanswered = sum(1 for r in judged if "finished" not in r)
    failed = sum(1 for r in judged
                 if r["id"] is None or r.get("status") in bad)
    done = [(r["prompt"], r["served"]) for r in judged
            if r.get("status") == "completed" and r["finished"] > t_open
            and len(r.get("served", ()))]
    samples = check.sample_requests(done, int(mix["check_requests"]), seed)
    numbers = check.served_gaps(ref, sz, seed, samples, cache_len)
    for mode in control_modes:   # only benchmark/limits.py asks for these
        numbers[f"control_gap.{mode}"] = check.served_gaps(
            ref, sz, seed, samples, cache_len, mode)["control_gap"]
    numbers["unanswered"] = unanswered + failed
    ends = loop.tick_ends
    ticks = {"before_window": sum(1 for t in ends if t <= t_open),
             "ms": [round((b - a) * 1e3, 1) for a, b in zip(ends, ends[1:])
                    if t_open <= a and b <= t_close]}
    return {"kind": mix["kind"], "seconds": seconds, "ticks": ticks,
            "t_open": t_open, "t_close": t_close, "events": events,
            "requests": rows, "peak_bytes": peak, "numbers": numbers,
            "attempted": len(judged), "failed": failed + unanswered,
            "trace_span": (tracer.t0, tracer.t1), "cache_len": cache_len}
