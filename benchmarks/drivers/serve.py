"""Driver of the serving cells: the zoo's TransformerLM behind
ContinuousDecodeServer in its production configuration, offered the traffic
file's load through `submit` from one feeder thread: an open loop on a
schedule, or a closed loop of clients that each wait for their reply.

Set-up: seeded weights, one warm-up request for each program the traffic
reaches, then a ramp of the same traffic to the steady occupancy. After the
window closes, the requests due inside it drain, the server is freed and the
plain reference runs over a seeded sample of the finished requests.
"""
import gc
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import compare, loader, traffic as traffic_gen, weights
from ..harness.window import memory_peak_bytes, now

DRAIN_S = 90.0          # how long past the close a due answer is waited for
COUNTERS = ("tokens_out", "prefix_rows_total", "prefix_rows_hit",
            "dispatches", "chunk_dispatches", "decode_iterations",
            "completed", "received")


def build_server(cfg, aux, blocks, recorder, spans=None):
    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
    from deeplearning4j_tpu.serving import ContinuousDecodeServer
    m = cfg["model"]
    # n_layers=0: the container without its own random blocks; the weights
    # are the benchmark's (one jitted call), installed as a swap would
    lm = TransformerLM(m["vocab_size"], d_model=m["n_embd"],
                       n_heads=m["n_head"], n_layers=0, d_ff=m["n_inner"],
                       max_len=m["n_positions"], dtype=jnp.bfloat16)
    lm.aux, lm.blocks = aux, blocks
    s = dict(cfg["server"])
    s["prompt_buckets"] = tuple(s["prompt_buckets"])
    return ContinuousDecodeServer(lm, metrics=recorder, tracer=spans, **s)


def warm(srv, plan, cfg, rng):
    """One request for each prefill program the traffic reaches (its one-shot
    buckets, the chunk program) with two tokens, so the decode program runs
    too. Everything the window dispatches has then been compiled."""
    chunk, buckets = cfg["server"]["chunked_prefill"], \
        sorted(cfg["server"]["prompt_buckets"])
    need = set()
    for r in plan["ramp"] + plan["window"]:
        n = len(r["prompt"])
        need.add(chunk + 1 if n > chunk
                 else next(b for b in buckets if b >= n))
    futs = [srv.submit(rng.integers(1, cfg["model"]["vocab_size"], n), 2)
            for n in sorted(need)]
    for f in futs:
        f.result(timeout=1100)


class Feeder:
    """The one thread that submits. Every request is logged with its due
    time and the clock before and after its `submit` call."""

    def __init__(self, srv, tracer):
        self.srv, self.tracer = srv, tracer
        self.log = []
        self.stop = threading.Event()
        self.thread = None

    def submit(self, req, due, phase):
        t_before = now()
        try:
            with self.tracer.annotate("bench.submit"):
                fut = self.srv.submit(req["prompt"], req["max_new"])
        except Exception as e:      # noqa: BLE001 shed or refused: a failure
            fut = e
        self.log.append({"req": req, "due": due, "phase": phase,
                         "t_before": t_before, "t_after": now(),
                         "future": fut})
        return fut

    def open_loop(self, schedule):
        for due, phase, req in schedule:
            delay = due - now()
            if delay > 0:
                time.sleep(delay)
            self.submit(req, due, phase)

    def closed_loop(self, sequence, clients):
        """`clients` callers, each sending its next request when its reply
        arrives; the replies' callbacks only queue the client's turn."""
        turns = queue.SimpleQueue()
        for _ in range(clients):
            turns.put(None)
        i = 0
        while not self.stop.is_set():
            try:
                turns.get(timeout=0.05)
            except queue.Empty:
                continue
            fut = self.submit(sequence[i % len(sequence)], now(), "loop")
            i += 1
            if isinstance(fut, Exception):
                turns.put(None)
            else:
                fut.add_done_callback(lambda _f: turns.put(None))

    def start(self, fn, *args):
        self.thread = threading.Thread(target=fn, args=args,
                                       name="bench-feeder", daemon=True)
        self.thread.start()


def sleep_until(t):
    d = t - now()
    if d > 0:
        time.sleep(d)


def join_samples(log, samples, slack=2e-3):
    """Each (t, ms) sample to the request it was stamped from. t - ms is the
    server's own submit stamp (plus the few microseconds between its clock
    reading and the recorder's), which lies between the feeder's readings
    around that `submit` call. Requests and samples are both in submit
    order, so one pass pairs them: a sample goes to the earliest unpaired
    request whose call it can have come from."""
    est = sorted((t - ms / 1e3, (t, ms, *rest)) for t, ms, *rest in samples)
    out, j = {}, 0
    for t_submit, sample in est:
        while j < len(log) and log[j]["t_after"] + slack < t_submit:
            j += 1                      # request j never got that far
        if j < len(log) and log[j]["t_before"] <= t_submit + 1e-5:
            out[j] = sample
            j += 1
    return out


def run(cell, seed, seconds, tracer, setup_done, on_served=None,
        control=False):
    from ..harness.recorder import Recorder
    cfg, traffic = cell["config"], cell["traffic"]
    model = cfg["model"]
    aux, blocks = weights.lm_weights(seed, model)
    rec = Recorder()
    spans = None
    if tracer.enabled:      # the program's own host spans, traced runs only
        from deeplearning4j_tpu.obs.trace import Tracer as SpanTracer
        spans = SpanTracer(capacity=1 << 18, enabled=True)
    srv = build_server(cfg, aux, blocks, rec, spans)
    plan = traffic_gen.build(traffic, seconds, seed, model["vocab_size"],
                             model["n_positions"])
    rng = np.random.default_rng(int(seed) + 1)
    feeder = Feeder(srv, tracer)
    srv.start()
    try:
        warm(srv, plan, cfg, rng)
        tracer.start()
        ramp_s = traffic["ramp_seconds"]
        t_start = now() + 0.05 + ramp_s
        if traffic["kind"] == "open_loop":
            schedule = [(t_start - ramp_s + r["due"], "ramp", r)
                        for r in plan["ramp"]] + \
                       [(t_start + r["due"], "window", r)
                        for r in plan["window"]]
            feeder.start(feeder.open_loop, schedule)
        else:
            feeder.start(feeder.closed_loop, plan["window"],
                         traffic["clients"])
        sleep_until(t_start)
        setup_done(t_start)
        marks = {"start": rec.counters(COUNTERS)}
        if tracer.enabled:
            with tracer.window():
                time.sleep(tracer.seconds)
            marks["traced"] = rec.counters(COUNTERS)
            tracer.stop()
        sleep_until(t_start + seconds)
        t_end = now()
        marks["end"] = rec.counters(COUNTERS)
        feeder.stop.set()
        feeder.thread.join(timeout=seconds + 30)
        log = feeder.log
        in_window = [i for i, e in enumerate(log)
                     if t_start <= e["due"] < t_end]
        give_up = t_end + DRAIN_S
        for i in in_window:         # answers due in the window: wait
            f = log[i]["future"]
            if not isinstance(f, Exception):
                try:
                    f.result(timeout=max(0.0, give_up - now()))
                except Exception:   # noqa: BLE001 judged below as failed
                    pass
        t_drained = now()
    finally:
        srv.stop(drain=False, timeout=30)
    peak = memory_peak_bytes(jax.local_devices()[:1])

    first = join_samples(log, rec.ttft)
    done = join_samples(log, rec.requests)
    failed, streams = 0, {}
    for i in in_window:
        f = log[i]["future"]
        ok = (not isinstance(f, Exception) and f.done()
              and f.exception() is None and i in first)
        if ok:
            streams[i] = np.asarray(f.result(), np.int32)
        else:
            failed += 1
    if on_served is not None:       # tests plant faults where answers leave
        streams = on_served(streams)

    # ---- the window is closed and read; free the server, then compare ----
    t_check = now()
    n_heads = model["n_head"]
    del srv, feeder
    gc.collect()
    numbers = served_numbers(loader.reference(cfg), aux, blocks, n_heads,
                             log, streams, traffic["sample_requests"], seed,
                             model["n_positions"], control=control)
    ok, compared = compare.judge(numbers, cfg["limits"])
    check_s = now() - t_check

    in_win = lambda t: t_start <= t < t_end
    tokens = sum(1 for t, _ in rec.ttft if in_win(t)) \
        + sum(1 for t, _ in rec.inter_token if in_win(t))
    worst = (t_drained - t_start) * 1e3
    ttft = [(first[i][0] - log[i]["due"]) * 1e3 if i in streams else worst
            for i in in_window]
    e2e = {"tokens_per_s": tokens / (t_end - t_start)}
    if traffic["kind"] == "open_loop":
        # plain percentiles of every request due, and of every gap that
        # ended, inside the window; BENCHMARK.json says which are judged
        itl = [ms for t, ms in rec.inter_token if in_win(t)]
        for q in (50, 75, 90):
            e2e[f"ttft_ms_p{q}"] = compare.percentile(ttft, q)
        for q in (50, 75, 90, 95):
            e2e[f"itl_ms_p{q}"] = compare.percentile(itl, q)
    return {
        "correct": ok and failed == 0, "compared": compared,
        "read": {k: v for k, v in numbers.items() if k not in compared},
        "attempted": len(in_window), "failed": failed,
        "memory_peak_bytes": int(peak), "end_to_end": e2e,
        "check_s": check_s, "drain_s": t_drained - t_end,
        "ctx": {"cell": cell, "recorder": rec, "log": log, "first": first,
                "done": done, "in_window": in_window, "marks": marks,
                "spans": spans, "t_start": t_start, "t_end": t_end,
                "t_drained": t_drained,
                "model": model,
                "trace": tracer.result()},
    }


def sample_of(streams, log, n, seed):
    """A seeded sample of the finished requests, the longest always in it."""
    ids = sorted(streams)
    if not ids:
        return []
    longest = max(ids, key=lambda i: len(streams[i]))
    rng = np.random.default_rng(int(seed) + 2)
    rest = [i for i in ids if i != longest]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[j] for j in pick]


def served_numbers(ref, aux, blocks, n_heads, log, streams, n, seed, max_len,
                   control=False):
    """The numbers `correct` compares. `malformed_streams`: answers that do
    not start with their prompt or have the wrong length (all finished
    requests). `logit_gap`: over a seeded sample, the widest gap by which a
    served token's logit lies below the reference's best at its position.
    With `control`, also the gap of the token the fp8 reference puts first
    (`control_logit_gap`), read at the same positions."""
    bad = 0
    for i, s in streams.items():
        p, new = log[i]["req"]["prompt"], log[i]["req"]["max_new"]
        if len(s) != len(p) + new or not np.array_equal(s[:len(p)], p):
            bad += 1
    out = {"malformed_streams": float(bad)}
    gaps, ctl, served = [0.0], [0.0], 0
    for i in sample_of(streams, log, n, seed):
        s = streams[i]
        plen = len(log[i]["req"]["prompt"])
        toks = np.zeros(max_len, np.int32)
        toks[:min(len(s), max_len)] = s[:max_len]
        nxt = np.roll(toks, -1)
        pos = np.arange(max_len)
        valid = (pos >= plen - 1) & (pos <= len(s) - 2)
        with jax.default_matmul_precision("highest"):
            ref_logits = ref.logits(aux, blocks, jnp.asarray(toks), n_heads)
            gaps.append(float(jnp.max(ref.served_gaps(
                ref_logits, jnp.asarray(nxt), jnp.asarray(valid)))))
            if control:
                low = ref.logits(aux, blocks, jnp.asarray(toks), n_heads,
                                 quant=True)
                ctl.append(float(jnp.max(ref.served_gaps(
                    ref_logits, jnp.argmax(low, -1), jnp.asarray(valid)))))
        served += int(valid.sum())
    out["logit_gap"] = max(gaps) if served else float("nan")
    if control:
        out["control_logit_gap"] = max(ctl)
    out["served_tokens_compared"] = float(served)
    return out


def calibrate(cell, seeds, emit, seconds=20.0):
    """Program and control readings over many seeds in one process, each
    from a short window at the cell's own load."""
    from ..harness.window import Tracer
    for seed in seeds:
        out = run(cell, seed, seconds, Tracer(False, None), lambda t: None,
                  control=True)
        nums = {k: c["value"] for k, c in out["compared"].items()}
        emit(seed, "program", {
            "logit_gap": nums["logit_gap"], "failed": out["failed"],
            "served": out["read"]["served_tokens_compared"]})
        emit(seed, "control_fp8",
             {"logit_gap": out["read"]["control_logit_gap"]})
        del out
        gc.collect()
