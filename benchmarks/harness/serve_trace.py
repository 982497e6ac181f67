"""What the serving metrics' readers share: which traced module is the decode
program and which the chunked-prefill program, and how many context rows
each decoding request held at a given time."""
import statistics

from . import trace as T


def step_programs(ctx):
    """(decode runs, chunk runs), each [(start_ns, seconds)]. The server jits
    both under the name `step`; they differ in their fingerprint. The chunk
    program reads the same weights and cache windows as the decode program
    and computes `chunk` times the rows, so of two the slower is the chunk
    program. With one, the counters say which one ran."""
    tr = ctx["trace"]
    runs = T.program_runs(tr["trace"], tr["t0"], tr["t1"])
    steps = sorted((r for n, r in runs.items()
                    if T.short_name(n) == "jit_step"),
                   key=lambda r: statistics.median(T.seconds(r)))
    if len(steps) == 2:
        return steps[0], steps[1]
    if len(steps) == 1:
        m0, m1 = ctx["marks"]["start"], ctx["marks"]["traced"]
        if m1["chunk_dispatches"] == m0["chunk_dispatches"]:
            return steps[0], []
        if m1["dispatches"] == m0["dispatches"]:
            return [], steps[0]
    return [], []


def live_rows_at(ctx, t):
    """Context rows of every request that was decoding at time t (on
    time.monotonic()'s clock): its prompt and the tokens it had by then, one
    token an iteration between its first token and its completion."""
    rows = []
    for i, (t_first, _) in ctx["first"].items():
        if t_first > t:
            continue
        req = ctx["log"][i]["req"]
        t_done = ctx["done"][i][0] if i in ctx["done"] else ctx["t_end"]
        if t >= t_done or req["max_new"] < 2:
            continue
        share = (t - t_first) / max(t_done - t_first, 1e-9)
        rows.append(len(req["prompt"]) + 1
                    + int(share * (req["max_new"] - 1)))
    return rows


def decode_work(ctx):
    """[(seconds, flops, bytes)] of every decode run in the traced window,
    the work being what the algorithm needs for the rows live at its
    start."""
    from . import work
    decode, _ = step_programs(ctx)
    off = ctx["trace"]["clock_offset_s"]
    out = []
    for start_ns, secs in decode:
        live = live_rows_at(ctx, start_ns / 1e9 + off)
        if live:
            out.append((secs, *work.lm_decode_step_work(ctx["model"], live)))
    return out
