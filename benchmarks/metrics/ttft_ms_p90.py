"""The 90th percentile of the time to the first token over every request due
in the window (raw samples). With some fifty requests in a window it has five
beyond it and neighbours there lie a quarter apart: a per-layer reading, not
one to bound (PERF.md, Open questions, `gpt2-xl.chat-open`)."""


def read(ctx):
    return ctx["end_to_end"].get("ttft_ms_p90")
