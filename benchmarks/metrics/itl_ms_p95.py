"""The 95th percentile of the gaps between output tokens that ended in the
window (raw samples). It sits where the share of iterations that also install
a short prompt crosses a twentieth, and flips between 648 and 781 ms: a
per-layer reading, not one to bound (PERF.md, Open questions,
`gpt2-xl.chat-open`)."""


def read(ctx):
    return ctx["end_to_end"].get("itl_ms_p95")
