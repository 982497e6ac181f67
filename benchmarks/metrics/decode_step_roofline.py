"""The decode program's share of its roofline over the traced window: the
least time the chip could take for each dispatch's work (the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s, the bytes being every weight
once and the LIVE rows' keys and values once) over its device time. In
percent; HBM-bound at these shapes."""


def read(ctx):
    from ..harness import serve_trace, work
    runs = serve_trace.decode_work(ctx)
    if not runs:
        return None
    least = sum(work.roofline_seconds(f, b, ctx["peaks"])[0]
                for _, f, b in runs)
    return 100.0 * least / sum(s for s, _, _ in runs)
