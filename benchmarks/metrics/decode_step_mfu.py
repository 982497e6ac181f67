"""The decode step's share of the chip's peak FLOP/s over the traced window:
the FLOPs its dispatches needed (active slots only) over their device time
and the peak. In percent."""


def read(ctx):
    from ..harness import serve_trace
    runs = serve_trace.decode_work(ctx)
    if not runs:
        return None
    return 100.0 * sum(f for _, f, _ in runs) / (
        sum(s for s, _, _ in runs) * ctx["peaks"]["bf16_flops"])
