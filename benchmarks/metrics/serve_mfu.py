"""The whole serving step's share of the chip's peak over the traced window:
2 x matmul parameters x every prompt and output row actually computed there
(rows the prefix cache held are excluded; attention's own products are not
counted), over the window's length and the peak. In percent."""


def read(ctx):
    from ..harness import work
    if "traced" not in ctx["marks"]:
        return None
    a, b = ctx["marks"]["start"], ctx["marks"]["traced"]
    d = lambda k: b[k] - a[k]
    rows = d("tokens_out") + d("prefix_rows_total") - d("prefix_rows_hit")
    if rows <= 0:
        return None
    flops = 2.0 * work.lm_matmul_params(ctx["model"]) * rows
    return 100.0 * flops / (ctx["trace"]["window_s"]
                            * ctx["peaks"]["bf16_flops"])
