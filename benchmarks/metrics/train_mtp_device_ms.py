"""Device time a step of the multi-token prediction module, forward and
backward: every operation on the path of a vertex of the module (the zoo
names them `mtp_*`: the tied embedding, the two norms, `W_eh`, the decoder
layer, the final norm, the tied head) or of its loss (`loss.mtp_head`)."""
from ..harness.vertex_scopes import vertices_ms


def read(ctx):
    return vertices_ms(ctx, lambda kind, vertex: vertex.startswith("mtp_"))
