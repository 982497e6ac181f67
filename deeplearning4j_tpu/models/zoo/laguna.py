"""Laguna-XS.2 as a ComputationGraph: a decoder whose layers follow a
PATTERN. `layer_types` says which layers attend inside a window of 512 and
which over every earlier key, `num_attention_heads_per_layer` how many query
heads each has over the same 8 key/value heads (64 on window layers, 48 on
full ones), `mlp_layer_types` which layers are a dense gated MLP (the first)
and which a mixture of 256 small experts beside a shared one. Window layers
turn all 128 slots of a head at theta 10,000; full layers the first 64 at
theta 500,000 under YaRN. Every attention's output passes a per-head sigmoid
gate. The third block family of the zoo behind the containers' one seam
(`*_conf(...)` -> ComputationGraphConfiguration, as `keye_vl_conf`).

Source: https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json;
the defaults below are its values. What the config leaves open (the gate's
form, the router's scoring, the activation) is listed in
`benchmarks/configs/laguna-xs.2.json` under `assumed`.

Input: `ids` [B, T] int32 at positions 0 .. T-1 (`fit(MultiDataSet([ids],
[labels], labels_masks=[mask]))`). Labels [B, T] int32 (the next token),
mask [B, T].

One chip's share of an expert-parallel deployment is the same function with
`experts_held` (and `first_held`) and `vocab_rows` set, as `keye_vl_conf`
has them; `layers` names the published layers that are kept (their types,
head counts and MLP kinds come from the three per-layer lists).
"""
from __future__ import annotations

from ...nn.conf.graph_vertices import ElementWiseVertex
from ...nn.conf.layers import (AttentionLayer, GatedMLPLayer, LMHeadLayer,
                               MoELayer, RMSNormLayer, TokenEmbeddingLayer)
from ...nn.conf.neural_net_configuration import NeuralNetConfiguration

_PERIOD = ("full_attention",) + ("sliding_attention",) * 3
ROPE_PARAMETERS = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}


def laguna_conf(hidden_size=2048, num_key_value_heads=8, head_dim=128,
                rms_norm_eps=1e-6, vocab_size=100352, intermediate_size=8192,
                num_experts=256, num_experts_per_tok=8,
                moe_intermediate_size=512,
                shared_expert_intermediate_size=512,
                moe_routed_scaling_factor=2.5, sliding_window=512,
                num_hidden_layers=40, layer_types=None,
                mlp_layer_types=None, num_attention_heads_per_layer=None,
                rope_parameters=None,
                layers=None, experts_held=None, first_held=0,
                vocab_rows=None, seed=123, learning_rate=1e-4,
                updater="adam", data_type="bfloat16", remat=True,
                initializer_range=0.02):
    L = num_hidden_layers
    layer_types = layer_types or (_PERIOD * L)[:L]
    mlp_layer_types = mlp_layer_types or ("dense",) + ("sparse",) * (L - 1)
    heads = num_attention_heads_per_layer or [
        48 if t == "full_attention" else 64 for t in layer_types]
    rope = rope_parameters or ROPE_PARAMETERS
    D, std, rows = hidden_size, initializer_range, vocab_rows or vocab_size
    norm = lambda: RMSNormLayer(n_in=D, eps=rms_norm_eps)
    gb = (NeuralNetConfiguration.Builder()
          .seed(seed).updater(updater).learning_rate(learning_rate)
          .activation("identity").data_type(data_type)
          .remat_segments(remat)
          .graph_builder().add_inputs("ids"))
    gb.add_layer("embed", TokenEmbeddingLayer(n_in=rows, n_out=D,
                                              init_std=std), "ids")
    x = "embed"
    for i in (range(L) if layers is None else layers):
        r = rope[layer_types[i]]
        yarn = (r["factor"], r["original_max_position_embeddings"],
                r["beta_fast"], r["beta_slow"], r["attention_factor"]
                ) if r["rope_type"] == "yarn" else None
        gb.add_layer(f"l{i}_norm1", norm(), x)
        gb.add_layer(f"l{i}_attn", AttentionLayer(
            n_in=D, n_out=D, n_heads=heads[i], n_kv_heads=num_key_value_heads,
            head_dim=head_dim, rope_theta=float(r["rope_theta"]),
            rotary_dim=int(head_dim * r["partial_rotary_factor"]), yarn=yarn,
            window=(sliding_window if layer_types[i] == "sliding_attention"
                    else None),
            init_std=std), f"l{i}_norm1")
        gb.add_vertex(f"l{i}_add1", ElementWiseVertex(op="add"), x,
                      f"l{i}_attn")
        gb.add_layer(f"l{i}_norm2", norm(), f"l{i}_add1")
        if mlp_layer_types[i] == "dense":
            gb.add_layer(f"l{i}_mlp", GatedMLPLayer(
                n_in=D, n_out=D, width=intermediate_size, init_std=std),
                f"l{i}_norm2")
        else:
            gb.add_layer(f"l{i}_mlp", MoELayer(
                n_in=D, n_out=D, n_experts=num_experts,
                experts_per_token=num_experts_per_tok,
                expert_width=moe_intermediate_size, norm_topk_prob=True,
                experts_held=experts_held, first_held=first_held,
                shared_width=shared_expert_intermediate_size,
                routed_scale=float(moe_routed_scaling_factor), init_std=std),
                f"l{i}_norm2")
        gb.add_vertex(f"l{i}_add2", ElementWiseVertex(op="add"),
                      f"l{i}_add1", f"l{i}_mlp")
        x = f"l{i}_add2"
    gb.add_layer("norm_f", norm(), x)
    gb.add_layer("head", LMHeadLayer(n_in=D, n_out=rows, init_std=std),
                 "norm_f")
    return gb.set_outputs("head").build()
