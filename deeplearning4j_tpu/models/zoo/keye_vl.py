"""Keye-VL-2.0's language model as a ComputationGraph: a decoder whose every
layer is grouped-query attention under a learned sparse selection (an
indexer, the top `topk` keys a query) followed by a mixture of experts, with
three-axis rotary positions and an image's embeddings spliced into the token
embeddings. The second block family of the zoo behind the containers' one
seam (`*_conf(...)` -> ComputationGraphConfiguration, as `resnet50_conf`).

Source: https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json
(text config with `sa_config`); the defaults below are its values. What the
config leaves open (per-head RMSNorm of q and k, the indexer's inputs and its
loss, the optimizer) is listed in `benchmarks/configs/keye-vl-2.0-30b-a3b.json`
under `assumed`. The vision tower is not built: its output enters as the
`image` input, [B, P, hidden_size].

Inputs, in order (`fit(MultiDataSet([ids, image, positions], [labels],
labels_masks=[mask]))`): `ids` [B, T] int32 (any id at an image position),
`image` [B, P, hidden_size], `positions` [B, T, 3] int32 (axes t, h, w; equal
on text). Labels [B, T] int32 (the next token), mask [B, T] (text positions).

One chip's share of an expert-parallel deployment is the same function with
`experts_held` (and `first_held`) and `vocab_rows` set: the router keeps its
width, the chip computes its own experts' part, ids, logits and loss are over
the vocabulary slice; `n_layers` cuts the depth (the period is one layer).
"""
from __future__ import annotations

from ...nn.conf.graph_vertices import ElementWiseVertex
from ...nn.conf.layers import (LMHeadLayer, MoELayer, RMSNormLayer,
                               SparseAttentionLayer, TokenEmbeddingLayer)
from ...nn.conf.neural_net_configuration import NeuralNetConfiguration


def keye_vl_conf(hidden_size=2048, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, rms_norm_eps=1e-6,
                 rope_theta=1e7, mrope_section=(16, 24, 24),
                 vocab_size=151936, num_experts=128, num_experts_per_tok=8,
                 moe_intermediate_size=768, norm_topk_prob=True,
                 num_hidden_layers=48, indexer_num_heads=16,
                 indexer_head_dim=64, topk=2048, q_chunk_size=512,
                 n_layers=None, experts_held=None, first_held=0,
                 vocab_rows=None, seed=123,
                 learning_rate=1e-4, updater="adam", data_type="bfloat16",
                 remat=True, initializer_range=0.02):
    D, std = hidden_size, initializer_range
    gb = (NeuralNetConfiguration.Builder()
          .seed(seed).updater(updater).learning_rate(learning_rate)
          .activation("identity").data_type(data_type)
          .remat_segments(remat)
          .graph_builder().add_inputs("ids", "image", "positions"))
    gb.add_layer("embed", TokenEmbeddingLayer(
        n_in=vocab_rows or vocab_size, n_out=D, init_std=std), "ids", "image")
    x = "embed"
    for i in range(n_layers or num_hidden_layers):
        gb.add_layer(f"l{i}_norm1", RMSNormLayer(n_in=D, eps=rms_norm_eps), x)
        gb.add_layer(f"l{i}_attn", SparseAttentionLayer(
            n_in=D, n_out=D, n_heads=num_attention_heads,
            n_kv_heads=num_key_value_heads, head_dim=head_dim,
            eps=rms_norm_eps, rope_theta=float(rope_theta),
            mrope_section=tuple(mrope_section),
            indexer_heads=indexer_num_heads,
            indexer_head_dim=indexer_head_dim, topk=topk,
            q_chunk_size=q_chunk_size, init_std=std),
            f"l{i}_norm1", "positions")
        gb.add_vertex(f"l{i}_add1", ElementWiseVertex(op="add"), x,
                      f"l{i}_attn")
        gb.add_layer(f"l{i}_norm2", RMSNormLayer(n_in=D, eps=rms_norm_eps),
                     f"l{i}_add1")
        gb.add_layer(f"l{i}_moe", MoELayer(
            n_in=D, n_out=D, n_experts=num_experts,
            experts_per_token=num_experts_per_tok,
            expert_width=moe_intermediate_size,
            norm_topk_prob=norm_topk_prob, experts_held=experts_held,
            first_held=first_held, init_std=std), f"l{i}_norm2")
        gb.add_vertex(f"l{i}_add2", ElementWiseVertex(op="add"),
                      f"l{i}_add1", f"l{i}_moe")
        x = f"l{i}_add2"
    gb.add_layer("norm_f", RMSNormLayer(n_in=D, eps=rms_norm_eps), x)
    gb.add_layer("head", LMHeadLayer(n_in=D, n_out=vocab_rows or vocab_size,
                                     init_std=std), "norm_f")
    return gb.set_outputs("head").build()
