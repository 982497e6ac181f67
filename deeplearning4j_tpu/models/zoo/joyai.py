"""JoyAI-LLM-Flash as a ComputationGraph: a decoder of multi-head latent
attention (a head scores over 128 slots of its own and 64 against ONE
rotary key that all 32 heads share, and sums values 128 wide; queries and
keys/values through low-rank chains of rank 1536 and 512), a leading dense
layer, then layers of 256 routed experts beside a shared one under a
sigmoid router whose top 8 are chosen by score plus a bias that a rule
moves after every step (no auxiliary loss), and a multi-token prediction
module of depth 1 on the main model's OWN embedding table and output head.
The fourth block family of the zoo behind the containers' one seam
(`*_conf(...)` -> ComputationGraphConfiguration, as `laguna_conf`).

Source: https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json
(`model_type: joyai_llm_flash`); the defaults below are its values. What
the config leaves open (the bias's rate, the second loss's weight, the
order of the module's concatenation) is listed in
`benchmarks/configs/joyai-llm-flash.json` under `assumed`.

Inputs: `ids` [B, T] int32 at positions 0 .. T-1 and, with the prediction
module, `next_ids` [B, T]: the token after each (position T-1's does not
exist; whatever stands there is read by no position whose loss counts).
Outputs: `head` (labels: the next token, mask i <= T-2) and `mtp_head`
(labels: the token after next, mask i <= T-3), so
`fit(MultiDataSet([ids, next_ids], [labels, labels2],
labels_masks=[mask, mask2]))`; the score is L_main + `mtp_loss_weight`
L_mtp, and each output leaves its own loss in its state.

The module (DeepSeek-V3, arXiv:2412.19437 section 2.2) embeds `next_ids`
with `embed`'s table and scores with `head`'s matrix: `mtp_embed` and
`mtp_head` are vertices tied to those (`add_layer(..., params_of=...)`), so
each table is one leaf, its gradient the sum of both uses, under one Adam
state. Between them: an RMSNorm on each stream, the two concatenated
(embedding first) through `mtp_proj` [2 D, D], one decoder layer of the
model's own kind (`mtp_norm1` .. `mtp_add2`), `mtp_norm`.

One chip's share of an expert-parallel deployment is the same function with
`experts_held` (and `first_held`) and `vocab_rows` set, as `laguna_conf` has
them; `layers` names the published main layers that are kept.
"""
from __future__ import annotations

from ...nn.conf.graph_vertices import ElementWiseVertex, MergeVertex
from ...nn.conf.layers import (GatedMLPLayer, LatentAttentionLayer,
                               LMHeadLayer, MoELayer, ProjectionLayer,
                               RMSNormLayer, TokenEmbeddingLayer)
from ...nn.conf.neural_net_configuration import NeuralNetConfiguration


def joyai_conf(hidden_size=2048, num_attention_heads=32, q_lora_rank=1536,
               kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
               v_head_dim=128, rope_theta=32000000, rms_norm_eps=1e-6,
               vocab_size=129280, intermediate_size=7168,
               first_k_dense_replace=1, n_routed_experts=256,
               num_experts_per_tok=8, moe_intermediate_size=768,
               n_shared_experts=1, routed_scaling_factor=2.5,
               norm_topk_prob=True, scoring_func="sigmoid",
               num_hidden_layers=40, num_nextn_predict_layers=1,
               bias_update_rate=0.001, mtp_loss_weight=0.3,
               layers=None, experts_held=None, first_held=0,
               vocab_rows=None, seed=123, learning_rate=1e-4,
               updater="adam", data_type="bfloat16", remat=True,
               initializer_range=0.02):
    if num_nextn_predict_layers not in (0, 1):
        raise ValueError("a prediction module of depth 0 or 1")
    D, std, rows = hidden_size, initializer_range, vocab_rows or vocab_size
    mtp = bool(num_nextn_predict_layers)
    norm = lambda: RMSNormLayer(n_in=D, eps=rms_norm_eps)
    gb = (NeuralNetConfiguration.Builder()
          .seed(seed).updater(updater).learning_rate(learning_rate)
          .activation("identity").data_type(data_type)
          .remat_segments(remat)
          .graph_builder()
          .add_inputs(*(("ids", "next_ids") if mtp else ("ids",))))
    gb.add_layer("embed", TokenEmbeddingLayer(n_in=rows, n_out=D,
                                              init_std=std), "ids")

    def decoder_layer(at, x, dense):
        """Vertices `<at>_norm1` .. `<at>_add2` over `x`; the last's name."""
        gb.add_layer(f"{at}_norm1", norm(), x)
        gb.add_layer(f"{at}_attn", LatentAttentionLayer(
            n_in=D, n_out=D, n_heads=num_attention_heads,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=float(rope_theta), eps=rms_norm_eps, init_std=std),
            f"{at}_norm1")
        gb.add_vertex(f"{at}_add1", ElementWiseVertex(op="add"), x,
                      f"{at}_attn")
        gb.add_layer(f"{at}_norm2", norm(), f"{at}_add1")
        if dense:
            gb.add_layer(f"{at}_mlp", GatedMLPLayer(
                n_in=D, n_out=D, width=intermediate_size, init_std=std),
                f"{at}_norm2")
        else:
            gb.add_layer(f"{at}_mlp", MoELayer(
                n_in=D, n_out=D, n_experts=n_routed_experts,
                experts_per_token=num_experts_per_tok,
                expert_width=moe_intermediate_size,
                norm_topk_prob=norm_topk_prob, experts_held=experts_held,
                first_held=first_held,
                shared_width=moe_intermediate_size * n_shared_experts or None,
                routed_scale=float(routed_scaling_factor),
                scoring=scoring_func, bias_update_rate=bias_update_rate,
                init_std=std), f"{at}_norm2")
        gb.add_vertex(f"{at}_add2", ElementWiseVertex(op="add"),
                      f"{at}_add1", f"{at}_mlp")
        return f"{at}_add2"

    x = "embed"
    for i in (range(num_hidden_layers) if layers is None else layers):
        x = decoder_layer(f"l{i}", x, dense=i < first_k_dense_replace)
    gb.add_layer("norm_f", norm(), x)
    gb.add_layer("head", LMHeadLayer(
        n_in=D, n_out=rows, init_std=std,
        loss_weight=1.0 if mtp else None), "norm_f")
    if not mtp:
        return gb.set_outputs("head").build()
    gb.add_layer("mtp_embed", TokenEmbeddingLayer(n_in=rows, n_out=D,
                                                  init_std=std),
                 "next_ids", params_of="embed")
    gb.add_layer("mtp_enorm", norm(), "mtp_embed")
    gb.add_layer("mtp_hnorm", norm(), "norm_f")
    gb.add_vertex("mtp_cat", MergeVertex(), "mtp_enorm", "mtp_hnorm")
    gb.add_layer("mtp_proj", ProjectionLayer(n_in=2 * D, n_out=D,
                                             init_std=std), "mtp_cat")
    x = decoder_layer("mtp", "mtp_proj", dense=False)
    gb.add_layer("mtp_norm", norm(), x)
    gb.add_layer("mtp_head", LMHeadLayer(
        n_in=D, n_out=rows, init_std=std,
        loss_weight=float(mtp_loss_weight)), "mtp_norm", params_of="head")
    return gb.set_outputs("head", "mtp_head").build()
