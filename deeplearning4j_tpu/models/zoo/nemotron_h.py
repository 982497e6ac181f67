"""NVIDIA-Nemotron-3-Nano-30B-A3B (`model_type: nemotron_h`) as a
ComputationGraph: a decoder whose every layer is ONE mixer under a pre-norm
and a residual add, its kind read from `hybrid_override_pattern`, a
character a layer: `M` a Mamba-2 state-space mixer (`mamba2`), `E` a
mixture of two-matrix relu^2 experts beside a shared one under a sigmoid
router with a selection bias (`moe`), `*` grouped-query attention with no
positional encoding and no gate (`attention`: Nemotron-H has no position
embeddings, arXiv:2504.03624 section 2; the state-space layers carry the
order). The fourth block family of the zoo behind the containers' one seam
(`*_conf(...)` -> ComputationGraphConfiguration, as `joyai_conf`).

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json;
the defaults below are its values. What the config leaves open is listed in
`benchmarks/configs/nemotron-3-nano-30b-a3b.json` under `assumed`.

Input: `ids` [B, T] int32 at positions 0 .. T-1 (`fit(MultiDataSet([ids],
[labels], labels_masks=[mask]))`), T a multiple of `chunk_size`. Labels
[B, T] int32 (the next token), mask [B, T]. Vertices of published layer i:
`l<i>_norm`, `l<i>_mixer`, `l<i>_add`.

One chip's share of an expert-parallel deployment is the same function with
`experts_held` (and `first_held`) and `vocab_rows` set, as `joyai_conf` has
them; `layers` names the published layers that are kept.
"""
from __future__ import annotations

from ...nn.conf.graph_vertices import ElementWiseVertex
from ...nn.conf.layers import (AttentionLayer, LMHeadLayer, Mamba2Layer,
                               MoELayer, RMSNormLayer, TokenEmbeddingLayer)
from ...nn.conf.neural_net_configuration import NeuralNetConfiguration

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def nemotron_h_conf(hidden_size=2688, hybrid_override_pattern=PATTERN,
                    mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128,
                    n_groups=8, conv_kernel=4, chunk_size=128,
                    time_step_min=0.001, time_step_max=0.1,
                    time_step_floor=1e-4, num_attention_heads=32,
                    num_key_value_heads=2, head_dim=128, n_routed_experts=128,
                    num_experts_per_tok=6, moe_intermediate_size=1856,
                    moe_shared_expert_intermediate_size=3712,
                    mlp_hidden_act="relu2", routed_scaling_factor=2.5,
                    norm_topk_prob=True, layer_norm_epsilon=1e-5,
                    vocab_size=131072, bias_update_rate=0.001,
                    layers=None, experts_held=None, first_held=0,
                    vocab_rows=None, seed=123, learning_rate=1e-4,
                    updater="adam", data_type="bfloat16", remat=True,
                    initializer_range=0.02):
    if mlp_hidden_act != "relu2":
        raise ValueError(f"mlp_hidden_act {mlp_hidden_act!r}: the family's "
                         f"experts are relu2")
    D, std, rows = hidden_size, initializer_range, vocab_rows or vocab_size
    eps = layer_norm_epsilon
    mixers = {
        "M": lambda: Mamba2Layer(
            n_in=D, n_out=D, mamba_num_heads=mamba_num_heads,
            mamba_head_dim=mamba_head_dim, ssm_state_size=ssm_state_size,
            n_groups=n_groups, conv_kernel=conv_kernel, chunk_size=chunk_size,
            time_step_min=time_step_min, time_step_max=time_step_max,
            time_step_floor=time_step_floor, eps=eps, init_std=std),
        "E": lambda: MoELayer(
            n_in=D, n_out=D, n_experts=n_routed_experts,
            experts_per_token=num_experts_per_tok,
            expert_width=moe_intermediate_size, norm_topk_prob=norm_topk_prob,
            experts_held=experts_held, first_held=first_held,
            shared_width=moe_shared_expert_intermediate_size or None,
            routed_scale=float(routed_scaling_factor), scoring="sigmoid",
            bias_update_rate=bias_update_rate, activation=mlp_hidden_act,
            init_std=std),
        "*": lambda: AttentionLayer(
            n_in=D, n_out=D, n_heads=num_attention_heads,
            n_kv_heads=num_key_value_heads, head_dim=head_dim,
            rope_theta=None, gate=False, init_std=std)}
    unknown = set(hybrid_override_pattern) - set(mixers)
    if unknown:
        raise ValueError(f"hybrid_override_pattern has {sorted(unknown)}: a "
                         f"layer is one of {sorted(mixers)}")
    gb = (NeuralNetConfiguration.Builder()
          .seed(seed).updater(updater).learning_rate(learning_rate)
          .activation("identity").data_type(data_type)
          .remat_segments(remat)
          .graph_builder().add_inputs("ids"))
    gb.add_layer("embed", TokenEmbeddingLayer(n_in=rows, n_out=D,
                                              init_std=std), "ids")
    x = "embed"
    kept = range(len(hybrid_override_pattern)) if layers is None else layers
    for i in kept:
        gb.add_layer(f"l{i}_norm", RMSNormLayer(n_in=D, eps=eps), x)
        gb.add_layer(f"l{i}_mixer", mixers[hybrid_override_pattern[i]](),
                     f"l{i}_norm")
        gb.add_vertex(f"l{i}_add", ElementWiseVertex(op="add"), x,
                      f"l{i}_mixer")
        x = f"l{i}_add"
    gb.add_layer("norm_f", RMSNormLayer(n_in=D, eps=eps), x)
    gb.add_layer("head", LMHeadLayer(n_in=D, n_out=rows, init_std=std),
                 "norm_f")
    return gb.set_outputs("head").build()
