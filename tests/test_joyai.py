"""JoyAI-LLM-Flash (models/zoo/joyai.py, the layer kinds `latentattention`
and `projection`, the sigmoid router and its selection bias in `moe`, the
weighted loss of `lmhead` in nn/conf/layers/decoder.py, tied vertices in the
graph container, `shared_key_attention` in ops/sparse_attention.py) against
its plain reference (benchmarks/references/joyai.py), on seeded weights at a
small size in float32 through `ComputationGraph.fit`: the latent layer's
value and every gradient with the kernels at a key width unlike the value
width; the interleaved rotary turn against a pair rotation by hand; the
router and its bias; the tied leaves; the share test that ties one chip's
experts to the whole layer; three steps of `fit`, both losses; the scopes
and the once-a-layer forward kernel on the lowered step.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.references import joyai as ref                    # noqa: E402
from deeplearning4j_tpu.datasets.dataset import MultiDataSet      # noqa: E402
from deeplearning4j_tpu.models.zoo import joyai_conf              # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import decoder             # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph          # noqa: E402
from deeplearning4j_tpu.ops import sparse_attention as sa         # noqa: E402
from deeplearning4j_tpu.parallel.moe import route_all             # noqa: E402

# layers 0-1 (dense, sparse) and the prediction module at hidden 64: 4 heads
# scoring over 16 + 8 slots and summing values 12 wide (three widths, all
# unlike), ranks 24 and 16, 16 experts top-4 of which 4 are held (4..7)
# beside a shared one, half the vocabulary; T = 128 in blocks of 32
MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 12, "rope_theta": 32000000, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "num_hidden_layers": 2, "vocab_size": 128,
    "n_routed_experts": 4, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "num_nextn_predict_layers": 1, "bias_update_rate": 0.001,
    "mtp_loss_weight": 0.3,
    "deployment": {"router_width": 16, "first_held": 4, "layers": [0, 1]}}
WHOLE = {**MODEL, "n_routed_experts": 16,
         "deployment": {"router_width": 16, "first_held": 0,
                        "layers": [0, 1]}}
TRAINER = {"learning_rate": 1e-3}
B, T = 2, 128
SPARSE = ("l1_mlp", "mtp_mlp")


def conf_of(model=MODEL, **over):
    dep = model["deployment"]
    kw = {k: model[k] for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
        "intermediate_size", "moe_intermediate_size", "n_shared_experts",
        "first_k_dense_replace", "num_hidden_layers", "num_experts_per_tok",
        "routed_scaling_factor", "rms_norm_eps", "num_nextn_predict_layers",
        "bias_update_rate", "mtp_loss_weight")}
    kw.update(n_routed_experts=dep["router_width"],
              experts_held=model["n_routed_experts"],
              first_held=dep["first_held"], layers=dep["layers"],
              vocab_rows=model["vocab_size"],
              learning_rate=TRAINER["learning_rate"], data_type="float32")
    kw.update(over)
    return joyai_conf(**kw)


def weights(model=MODEL, seed=0):
    shapes = ref.param_shapes(model)
    flat = [(n, k) for n in sorted(shapes) for k in sorted(shapes[n])]
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = {n: {} for n in shapes}
    for (n, k), kk in zip(flat, keys):
        a = jax.random.normal(kk, shapes[n][k], jnp.float32)
        # norm weights near 1 and not at it; matrices large enough that
        # the router discriminates at this size
        out[n][k] = 1.0 + 0.1 * a if len(shapes[n][k]) == 1 else 0.2 * a
    return out


def batch_of(seed, model=MODEL, t=T, rows=B):
    ids = jax.random.randint(jax.random.PRNGKey(100 + seed), (rows, t), 0,
                             model["vocab_size"], jnp.int32)
    keep = lambda last: jnp.broadcast_to(
        (jnp.arange(t) < t - last).astype(jnp.float32), (rows, t))
    nxt = jnp.roll(ids, -1, 1)
    return {"ids": ids, "next_ids": nxt, "labels": nxt, "mask": keep(1),
            "labels2": jnp.roll(ids, -2, 1), "mask2": keep(2)}


def mds_of(b):
    return MultiDataSet([b["ids"], b["next_ids"]],
                        [b["labels"], b["labels2"]],
                        labels_masks=[b["mask"], b["mask2"]])


def trainer(w, **over):
    net = ComputationGraph(conf_of(**over)).init()
    assert {n: {k: a.shape for k, a in d.items()}
            for n, d in net._params.items()} == \
        {n: {k: a.shape for k, a in d.items()} for n, d in w.items()}
    net._params = jax.tree.map(jnp.array, w)
    return net


@pytest.fixture(scope="module")
def followed():
    """Three steps of `fit` on three batches, and the reference's."""
    w = weights()
    batches = [batch_of(i) for i in range(3)]
    net = trainer(w)
    got = {"losses": [], "parts": []}
    for i, b in enumerate(batches):
        net.fit(mds_of(b))
        got["losses"].append(float(net._score))
        said = net.publish_layer_gauges()
        got["parts"].append([said["lmhead.head.loss"],
                             said["lmhead.mtp_head.loss"]])
        if i == 0:
            got["m1"] = jax.tree.map(np.asarray, net._updater_state)
            got["gauges"] = said
    got["params"] = jax.tree.map(np.asarray, net._params)
    got["bias"] = np.stack([net._model_state[n]["bias"] for n in SPARSE])
    got["state"] = net._model_state
    with jax.default_matmul_precision("highest"):
        want = {}
        (_, want["aux1"]), want["g1"] = jax.value_and_grad(
            ref.loss, has_aux=True)(w, batches[0], MODEL)
        # the reference's own three steps (it is handed a copy: it donates)
        losses, g1, change, aux = ref.train_steps(
            jax.tree.map(jnp.array, w), batches, MODEL, TRAINER,
            remake=lambda: w)
        want.update(losses=np.asarray(losses), change=np.asarray(change),
                    parts=np.asarray(aux["loss_parts"]),
                    bias=np.asarray(aux["bias"]))
        got["change"] = np.asarray(ref.leaf_norms(
            jax.tree.map(lambda a, b: a - b, got["params"], w)))
    return got, want


CLOSE = dict(rtol=2e-4, atol=2e-6)      # float32 against float32 `highest`


@pytest.mark.parametrize("what", ["loss", "both_losses", "gradient",
                                  "three_adam_steps", "counters", "bias"])
def test_fit_agrees_with_the_reference(followed, what):
    got, want = followed
    if what == "loss":
        np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    elif what == "both_losses":
        # L_mtp apart from L_main: a wrong second loss cannot hide
        np.testing.assert_allclose(got["parts"], want["parts"], rtol=1e-5)
        np.testing.assert_allclose(
            got["losses"], want["parts"] @ np.asarray([1.0, 0.3]), rtol=1e-5)
        assert abs(want["parts"][0, 0] - want["parts"][0, 1]) > 1e-3
    elif what == "gradient":
        # the first gradient as Adam got it: m1 = 0.1 g, every leaf, the
        # tied table and head among them (each the sum of both uses)
        for n, leaves in want["g1"].items():
            for k, g in leaves.items():
                np.testing.assert_allclose(
                    got["m1"][n][k]["m"] / 0.1, g, rtol=2e-3,
                    atol=1e-5 * float(jnp.max(jnp.abs(g))) + 1e-9,
                    err_msg=f"{n}.{k}")
                assert float(jnp.max(jnp.abs(g))) > 0, f"{n}.{k} is dead"
    elif what == "three_adam_steps":
        np.testing.assert_allclose(got["change"], want["change"], rtol=2e-3)
    elif what == "counters":
        held = np.asarray(want["aux1"]["held_pairs"])       # [2 layers, 4]
        for name, row in zip(SPARSE, held):
            g = lambda leaf: got["gauges"][f"moe.{name}.{leaf}"]
            assert g("held_pairs_max") == row.max()
            assert g("held_pairs_mean") == pytest.approx(row.mean())
            assert g("absent_pairs") + row.sum() == B * T * 4
            assert g("bias_abs_max") == pytest.approx(0.001)
        for at in ("l0", "l1", "mtp"):
            assert got["gauges"][
                f"latentattention.{at}_attn.attend_grid_steps_per_tile"] == 1
            assert got["gauges"][
                f"latentattention.{at}_attn.attend_backward_passes"] == 1
    else:
        # after three steps every entry is within 3 gamma of zero and is
        # the reference's; it moved, and not all one way
        np.testing.assert_allclose(got["bias"], want["bias"], atol=1e-7)
        assert np.abs(got["bias"]).max() <= 0.003 + 1e-7
        assert (got["bias"] > 0).any() and (got["bias"] < 0).any()


# ---------------------------------------------------------------- the layer
def latent_layer(**over):
    kw = dict(n_in=64, n_out=64, n_heads=4, q_lora_rank=24, kv_lora_rank=16,
              qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
              rope_theta=32e6)
    kw.update(over)
    return decoder.LatentAttentionLayer(**kw)


def test_the_latent_layer_is_the_references_value_and_every_gradient(
        monkeypatch):
    """Blocks of 32, so that the walk has runs of several tiles; the key is
    24 wide (16 of the head's own, 8 shared) under values 12 wide."""
    monkeypatch.setattr(sa, "BLOCK", 32)
    layer = latent_layer()
    p = weights()["l1_attn"]
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(8), (B, T, 64), jnp.float32)
    z, lin = ref.sizes(MODEL), ref.linear(False)
    mine = lambda p, x: jnp.sum(w * layer.forward_with_state(
        p, x, layer.init_state())[0])
    plain = lambda p, x: jnp.sum(w * ref.attention(p, x, z, lin,
                                                   24 ** -0.5))
    with jax.default_matmul_precision("highest"):
        got = layer.forward_with_state(p, x, layer.init_state())[0]
        np.testing.assert_allclose(
            got, ref.attention(p, x, z, lin, 24 ** -0.5), **CLOSE)
        g_got = jax.grad(mine, (0, 1))(p, x)
        g_want = jax.grad(plain, (0, 1))(p, x)
    for k in p:     # Wkv_a's last 8 columns make the shared rotary key
        np.testing.assert_allclose(
            g_got[0][k], g_want[0][k], rtol=2e-3,
            atol=1e-5 * float(jnp.max(jnp.abs(g_want[0][k]))), err_msg=k)
    assert float(jnp.max(jnp.abs(g_want[0]["Wkv_a"][:, 16:]))) > 0
    np.testing.assert_allclose(g_got[1], g_want[1], rtol=2e-3, atol=1e-5)


@pytest.mark.parametrize("kv, shared_heads", [(4, 1), (2, 1), (4, 2)])
def test_the_shared_keys_gradient_is_the_sum_over_the_heads_that_read_it(
        kv, shared_heads):
    """`shared_key_attention` against the same scores from ONE wide operand
    (`masked_attention` over keys with the shared slots repeated a head):
    there the shared key's gradient comes out a copy a head, and their sum
    is what the kernel's one run a key block leaves."""
    H, t, d, d2, dv = 4, 64, 16, 8, 12
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v, q2, k2, w = (jax.random.normal(kk, s, jnp.float32)
                          for kk, s in zip(ks, (
        (B, H, t, d), (B, kv, t, d), (B, kv, t, dv), (B, H, t, d2),
        (B, shared_heads, t, d2), (B, H, t, dv))))
    scale = (d + d2) ** -0.5
    rep = lambda a: jnp.repeat(a, H // a.shape[1], 1)

    def wide(q, k, v, q2, k2):
        return jnp.sum(w * sa.masked_attention(
            jnp.concatenate([q, q2], -1),
            jnp.concatenate([rep(k), rep(k2)], -1), rep(v), None, scale,
            16, 16)[0])

    def two(*a):
        return jnp.sum(w * sa.shared_key_attention(*a, scale, 16, 32)[0])

    args = (q, k, v, q2, k2)
    np.testing.assert_allclose(two(*args), wide(*args), rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.grad(two, range(5))(*args),
                    jax.grad(wide, range(5))(*args)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_equal_widths_and_no_shared_key_lower_as_before():
    """The by-key schedule without `own` is the table it was: no bit of
    the shared walk in it."""
    plain = sa.tile_schedule(128, 32, 32, heads=4)
    owned = sa.tile_schedule(128, 32, 32, heads=4, own=1)
    assert not any(plain["by_key"][3] & (sa.OWN_FIRST | sa.OWN_LAST))
    np.testing.assert_array_equal(
        owned["by_key"][3] & ~(sa.OWN_FIRST | sa.OWN_LAST),
        plain["by_key"][3])
    # each key/value head's own run inside a shared block: one first, one
    # last a (key block, head)
    e = owned["by_key"][3]
    assert (e & sa.OWN_FIRST != 0).sum() == (e & sa.OWN_LAST != 0).sum() \
        == 4 * 4
    assert (e & sa.FIRST != 0).sum() == (e & sa.LAST != 0).sum() == 4


def test_interleaved_rotary_is_a_pair_rotation_by_hand():
    layer = latent_layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 2, 8), jnp.float32)
    got = np.asarray(layer.turn(x, jnp.arange(5)[None]))
    for t in range(5):
        for i in range(4):
            ang = t * 32e6 ** (-2 * i / 8)
            a, b = np.asarray(x[0, t, :, 2 * i]), np.asarray(
                x[0, t, :, 2 * i + 1])
            np.testing.assert_allclose(
                got[0, t, :, 2 * i], a * np.cos(ang) - b * np.sin(ang),
                rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                got[0, t, :, 2 * i + 1], b * np.cos(ang) + a * np.sin(ang),
                rtol=1e-5, atol=1e-6)
    # position 0 turns nothing; the reference turns the same way
    np.testing.assert_array_equal(got[0, 0], x[0, 0])
    np.testing.assert_allclose(got[0], ref.rotary(x[0], 32e6), rtol=1e-5,
                               atol=1e-6)


# --------------------------------------------------------------- the router
def moe_layer(held, first, **over):
    kw = dict(n_in=64, n_out=64, n_experts=16, experts_per_token=4,
              expert_width=32, experts_held=held, first_held=first,
              shared_width=32, routed_scale=2.5, scoring="sigmoid",
              bias_update_rate=0.001)
    kw.update(over)
    return decoder.MoELayer(**kw)


def test_the_router_selects_by_score_plus_bias_and_weighs_by_score():
    wr = jax.random.normal(jax.random.PRNGKey(2), (64, 16), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (50, 64), jnp.float32)
    s = np.asarray(jax.nn.sigmoid(x @ wr))
    bias = np.zeros(16, np.float32)
    bias[5] = 10.0          # expert 5 wins every token, at its own score
    experts, gates = route_all(wr, x, 4, True, scoring="sigmoid",
                               bias=jnp.asarray(bias))
    experts, gates = np.asarray(experts), np.asarray(gates)
    assert (experts == 5).any(-1).all()
    for t in range(50):
        want = np.argsort(-(s[t] + bias), kind="stable")[:4]
        assert set(experts[t]) == set(want)
        np.testing.assert_allclose(gates[t], s[t, experts[t]]
                                   / s[t, experts[t]].sum(), rtol=1e-5)
    # no bias and softmax: what it was
    e0, g0 = route_all(wr, x, 4, True)
    top, idx = jax.lax.top_k(jax.nn.softmax(x @ wr, -1), 4)
    np.testing.assert_array_equal(e0, idx)
    np.testing.assert_allclose(g0, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)


def test_the_bias_steps_against_the_load_and_takes_no_gradient():
    layer = moe_layer(16, 0)
    p = weights(WHOLE)["l1_mlp"]
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, 64), jnp.float32)
    state = layer.init_state()
    y, new = layer.forward_with_state(p, x, state, train=True)
    experts, _ = route_all(p["Wr"], x.reshape(B * T, 64), 4, True,
                           scoring="sigmoid", bias=state["bias"])
    counts = np.bincount(np.asarray(experts).ravel(), minlength=16)
    assert counts.max() > counts.min()                  # uneven
    np.testing.assert_allclose(
        new["bias"], 0.001 * np.sign(counts.mean() - counts), atol=1e-9)
    # an inference forward leaves it, and reads it
    _, same = layer.forward_with_state(p, x, new, train=False)
    np.testing.assert_array_equal(same["bias"], new["bias"])
    # the bias is state: a gradient with respect to it is nought, and the
    # trainer holds neither a leaf nor an Adam state for it
    g = jax.grad(lambda b: jnp.sum(layer.forward_with_state(
        p, x, {**state, "bias": b}, train=True)[0]))(state["bias"])
    assert not np.asarray(g).any()
    net = ComputationGraph(conf_of()).init()
    for tree in (net._params, net._updater_state):
        assert "bias" not in tree["l1_mlp"] and set(tree["l1_mlp"]) == {
            "Wr", "Wg", "Wu", "Wd", "Sg", "Su", "Sd"}
    assert net._model_state["l1_mlp"]["bias"].shape == (16,)


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    """Four shares of four experts each, computed by the program's layer
    with ITS experts' weights; every share computes the shared expert
    alike, so it is counted once: the sum is the uncut reference's layer
    over all 16, under a bias that is not nought."""
    w = weights(WHOLE)["l1_mlp"]
    u = jax.random.normal(jax.random.PRNGKey(3), (B, T, 64), jnp.float32)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (16,),
                                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        z, lin = ref.sizes(WHOLE), ref.linear(False)
        flat = u.reshape(B * T, 64)
        want, counts, every = ref.experts_part(w, flat, z, lin, bias)
        alone = ref.gated_mlp(flat, w["Sg"], w["Su"], w["Sd"], lin)
        total = 0.0
        for first in range(0, 16, 4):
            layer = moe_layer(4, first)
            share = {**{k: w[k] for k in ("Wr", "Sg", "Su", "Sd")},
                     **{k: w[k][first:first + 4] for k in ("Wg", "Wu", "Wd")}}
            y, st = layer.forward_with_state(
                share, u, {**layer.init_state(), "bias": bias})
            np.testing.assert_array_equal(st["held_pairs"],
                                          counts[first:first + 4])
            total = total + y.reshape(B * T, 64) - alone
    np.testing.assert_allclose(total + alone, want, **CLOSE)
    np.testing.assert_array_equal(every, counts)
    assert int(counts.sum()) == B * T * 4
    assert float(jnp.max(jnp.abs(alone))) > 1e-2


# ------------------------------------------------------------ tied vertices
def test_one_table_and_one_head_are_reached_from_two_places():
    """`mtp_embed` and `mtp_head` read `embed`'s and `head`'s leaves: no
    second copy in the parameters, the gradient or Adam's state, and each
    leaf's gradient is the sum of both uses (the reference's, which indexes
    one table twice and multiplies by one head twice)."""
    conf = conf_of()
    assert conf.vertices["mtp_embed"].params_of == "embed"
    assert conf.vertices["mtp_head"].params_of == "head"
    twin = type(conf).from_json(conf.to_json())
    assert twin.to_dict() == conf.to_dict()
    assert twin.vertices["mtp_head"].params_of == "head"
    net = ComputationGraph(conf).init()
    shapes = ref.param_shapes(MODEL)
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    want = sum(int(np.prod(s)) for d in shapes.values() for s in d.values())
    assert "mtp_embed" not in net._params and "mtp_head" not in net._params
    assert count(net._params) == want == net.num_params()
    assert sum(a.nbytes for a in jax.tree.leaves(net._params)) == 4 * want
    # Adam: m and v a leaf, nothing for the tied vertices
    assert set(net._updater_state) == set(net._params)
    assert sum(int(np.prod(s[m].shape)) for d in
               net._updater_state.values() for s in d.values()
               for m in ("m", "v")) == 2 * want
    tables = [n for n, d in net._params.items() if n != "mtp_proj"
              for a in d.values() if a.shape in ((128, 64), (64, 128))]
    assert sorted(tables) == ["embed", "head"]
    # each use alone gives a part of the gradient, and the step's is both
    w, b = weights(), batch_of(0)
    with jax.default_matmul_precision("highest"):
        g = jax.grad(lambda p: ref.loss(p, b, MODEL)[0])(w)
        only_main = jax.grad(lambda p: ref.loss(p, b, MODEL)[1][
            "loss_main"])(w)
    for n in ("embed", "head"):
        assert float(jnp.max(jnp.abs(g[n]["W"] - only_main[n]["W"]))) > 1e-4
    net = trainer(w)
    net.fit(mds_of(b))
    for n in ("embed", "head"):
        np.testing.assert_allclose(
            np.asarray(net._updater_state[n]["W"]["m"]) / 0.1, g[n]["W"],
            rtol=2e-3, atol=1e-5 * float(jnp.max(jnp.abs(g[n]["W"]))))


def test_a_vertex_may_be_tied_to_a_layer_with_parameters_of_its_own_only():
    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    gb = (NeuralNetConfiguration.Builder().graph_builder().add_inputs("a")
          .add_layer("x", decoder.RMSNormLayer(n_in=4), "a")
          .add_layer("y", decoder.RMSNormLayer(n_in=4), "x", params_of="x")
          .add_layer("z", decoder.RMSNormLayer(n_in=4), "y", params_of="y")
          .set_outputs("z"))
    with pytest.raises(ValueError, match="parameters of its own"):
        gb.build()


# ------------------------------------------------------- the lowered step
@pytest.fixture(scope="module")
def lowered():
    net = ComputationGraph(conf_of()).init()
    return net.lower_step(mds_of(batch_of(0))).as_text(debug_info=True)


def paths_of(text):
    return set(re.findall(r'loc\("([^"]*/[^"]*)"', text))


def test_configuration_names_its_kinds_and_the_configs_keys():
    conf = conf_of()
    kinds = {s.conf.layer_type for s in conf.vertices.values() if s.is_layer}
    assert kinds == {"tokenembedding", "rmsnorm", "latentattention",
                     "gatedmlp", "moe", "projection", "lmhead"}
    attn = conf.vertices["mtp_attn"].conf
    assert (attn.q_lora_rank, attn.kv_lora_rank, attn.qk_nope_head_dim,
            attn.qk_rope_head_dim, attn.v_head_dim) == (24, 16, 16, 8, 12)
    moe = conf.vertices["l1_mlp"].conf
    assert (moe.scoring, moe.bias_update_rate, moe.shared_width,
            moe.routed_scale) == ("sigmoid", 0.001, 32, 2.5)
    assert conf.vertices["l0_mlp"].conf.layer_type == "gatedmlp"
    assert conf.vertices["mtp_head"].conf.loss_weight == 0.3
    assert conf.network_inputs == ["ids", "next_ids"]
    assert conf.network_outputs == ["head", "mtp_head"]
    # without the module: one input, one output, a head that keeps no state
    plain = conf_of(num_nextn_predict_layers=0)
    assert plain.network_inputs == ["ids"]
    assert not plain.vertices["head"].conf.has_state()


def test_the_scopes_are_on_the_lowered_step_forward_and_backward(lowered):
    paths = paths_of(lowered)
    for scope, kind, vertices in (
            ("latent", "latentattention", ("l0_attn", "l1_attn", "mtp_attn")),
            ("rotary", "latentattention", ("l0_attn", "mtp_attn")),
            ("attend_latent", "latentattention",
             ("l0_attn", "l1_attn", "mtp_attn")),
            ("router", "moe", ("l1_mlp", "mtp_mlp")),
            ("shared", "moe", ("l1_mlp", "mtp_mlp"))):
        mine = {p for p in paths if f"/{scope}/" in p}
        assert any("transpose(" in p for p in mine), scope     # a backward
        assert any("transpose(" not in p for p in mine), scope  # a forward
        for v in vertices:
            assert any(f"{kind}.{v}" in p for p in mine), (scope, v)
    # every kernel is under `attend_latent`; the module's vertices and its
    # loss have operations of their own, forward and backward
    kernels = {p for p in paths if p.endswith("/pallas_call")}
    assert kernels and all("/attend_latent/" in p for p in kernels)
    for name in ("tokenembedding.mtp_embed", "rmsnorm.mtp_enorm",
                 "rmsnorm.mtp_hnorm", "projection.mtp_proj",
                 "latentattention.mtp_attn", "moe.mtp_mlp",
                 "rmsnorm.mtp_norm", "loss.mtp_head", "loss.head"):
        mine = {p for p in paths if name in p}
        assert any("transpose(" in p for p in mine), name
        assert any("transpose(" not in p for p in mine), name


def test_the_forward_kernel_runs_once_a_layer(lowered):
    """`latentattention` names the kernel's o and lse for keeping: each of
    the three layers' forward kernel is in the step once, outside its
    segment's rematerialisation, and the gauge counts three segments."""
    paths = paths_of(lowered)
    again = {p for p in paths if "/rematted_computation/" in p}
    for at in ("l0", "l1", "mtp"):
        mine = {p for p in paths if f"latentattention.{at}_attn/" in p
                or f"latentattention.{at}_attn)/" in p}
        fwd = {p for p in mine
               if p.endswith("sparse_attention_fwd/pallas_call")}
        assert len(fwd) == 1 and not fwd & again, fwd
        # the backward is one kernel a layer (PR 37), not dQ's and dK/dV's
        bwd = {p for p in mine if re.search(
            r"sparse_attention_(bwd|dq|dkv)/pallas_call$", p)}
        assert len(bwd) == 1 and next(iter(bwd)).endswith(
            "sparse_attention_bwd/pallas_call"), bwd
        assert any("/latent/" in p for p in mine & again), at
    from deeplearning4j_tpu import obs
    net = ComputationGraph(conf_of()).init()
    net._remat_plan()
    names = [n for n, s in net.conf.vertices.items()
             if s.is_layer and s.conf.remat_keeps()]
    assert sorted(names) == ["l0_attn", "l1_attn", "mtp_attn"]
    assert obs.default_registry().gauge(
        "train.remat_kept_segments").value == len(names)
