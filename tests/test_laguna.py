"""Laguna-XS.2 (models/zoo/laguna.py, the layer kinds `attention` and
`gatedmlp` and the shared expert of `moe` in nn/conf/layers/decoder.py, the
position-masked schedule of ops/sparse_attention.py) against its plain
reference (benchmarks/references/laguna.py), on seeded weights at a small
size in float32 through `ComputationGraph.fit`; the band schedule tile by
tile; the position-masked kernels against the mask-operand ones; YaRN's
frequencies against hand values; the share test that ties one chip's experts
and vocabulary slice to the whole layer; and Keye's expert layer, which the
shared expert must leave as it lowered.
"""
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.references import laguna as ref                   # noqa: E402
from deeplearning4j_tpu.datasets.dataset import MultiDataSet      # noqa: E402
from deeplearning4j_tpu.models.zoo import laguna_conf             # noqa: E402
from deeplearning4j_tpu.models.zoo.laguna import ROPE_PARAMETERS  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import decoder             # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph          # noqa: E402
from deeplearning4j_tpu.ops import sparse_attention as sa         # noqa: E402

# layers 0-4 of the pattern (full, window x 3, full; dense, sparse x 4) at
# hidden 64, 6 / 8 query heads over 2 key/value heads of 16 (groups of 3 and
# 4), window 32, 8 experts top-2 of which 4 held beside a shared one, half
# the vocabulary; T = 128
TYPES = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
MODEL = {
    "hidden_size": 64, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_hidden_layers": 5,
    "vocab_size": 128, "num_experts": 4, "num_experts_per_tok": 2,
    "moe_routed_scaling_factor": 2.5, "sliding_window": 32,
    "rms_norm_eps": 1e-6, "gating": True, "layer_types": TYPES,
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "rope_parameters": ROPE_PARAMETERS,
    "deployment": {"router_width": 8, "first_held": 4}}
TRAINER = {"learning_rate": 1e-3}
B, T = 2, 128


def conf_of(model=MODEL, **over):
    dep = model["deployment"]
    kw = {k: model[k] for k in (
        "hidden_size", "num_key_value_heads", "head_dim", "intermediate_size",
        "moe_intermediate_size", "shared_expert_intermediate_size",
        "num_hidden_layers", "num_experts_per_tok", "sliding_window",
        "moe_routed_scaling_factor", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "rope_parameters")}
    kw.update(num_experts=dep["router_width"],
              experts_held=model["num_experts"],
              first_held=dep["first_held"], vocab_rows=model["vocab_size"],
              learning_rate=TRAINER["learning_rate"], data_type="float32")
    kw.update(over)
    return laguna_conf(**kw)


def weights(model=MODEL, seed=0):
    shapes = ref.param_shapes(model)
    flat = [(n, k) for n in sorted(shapes) for k in sorted(shapes[n])]
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = {n: {} for n in shapes}
    for (n, k), kk in zip(flat, keys):
        a = jax.random.normal(kk, shapes[n][k], jnp.float32)
        # norm weights near 1 and not at it; matrices large enough that
        # the router and the gate discriminate at this size
        out[n][k] = 1.0 + 0.1 * a if len(shapes[n][k]) == 1 else 0.2 * a
    return out


def batch_of(seed, model=MODEL, t=T, rows=B):
    ids = jax.random.randint(jax.random.PRNGKey(100 + seed), (rows, t), 0,
                             model["vocab_size"], jnp.int32)
    return {"ids": ids, "labels": jnp.roll(ids, -1, 1),
            "mask": jnp.broadcast_to((jnp.arange(t) < t - 1)
                                     .astype(jnp.float32), (rows, t))}


def mds_of(b):
    return MultiDataSet([b["ids"]], [b["labels"]], labels_masks=[b["mask"]])


def trainer(w, **over):
    net = ComputationGraph(conf_of(**over)).init()
    assert {n: {k: a.shape for k, a in d.items()}
            for n, d in net._params.items() if d} == \
        {n: {k: a.shape for k, a in d.items()} for n, d in w.items()}
    net._params = {n: jax.tree.map(jnp.array, w.get(n, d))
                   for n, d in net._params.items()}
    return net


@pytest.fixture(scope="module")
def followed():
    """Three steps of `fit` on three batches, and the reference's."""
    w = weights()
    batches = [batch_of(i) for i in range(3)]
    net = trainer(w)
    got = {"logits": net.output(*mds_of(batches[0]).features)[0],
           "losses": [], "m1": None}
    for i, b in enumerate(batches):
        net.fit(mds_of(b))
        got["losses"].append(float(net._score))
        if i == 0:
            got["m1"] = jax.tree.map(np.asarray, net._updater_state)
            got["gauges"] = net.publish_layer_gauges()
    got["params"] = jax.tree.map(np.asarray, net._params)
    with jax.default_matmul_precision("highest"):
        want = {"logits": ref.logits(w, batches[0], MODEL), "losses": []}
        p = w
        m = v = jax.tree.map(jnp.zeros_like, w)
        for i, b in enumerate(batches):
            (l, aux), g = jax.value_and_grad(ref.loss, has_aux=True)(
                p, b, MODEL)
            want["losses"].append(float(l))
            if i == 0:
                want["g1"], want["aux1"] = g, aux
            out = jax.tree.map(lambda a, b_, c, d: ref.adam(
                a, b_, c, d, float(i + 1), TRAINER), p, m, v, g)
            p, m, v = (jax.tree.map(lambda _, o, j=j: o[j], w, out)
                       for j in range(3))
        want["params"] = p
    return got, want


CLOSE = dict(rtol=2e-4, atol=2e-6)      # float32 against float32 `highest`


@pytest.mark.parametrize("what", ["logits", "loss", "gradient",
                                  "three_adam_steps", "counters"])
def test_fit_agrees_with_the_reference(followed, what):
    got, want = followed
    if what == "logits":
        np.testing.assert_allclose(got["logits"], want["logits"],
                                   rtol=2e-4, atol=2e-4)
    elif what == "loss":
        np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    elif what == "gradient":
        # the first gradient as Adam got it: m1 = 0.1 g, every leaf
        for n, leaves in want["g1"].items():
            for k, g in leaves.items():
                np.testing.assert_allclose(
                    got["m1"][n][k]["m"] / 0.1, g, rtol=2e-3,
                    atol=1e-5 * float(jnp.max(jnp.abs(g))) + 1e-9,
                    err_msg=f"{n}.{k}")
                assert float(jnp.max(jnp.abs(g))) > 0, f"{n}.{k} is dead"
    elif what == "three_adam_steps":
        for n, leaves in want["params"].items():
            for k, p in leaves.items():
                # a step moves an element by up to 1e-3, whatever its
                # gradient's size: 5e-5 is a twentieth of one step
                np.testing.assert_allclose(got["params"][n][k], p,
                                           rtol=1e-4, atol=5e-5,
                                           err_msg=f"{n}.{k}")
    else:
        held = np.asarray(want["aux1"]["held_pairs"])       # [4 layers, 4]
        for j, row in enumerate(held, start=1):
            g = lambda leaf: got["gauges"][f"moe.l{j}_mlp.{leaf}"]
            assert g("held_pairs_max") == row.max()
            assert g("held_pairs_mean") == pytest.approx(row.mean())
            assert g("absent_pairs") + row.sum() == B * T * 2
        # the kernels' grid holds only tiles with a visible pair
        for j in range(5):
            assert got["gauges"][
                f"attention.l{j}_attn.attend_grid_steps_per_tile"] == 1.0
            # and the backward walks them once
            assert got["gauges"][
                f"attention.l{j}_attn.attend_backward_passes"] == 1.0


def attention_layer(window, heads=8, **over):
    kw = dict(n_in=64, n_out=64, n_heads=heads, n_kv_heads=2, head_dim=16,
              window=window)
    kw.update(over)
    return decoder.AttentionLayer(**kw)


def test_a_window_layer_no_shorter_than_the_sequence_is_a_full_one():
    w = weights()["l1_attn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (B, 32, 64), jnp.float32)
    out = lambda layer, x: layer.forward_with_state(
        w, x, layer.init_state())[0]
    np.testing.assert_array_equal(out(attention_layer(32), x),
                                  out(attention_layer(None), x))
    # and past the window it is not: the band is real
    x = jax.random.normal(jax.random.PRNGKey(6), (B, 64, 64), jnp.float32)
    assert float(jnp.max(jnp.abs(out(attention_layer(32), x)
                                 - out(attention_layer(None), x)))) > 1e-3


# ---------------------------------------------------------------- schedule
def visible(T, window):
    d = np.arange(T)[:, None] - np.arange(T)[None, :]
    return (d >= 0) & ((d < window) if window else True)


@pytest.mark.parametrize("T, bq, bk, window", [
    (256, 64, 64, None), (256, 64, 64, 64), (256, 64, 64, 96),
    (256, 32, 64, 50), (256, 64, 32, 100), (192, 64, 32, 1),
    (240, 48, 80, 77), (128, 128, 128, 32), (256, 64, 128, 300)])
def test_the_band_schedule_visits_each_tile_with_a_visible_pair_once(
        T, bq, bk, window):
    seen = visible(T, window).reshape(T // bq, bq, T // bk, bk)
    want = {(i, j) for i, j in zip(*np.nonzero(seen.any((1, 3))))}
    whole = {(i, j) for i, j in zip(*np.nonzero(seen.all((1, 3))))}
    heads = 3
    sched = sa.tile_schedule(T, bq, bk, heads=heads, window=window)
    i, j, e = sched["by_query"]
    assert sorted(zip(i, j)) == sorted(want)            # each once, no other
    assert sched["grid_steps"] == sched["computing_steps"] == len(want)
    # a run a query block: its key blocks in order, first and last marked,
    # and a mask only where the diagonal or the band's edge crosses
    for n in range(len(i)):
        first = n == 0 or i[n - 1] != i[n]
        last = n == len(i) - 1 or i[n + 1] != i[n]
        assert bool(e[n] & sa.FIRST) == first and bool(e[n] & sa.LAST) == last
        assert first or j[n] == j[n - 1] + 1
        assert bool(e[n] & sa.CROSSED) == ((i[n], j[n]) not in whole)
    kj, kr, ki, ke = sched["by_key"]
    assert len(kj) == heads * len(want)
    assert sorted(zip(ki, kj, kr)) == sorted(
        (a, b, r) for a, b in want for r in range(heads))
    for n in range(len(kj)):
        first = n == 0 or kj[n - 1] != kj[n]
        last = n == len(kj) - 1 or kj[n + 1] != kj[n]
        assert bool(ke[n] & sa.FIRST) == first
        assert bool(ke[n] & sa.LAST) == last
        assert bool(ke[n] & sa.CROSSED) == ((ki[n], kj[n]) not in whole)


def test_the_cells_window_layers_walk_two_tiles_a_query_block():
    """T = 16384 under a window of 512: at the module's block shape a query
    block sees 2 key blocks where the causal walk has up to 32."""
    bq, bk = sa.WINDOW_BLOCK
    band = sa.tile_schedule(16384, bq, bk, window=512)
    per_block = np.bincount(band["by_query"][0])
    assert per_block.max() == (bq + 510) // bk + 1
    assert sa.tile_schedule(16384, 512, 512, window=512)["grid_steps"] == 63
    causal = sa.tile_schedule(16384, 512, 512)
    assert np.bincount(causal["by_query"][0]).max() == 32
    assert sa.grid_steps_per_tile(16384, window=512) == 1.0


@pytest.mark.parametrize("heads, kv, window, bq, bk", [
    (6, 1, None, 64, 64), (8, 1, 96, 64, 32), (8, 2, 50, 32, 64),
    (12, 2, None, 64, 128)])
def test_position_masked_kernels_are_the_mask_operand_kernels(heads, kv,
                                                              window, bq, bk):
    """Forward and all three gradients, groups of 6, 8 and 4: the same
    arithmetic tile by tile (a tile the band does not cross skips the
    select, and the CPU's compiler contracts its branch differently: a few
    units in the last place)."""
    T, d = 256, 16
    ks = jax.random.split(jax.random.PRNGKey(heads), 4)
    q, do = (jax.random.normal(k, (1, heads, T, d)) for k in ks[:2])
    k, v = (jax.random.normal(k, (1, kv, T, d)) for k in ks[2:])
    mask = jnp.asarray(visible(T, window).astype(np.int8))[None]

    def both(mask, **by):
        f = lambda q, k, v: sa.masked_attention(q, k, v, mask, d ** -0.5,
                                                bq, bk, None, **by)
        (o, lse), pull = jax.vjp(f, q, k, v)
        return (o, lse, *pull((do, jnp.zeros_like(lse))))

    for got, want in zip(both(None, window=window), both(mask)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="its own window"):
        sa.masked_attention(q, k, v, mask, 1.0, bq, bk, None, 8)


# ------------------------------------------------------------------ rotary
def test_yarn_frequencies_and_the_untouched_slots_against_hand_values():
    rope = ROPE_PARAMETERS["full_attention"]
    yarn = (rope["factor"], rope["original_max_position_embeddings"],
            rope["beta_fast"], rope["beta_slow"], rope["attention_factor"])
    # 64 x ln(4096 / (64 x 2 pi)) / (2 ln 500000) = 5.66 -> 5;
    # 64 x ln(4096 / (2 pi)) / (2 ln 500000) = 15.80 -> 16
    assert decoder.yarn_correction_range(64, 500000, 4096, 64, 1) == (5, 16)
    inv, factor = decoder.rotary_inv_freq(64, 500000.0, yarn)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    assert factor == 1.4158883083359672
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)    # ramp 0
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-6)
    np.testing.assert_allclose(                  # slot 10: ramp 5 / 11
        inv[10], plain[10] * (1 - 5 / 11) + plain[10] / 64 * (5 / 11),
        rtol=1e-6)
    np.testing.assert_allclose(ref.inv_freq(rope, 128)[0], inv, rtol=1e-6)
    # the turn: pairs (i, i + 32) of the first 64 slots, the rest untouched
    layer = attention_layer(None, head_dim=128, rope_theta=500000.0,
                            rotary_dim=64, yarn=yarn)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 2, 128))
    got = np.asarray(layer.turn(x, jnp.arange(12)[None]))
    np.testing.assert_array_equal(got[..., 64:], np.asarray(x)[..., 64:])
    ang = np.arange(12)[:, None] * np.asarray(inv)
    c, s = (f(ang)[None, :, None] * factor for f in (np.cos, np.sin))
    a, b = np.asarray(x[..., :32]), np.asarray(x[..., 32:64])
    np.testing.assert_allclose(got[..., :32], a * c - b * s, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got[..., 32:64], b * c + a * s, rtol=1e-5,
                               atol=1e-5)
    # a window layer turns all 128 slots at theta 10,000, unscaled
    plain_layer = attention_layer(32, head_dim=128, rope_theta=10000.0)
    got = np.asarray(plain_layer.turn(x, jnp.arange(12)[None]))
    ang = np.arange(12)[:, None] * 10000.0 ** (-np.arange(64) / 64.0)
    a, b = np.asarray(x[..., :64]), np.asarray(x[..., 64:])
    np.testing.assert_allclose(
        got[..., :64], a * np.cos(ang)[None, :, None]
        - b * np.sin(ang)[None, :, None], rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ the shares
WHOLE = dict(MODEL, num_experts=8, deployment={"router_width": 8,
                                               "first_held": 0})


def moe_layer(held, first, **over):
    kw = dict(n_in=64, n_out=64, n_experts=8, experts_per_token=2,
              expert_width=32, experts_held=held, first_held=first,
              shared_width=32, routed_scale=2.5)
    kw.update(over)
    return decoder.MoELayer(**kw)


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    """Eight shares of one expert each, computed by the program's layer with
    ITS expert's weights; every share computes the shared expert alike, so
    it is counted once: the sum is the uncut reference's layer over all 8."""
    w = weights(WHOLE)["l1_mlp"]
    u = jax.random.normal(jax.random.PRNGKey(3), (B, T, 64), jnp.float32)
    shared = lambda: {k: w[k] for k in ("Sg", "Su", "Sd")}
    with jax.default_matmul_precision("highest"):
        z, lin = ref.sizes(WHOLE), ref.linear(False)
        want, counts = ref.experts_part(w, u.reshape(B * T, 64), z, lin)
        alone = ref.gated_mlp(u.reshape(B * T, 64), w["Sg"], w["Su"],
                              w["Sd"], lin)
        total = 0.0
        for first in range(8):
            layer = moe_layer(1, first)
            share = {"Wr": w["Wr"], **shared(),
                     **{k: w[k][first:first + 1] for k in ("Wg", "Wu", "Wd")}}
            y, st = layer.forward_with_state(share, u, layer.init_state())
            np.testing.assert_array_equal(st["held_pairs"],
                                          counts[first:first + 1])
            total = total + y.reshape(B * T, 64) - alone
    np.testing.assert_allclose(total + alone, want, **CLOSE)
    assert int(counts.sum()) == B * T * 2
    assert float(jnp.max(jnp.abs(alone))) > 1e-2


def test_the_slices_of_the_vocabulary_are_the_whole_heads_logits():
    w = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (64, 128))
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, 64), jnp.float32)
    whole = decoder.LMHeadLayer(n_in=64, n_out=128).forward({"W": w}, x)
    parts = [decoder.LMHeadLayer(n_in=64, n_out=16).forward(
        {"W": w[:, i:i + 16]}, x) for i in range(0, 128, 16)]
    np.testing.assert_allclose(jnp.concatenate(parts, -1), whole, **CLOSE)


def test_no_routed_pair_is_dropped_under_a_router_skewed_onto_one_expert():
    """Every token's first choice is expert 5 (a router column far above
    the rest): all 256 tokens' pairs on one held expert, all computed, and
    the layer's result is the reference's."""
    w = dict(weights()["l1_mlp"])
    w["Wr"] = (0.1 * w["Wr"]).at[:, 5].set(0.5)
    u = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (B, T, 64),
                                  jnp.float32)) + 0.1
    layer = moe_layer(4, 4)
    y, st = layer.forward_with_state(w, u, layer.init_state())
    assert int(st["held_pairs"][1]) == B * T
    with jax.default_matmul_precision("highest"):
        want, counts = ref.experts_part(w, u.reshape(B * T, 64),
                                        ref.sizes(MODEL), ref.linear(False))
    np.testing.assert_array_equal(st["held_pairs"], counts)
    np.testing.assert_allclose(y.reshape(B * T, 64), want, rtol=1e-4,
                               atol=1e-5)


# ------------------------------------------------- what the lowered step is
@pytest.fixture(scope="module")
def lowered():
    net = ComputationGraph(conf_of()).init()
    return net.lower_step(mds_of(batch_of(0))).as_text(debug_info=True)


def test_configuration_round_trips_and_names_its_kinds():
    conf = conf_of()
    kinds = {s.conf.layer_type for s in conf.vertices.values() if s.is_layer}
    assert kinds == {"tokenembedding", "rmsnorm", "attention", "gatedmlp",
                     "moe", "lmhead"}
    twin = type(conf).from_json(conf.to_json())
    assert twin.to_dict() == conf.to_dict()
    attn = {n: s.conf for n, s in conf.vertices.items()
            if n.endswith("_attn")}
    assert [attn[f"l{i}_attn"].n_heads for i in range(5)] == [6, 8, 8, 8, 6]
    assert [attn[f"l{i}_attn"].window for i in range(5)] == \
        [None, 32, 32, 32, None]
    assert [attn[f"l{i}_attn"].rotary_dim for i in range(5)] == \
        [8, 16, 16, 16, 8]
    assert attn["l0_attn"].yarn and not attn["l1_attn"].yarn


def paths_of(text):
    return set(re.findall(r'loc\("([^"]*/[^"]*)"', text))


def test_the_inner_scopes_are_on_the_lowered_step_forward_and_backward(
        lowered):
    paths = paths_of(lowered)
    for scope, kind, vertices in (
            ("attend_full", "attention", ("l0_attn", "l4_attn")),
            ("attend_window", "attention", ("l1_attn", "l2_attn", "l3_attn")),
            ("shared", "moe", ("l1_mlp", "l4_mlp")),
            ("rotary", "attention", ("l0_attn", "l1_attn")),
            ("gate", "attention", ("l0_attn", "l1_attn"))):
        mine = {p for p in paths if f"/{scope}/" in p}
        assert any("transpose(" in p for p in mine), scope     # a backward
        assert any("transpose(" not in p for p in mine), scope  # a forward
        for v in vertices:
            assert any(f"{kind}.{v}" in p for p in mine), (scope, v)
    # each attention kernel under its layer type's scope and no other
    kernels = {p for p in paths if p.endswith("/pallas_call")}
    assert kernels
    for p in kernels:
        full = re.search(r"attention\.l[04]_attn", p) is not None
        assert ("/attend_full/" in p) == full, p
        assert ("/attend_window/" in p) == (not full), p


def test_a_segment_keeps_its_attention_kernels_output_and_recomputes_the_rest(
        lowered, monkeypatch):
    """`attention` names the kernel's o and lse for keeping
    (`remat_keeps`), so the forward kernel of each layer is in the step's
    forward once and not again in its segment's rematerialisation, where
    rotary, gate and the projections still are."""
    paths = paths_of(lowered)
    again = {p for p in paths if "/rematted_computation/" in p}
    for i in range(5):
        mine = {p for p in paths if f"attention.l{i}_attn/" in p
                or f"attention.l{i}_attn)/" in p}
        fwd = {p for p in mine if p.endswith("sparse_attention_fwd/pallas_call")}
        assert len(fwd) == 1 and not fwd & again, fwd
        assert "transpose(" not in next(iter(fwd))
        # the backward is one kernel a layer (PR 37), not dQ's and dK/dV's
        bwd = {p for p in mine if re.search(
            r"sparse_attention_(bwd|dq|dkv)/pallas_call$", p)}
        assert len(bwd) == 1 and next(iter(bwd)).endswith(
            "sparse_attention_bwd/pallas_call"), bwd
        for what in ("/rotary/", "/gate/", "/dot_general"):
            assert any(what in p for p in mine & again), (i, what)
    # the parent's text, where the layer names nothing: the kernel twice
    monkeypatch.setattr(decoder.AttentionLayer, "remat_keeps", lambda self: ())
    text = ComputationGraph(conf_of()).init().lower_step(
        mds_of(batch_of(0))).as_text(debug_info=True)
    twice = {p for p in paths_of(text)
             if p.endswith("sparse_attention_fwd/pallas_call")}
    assert len(twice) == 10
    assert sum("/rematted_computation/" in p for p in twice) == 5


@pytest.mark.parametrize("layer", [0, 1], ids=["full", "window"])
def test_fit_under_the_keeping_policy_is_fit_under_plain_rematerialisation(
        layer, monkeypatch):
    """Three steps of `fit` on one layer of the pattern (0: full attention
    and the dense MLP; 1: window attention and the experts): losses and
    parameters are the bits that `jax.checkpoint(seg_fn)` alone leaves,
    the kept o and lse being what the second run of the kernel wrote."""
    w = weights()

    def three_steps():
        net = ComputationGraph(conf_of(layers=[layer])).init()
        net._params = {n: jax.tree.map(jnp.array, w.get(n, d))
                       for n, d in net._params.items()}
        losses = []
        for i in range(3):
            net.fit(mds_of(batch_of(i)))
            losses.append(np.asarray(net._score))
        return net, losses, jax.tree.map(np.asarray, net._params)

    kept, losses, params = three_steps()
    assert kept._remat_plan()[2] == {0: (sa.KEEP,)}
    monkeypatch.setattr(decoder.AttentionLayer, "remat_keeps", lambda self: ())
    plain, plain_losses, plain_params = three_steps()
    assert plain._remat_plan()[2] == {}
    np.testing.assert_array_equal(losses, plain_losses)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(plain_params)):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(params[f"l{layer}_attn"]["Wq"],
                              np.asarray(w[f"l{layer}_attn"]["Wq"]))


def test_the_gauge_counts_the_segments_that_keep():
    from deeplearning4j_tpu import obs
    gauge = obs.default_registry().gauge("train.remat_kept_segments")
    net = ComputationGraph(conf_of()).init()
    assert net._remat_plan()[2] == {i: (sa.KEEP,) for i in (0, 2, 4, 6, 8)}
    assert gauge.value == 5


def test_no_square_array_of_the_sequence_is_in_the_lowered_step(monkeypatch):
    """The mask is made tile by tile inside the kernels, which the CPU
    lowers inline: at tiles of 32 x 32 no [T, T] value is in the step
    (T = 160 is no width of this model), where a mask operand would be
    one."""
    monkeypatch.setattr(sa, "BLOCK", 32)
    monkeypatch.setattr(sa, "WINDOW_BLOCK", (32, 32))
    net = ComputationGraph(conf_of()).init()
    text = net.lower_step(mds_of(batch_of(0, t=160))).as_text()
    assert re.search(r"tensor<(\d+x)*32x32x", text)       # the tiles
    assert not re.search(r"tensor<(\d+x)*160x160x", text)


MOE_WITHOUT_SHARED_SHA256 = \
    "417faa98aa5c556c2ca52d7f98c884b429184651176d29c983f5165a11c46e9c"


def test_an_expert_layer_without_a_shared_expert_lowers_as_it_did():
    """`shared_width` None and `routed_scale` 1 are Keye's layer: value and
    gradients lower to the parent commit's text (the hash is of that text
    under the suite's x64; a PR that changes the layer on purpose computes
    it anew), and its parameters are the parent's."""
    layer = decoder.MoELayer(n_in=64, n_out=64, n_experts=8,
                             experts_per_token=2, expert_width=32,
                             experts_held=4, first_held=2)
    p = layer.init_params(jax.random.PRNGKey(0))
    assert sorted(p) == ["Wd", "Wg", "Wr", "Wu"]

    def loss(p, x):
        y, st = layer.forward_with_state(p, x, layer.init_state())
        return jnp.sum(y * y) + jnp.sum(st["held_pairs"])

    text = jax.jit(jax.value_and_grad(loss)).lower(
        p, jnp.zeros((2, 32, 64), jnp.float32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        MOE_WITHOUT_SHARED_SHA256
    wr = np.asarray(p["Wr"])
    assert hashlib.sha256(wr.tobytes()).hexdigest()[:16] == \
        "7ceebcb77a3f0845"
