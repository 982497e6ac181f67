"""The sparse-attention mixture-of-experts decoder (models/zoo/keye_vl.py and
the layer kinds of nn/conf/layers/decoder.py) against its plain reference
(benchmarks/references/keye_vl.py), on seeded weights at a small size in
float32 through `ComputationGraph.fit`; the share test that ties one chip's
experts and vocabulary slice to the whole layer; no dropped pair under a
skewed routing; and ResNet-50's step, which the layer-loss seam shares and
must leave as it was.
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

from benchmarks.drivers.train_vl import (                         # noqa: E402
    positions as vl_positions)
from benchmarks.references import keye_vl as ref                  # noqa: E402
from deeplearning4j_tpu.datasets.dataset import (DataSet,         # noqa: E402
                                                 MultiDataSet)
from deeplearning4j_tpu.models.zoo import keye_vl_conf            # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import decoder             # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph          # noqa: E402
from deeplearning4j_tpu.parallel.moe import (held_experts_ffn,    # noqa: E402
                                             route_all)

# 2 layers, hidden 64, 8 experts top-2 of which 4 held, indexer 2 x 8,
# topk 16, half the vocabulary; T = 64 with a 4 x 4 image
MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "num_hidden_layers": 2,
    "vocab_size": 128, "num_experts": 4, "num_local_experts": 4,
    "num_experts_per_tok": 2, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "rope_scaling": {"mrope_section": [2, 3, 3]},
    "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 8, "topk": 16},
    "deployment": {"router_width": 8, "first_held": 2}}
TRAINER = {"learning_rate": 1e-3}
B, T, GRID = 2, 64, 4


def conf_of(model=MODEL, **over):
    sa, dep = model["sa_config"], model["deployment"]
    kw = dict(
        hidden_size=model["hidden_size"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], rope_theta=model["rope_theta"],
        mrope_section=model["rope_scaling"]["mrope_section"],
        num_experts=dep["router_width"],
        num_experts_per_tok=model["num_experts_per_tok"],
        moe_intermediate_size=model["moe_intermediate_size"],
        n_layers=model["num_hidden_layers"],
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
        q_chunk_size=16, experts_held=model["num_local_experts"],
        first_held=dep["first_held"], vocab_rows=model["vocab_size"],
        learning_rate=TRAINER["learning_rate"], data_type="float32")
    kw.update(over)
    return keye_vl_conf(**kw)


def weights(model=MODEL, seed=0):
    shapes = ref.param_shapes(model)
    flat = [(n, k) for n in sorted(shapes) for k in sorted(shapes[n])]
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = {n: {} for n in shapes}
    for (n, k), kk in zip(flat, keys):
        a = jax.random.normal(kk, shapes[n][k], jnp.float32)
        # norm weights near 1 and not at it; matrices large enough that
        # the router and the indexer discriminate at this size
        out[n][k] = 1.0 + 0.1 * a if len(shapes[n][k]) == 1 else 0.2 * a
    return out


def positions(t, grid):
    return vl_positions(t, (grid, grid))


def batch_of(seed, model=MODEL, t=T, grid=GRID, rows=B):
    k = jax.random.split(jax.random.PRNGKey(100 + seed), 2)
    ids = jax.random.randint(k[0], (rows, t), 0, model["vocab_size"],
                             jnp.int32)
    p = grid * grid
    return {"ids": ids,
            "image": jax.random.normal(k[1], (rows, p, model["hidden_size"]),
                                       jnp.float32),
            "positions": jnp.broadcast_to(positions(t, grid), (rows, t, 3)),
            "labels": jnp.roll(ids, -1, 1),
            "mask": jnp.broadcast_to((jnp.arange(t) >= p)
                                     .astype(jnp.float32), (rows, t))}


def mds_of(b):
    return MultiDataSet([b["ids"], b["image"], b["positions"]],
                        [b["labels"]], labels_masks=[b["mask"]])


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
           "losses": [], "l_i": [], "m1": None}
    for i, b in enumerate(batches):
        net.fit(mds_of(b))
        got["losses"].append(float(net._score))
        got["l_i"].append([float(net._model_state[f"l{j}_attn"]["layer_loss"])
                           for j in range(2)])
        if i == 0:
            got["m1"] = jax.tree.map(np.asarray, net._updater_state)
    got["params"] = jax.tree.map(np.asarray, net._params)
    got["gauges"] = net.publish_layer_gauges()
    with jax.default_matmul_precision("highest"):
        want = {"logits": ref.logits(w, batches[0], MODEL), "losses": [],
                "l_i": [], "g1": None}
        p = w
        m = v = jax.tree.map(jnp.zeros_like, w)
        for i, b in enumerate(batches):
            (l, aux), g = jax.value_and_grad(ref.loss, has_aux=True)(
                p, b, MODEL)
            want["losses"].append(float(l))
            want["l_i"].append([float(a) for a in aux["indexer_loss"]])
            if i == 0:
                want["g1"], want["aux1"] = g, aux
            out = jax.tree.map(lambda a, b_, c, d: ref.adam(
                a, b_, c, d, float(i + 1), TRAINER), p, m, v, g)
            p, m, v = (jax.tree.map(lambda _, o, j=j: o[j], w, out)
                       for j in range(3))
        want["params"] = p
    return got, want


CLOSE = dict(rtol=2e-4, atol=2e-6)      # float32 against float32 `highest`


@pytest.mark.parametrize("what", ["logits", "loss", "indexer_loss",
                                  "gradient", "three_adam_steps",
                                  "counters"])
def test_fit_agrees_with_the_reference(followed, what):
    got, want = followed
    if what == "logits":
        np.testing.assert_allclose(got["logits"], want["logits"],
                                   rtol=2e-4, atol=2e-4)
    elif what == "loss":
        np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    elif what == "indexer_loss":
        assert min(min(r) for r in want["l_i"]) > 1e-3
        np.testing.assert_allclose(got["l_i"], want["l_i"], rtol=1e-4)
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
                np.testing.assert_allclose(got["params"][n][k], p,
                                           rtol=1e-4, atol=2e-5,
                                           err_msg=f"{n}.{k}")
    else:
        aux = want["aux1"]                  # the gauges are the last step's
        assert got["gauges"]["moe.l0_moe.absent_pairs"] + 4 * got[
            "gauges"]["moe.l0_moe.held_pairs_mean"] == B * T * 2
        assert float(aux["selected_keys"][0]) > 12
        assert got["gauges"][
            "sparseattention.l0_attn.selected_keys_per_query"] > 12


def test_selections_are_the_references():
    """Every query's selected set, program against reference, first layer
    (both read the same embedding); ties with the 16th score are kept by
    both. 2 indexer heads of ReLU tie often, so this exercises the rule."""
    w, b = weights(), batch_of(0)
    want = np.asarray(ref.first_layer_selection(w, b, MODEL, 0, T))
    conf = conf_of()
    attn = conf.vertices["l0_attn"].conf
    h = conf.vertices["l0_norm1"].conf.forward(
        w["l0_norm1"], conf.vertices["embed"].conf.forward(
            w["embed"], b["ids"], extras=(b["image"],)))
    _, _, _, qi, ki, ww = attn.project(w["l0_attn"], h, b["positions"])
    got = np.stack([np.asarray(decoder.select_keys(
        decoder.index_scores(qi[r], ki[r], ww[r]), jnp.arange(T), attn.topk))
        for r in range(B)])
    assert (got == want).all()
    per_query = want.sum(-1)
    assert (per_query[:, :16] == np.arange(1, 17)).all()
    assert (per_query[:, 16:] >= 16).all() and per_query.max() < T


def dense_causal(p, h, pos, attn):
    q, k, v, *_ = attn.project(p, h, pos)
    b, t = h.shape[:2]
    s = jnp.einsum("bcgrd,bsgd->bgrcs", q, k) / np.sqrt(attn.head_dim)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bgrcs,bsgd->bcgrd", jax.nn.softmax(s, -1), v)
    return o.reshape(b, t, -1) @ p["Wo"]


def test_a_sequence_no_longer_than_topk_is_dense_causal_attention():
    w, b = weights(), batch_of(1, t=16, grid=2)
    attn = conf_of().vertices["l0_attn"].conf
    h = jax.random.normal(jax.random.PRNGKey(5), (B, 16, 64), jnp.float32)
    got = attn.forward(w["l0_attn"], h, extras=(b["positions"],))
    np.testing.assert_allclose(
        got, dense_causal(w["l0_attn"], h, b["positions"], attn), **CLOSE)
    # and past topk it is not: the selection is real
    b = batch_of(1)
    h = jax.random.normal(jax.random.PRNGKey(6), (B, T, 64), jnp.float32)
    got = attn.forward(w["l0_attn"], h, extras=(b["positions"],))
    assert float(jnp.max(jnp.abs(got - dense_causal(
        w["l0_attn"], h, b["positions"], attn)))) > 1e-3


def test_text_alone_is_one_axis_rope():
    """With the three axes equal the turn is plain RoPE at theta."""
    t, dh, theta = 12, 16, 10000.0
    pos = jnp.broadcast_to(jnp.arange(t)[None, :, None], (1, t, 3))
    cos, sin = decoder.mrope_angles(pos, dh, theta, (2, 3, 3))
    inv = theta ** (-np.arange(0, dh, 2) / dh)
    np.testing.assert_allclose(cos[0], np.cos(np.arange(t)[:, None] * inv),
                               rtol=1e-5, atol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, t, 2, dh), jnp.float32)
    got = decoder.rotate_half(x, cos, sin)
    ang = np.arange(t)[:, None] * inv
    a, b_ = np.asarray(x[..., :8]), np.asarray(x[..., 8:])
    c, s = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    np.testing.assert_allclose(
        got, np.concatenate([a * c - b_ * s, b_ * c + a * s], -1),
        rtol=1e-5, atol=1e-6)
    # an image's positions differ by axis: h and w turn their own slots
    img = jnp.asarray(positions(8, 2))[None]
    ci, _ = decoder.mrope_angles(img, dh, theta, (2, 3, 3))
    # row 2 of a 2 x 2 grid is (t, h, w) = (0, 1, 0): slot 2 is h's first
    assert np.allclose(ci[0, 2, :2], 1.0) and np.allclose(ci[0, 2, 5:], 1.0)
    assert float(ci[0, 2, 2]) == pytest.approx(np.cos(inv[2]), abs=1e-6)


# ------------------------------------------------------------ the shares
WHOLE = dict(MODEL, num_local_experts=8, deployment={"router_width": 8,
                                                     "first_held": 0})


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    """Held ranges 0-1 .. 6-7 (four shares of two), each computed by the
    program's layer with ITS experts' weights, summed: the uncut
    reference's expert layer over all 8."""
    w = weights(WHOLE)["l0_moe"]
    u = jax.random.normal(jax.random.PRNGKey(3), (B, T, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, counts = ref.experts_part(w, u.reshape(B * T, 64),
                                        ref.sizes(WHOLE), ref.linear(False))
        total = 0.0
        for first in range(0, 8, 2):
            layer = decoder.MoELayer(n_in=64, n_out=64, n_experts=8,
                                     experts_per_token=2, expert_width=32,
                                     experts_held=2, first_held=first)
            share = {"Wr": w["Wr"], **{k: w[k][first:first + 2]
                                       for k in ("Wg", "Wu", "Wd")}}
            y, st = layer.forward_with_state(share, u, layer.init_state())
            np.testing.assert_array_equal(st["held_pairs"],
                                          counts[first:first + 2])
            total = total + y
    np.testing.assert_allclose(total.reshape(B * T, 64), want, **CLOSE)
    assert int(counts.sum()) == B * T * 2


def test_the_slices_of_the_vocabulary_are_the_whole_heads_logits():
    w = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (64, 128))
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, 64), jnp.float32)
    whole = decoder.LMHeadLayer(n_in=64, n_out=128).forward({"W": w}, x)
    parts = [decoder.LMHeadLayer(n_in=64, n_out=16).forward(
        {"W": w[:, i:i + 16]}, x) for i in range(0, 128, 16)]
    np.testing.assert_allclose(jnp.concatenate(parts, -1), whole, **CLOSE)


def test_no_routed_pair_of_a_held_expert_is_dropped_under_skew():
    """Every token's first choice is expert 5 (a router column far above
    the rest): 128 pairs on one held expert, all computed, in blocks of 16
    rows of which only the needed ones run."""
    n, d, f = 128, 32, 16
    k = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jnp.abs(jax.random.normal(k[0], (n, d), jnp.float32)) + 0.1
    wr = 0.1 * jax.random.normal(k[1], (d, 8)).at[:, 5].set(3.0)
    wg, wu = (0.2 * jax.random.normal(kk, (8, d, f)) for kk in k[2:4])
    wd = 0.2 * jax.random.normal(k[4], (8, f, d))
    experts, gates = route_all(wr, x, 2)
    assert (experts[:, 0] == 5).all()
    y, counts, n_run = held_experts_ffn(x, experts, gates, wg[4:6], wu[4:6],
                                        wd[4:6], 4, block_rows=16)
    assert int(counts[1]) == n and int(counts.sum()) >= n
    assert int(n_run) == -(-int(counts.sum()) // 16) < 2 * n // 16
    want = jnp.zeros((n, d))
    for e in (4, 5):
        out = (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
        want = want + out * jnp.sum(jnp.where(experts == e, gates, 0.0),
                                    -1)[:, None]
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.min(jnp.linalg.norm(y, axis=-1))) > 0   # no token lost


def scan_of_conditionals(x, experts, gates, w_gate, w_up, w_down, first_held,
                         block_rows):
    """The plain reference of the walk over blocks, as it stood before the
    trip count: EVERY block of the static worst case under a `lax.cond`,
    scanned under `jax.checkpoint`, differentiated by jax."""
    N, D = x.shape
    k = experts.shape[1]
    G = w_gate.shape[0]
    local = experts.reshape(-1) - first_held
    key = jnp.where((local >= 0) & (local < G), local, G)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.bincount(key, length=G + 1)[:G].astype(jnp.int32)
    ends = jnp.cumsum(counts)
    n_held = ends[-1]
    starts = ends - counts
    rows = min(int(block_rows), N * k)
    n_blocks = -(-(N * k) // rows)
    order = jnp.pad(order, (0, n_blocks * rows - N * k))
    gate_flat = gates.reshape(-1)

    def block(y, i):
        lo = i * rows

        def run(y):
            pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
            valid = (lo + jnp.arange(rows) < n_held)[:, None]
            tok = pair // k
            sizes = (jnp.clip(ends, lo, lo + rows)
                     - jnp.clip(starts, lo, lo + rows))
            rd = lambda a, w: jnp.where(valid, jax.lax.ragged_dot(
                a, w, sizes, preferred_element_type=jnp.float32), 0.0)
            xs = jnp.where(valid, x[tok], 0)
            h = (jax.nn.silu(rd(xs, w_gate)) * rd(xs, w_up)).astype(x.dtype)
            out = rd(h, w_down) * jnp.where(valid[:, 0], gate_flat[pair],
                                            0.0)[:, None]
            return y.at[tok].add(out)

        return jax.lax.cond(lo < n_held, run, lambda y: y, y), None

    y, _ = jax.lax.scan(jax.checkpoint(block),
                        jnp.zeros((N, D), jnp.float32),
                        jnp.arange(n_blocks, dtype=jnp.int32))
    return y


# 96 tokens, 2 choices each, experts 4 .. 7 held, blocks of 40 pairs (1.25
# times an even share of 32, as the default is; 5 in the static worst case);
# the trips each routing must take
WALK = {"n": 96, "d": 32, "f": 16, "k": 2, "held": 4, "rows": 40}
ROUTINGS = {"no_held_pair": 0, "even": 1, "every_pair_held": 5}


def walk_case(routing, dtype):
    c = WALK
    k = jax.random.split(jax.random.PRNGKey(11), 6)
    x = jax.random.normal(k[0], (c["n"], c["d"]), jnp.float32)
    gates = jax.nn.softmax(jax.random.normal(k[1], (c["n"], c["k"]),
                                             jnp.float32), -1)
    if routing == "every_pair_held":        # both choices among 4 .. 7
        first = jax.random.randint(k[2], (c["n"],), 4, 8)
        experts = jnp.stack([first, 4 + (first - 3) % 4], -1)
    elif routing == "no_held_pair":         # both among 8 .. 23
        first = jax.random.randint(k[2], (c["n"],), 8, 24)
        experts = jnp.stack([first, 8 + (first - 7) % 16], -1)
    else:                                   # 24 experts, 4 held: a sixth,
                                            # 32 pairs expected
        first = jax.random.randint(k[2], (c["n"],), 0, 24)
        experts = jnp.stack([first, (first + 7) % 24], -1)
    w = [0.3 * jax.random.normal(kk, shape, jnp.float32) for kk, shape in zip(
        k[3:], [(c["held"], c["d"], c["f"])] * 2
        + [(c["held"], c["f"], c["d"])])]
    cast = lambda a: a.astype(dtype)
    return (cast(x), experts.astype(jnp.int32), gates, *map(cast, w))


@pytest.fixture(scope="module")
def walks():
    """y and the five gradients of sum(y * seeded cotangent), by the walk
    with a trip count and by the scan of conditionals, jitted, a case a
    (routing, dtype)."""
    made = {}

    def of(routing, dtype):
        if (routing, dtype) not in made:
            x, experts, gates, wg, wu, wd = walk_case(routing, dtype)
            ct = jax.random.normal(jax.random.PRNGKey(12), x.shape,
                                   jnp.float32)

            def both(fn):
                def loss(x, gates, wg, wu, wd):
                    y = fn(x, gates, wg, wu, wd)
                    return jnp.sum(y * ct), y
                (_, y), g = jax.jit(jax.value_and_grad(
                    loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                        x, gates, wg, wu, wd)
                return dict(zip(("y", "x", "gates", "Wg", "Wu", "Wd"),
                                (y,) + g))

            mine = lambda *a: held_experts_ffn(
                a[0], experts, *a[1:], 4, block_rows=WALK["rows"])
            got = both(lambda *a: mine(*a)[0])
            want = both(lambda x, gates, wg, wu, wd: scan_of_conditionals(
                x, experts, gates, wg, wu, wd, 4, WALK["rows"]))
            made[routing, dtype] = got, want, int(
                mine(x, gates, wg, wu, wd)[2])
        return made[routing, dtype]

    return of


@pytest.mark.parametrize("what", ["y", "x", "gates", "Wg", "Wu", "Wd"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_walk_with_a_trip_count_is_the_scan_of_conditionals(
        walks, routing, dtype, what):
    """Bit for bit at equal `block_rows`: the same blocks in the same
    order forward, the running blocks last to first backward; what went is
    additions of zero."""
    got, want, n_run = walks(routing, jnp.dtype(dtype))
    assert n_run == ROUTINGS[routing]
    assert got[what].dtype == want[what].dtype
    np.testing.assert_array_equal(np.asarray(got[what], np.float32),
                                  np.asarray(want[what], np.float32))
    if routing == "no_held_pair":
        assert not np.asarray(got[what], np.float32).any()
    else:
        assert np.asarray(got[what], np.float32).any()


# ------------------------------------------------- what the seam shares
def test_remat_from_the_configuration_changes_memory_not_results():
    w, b = weights(), batch_of(0)
    scores = []
    for remat in (True, False):
        net = trainer(w, remat=remat)
        assert net._remat is remat
        net.fit(mds_of(b))
        net.fit(mds_of(b))
        scores.append(float(net._score))
    assert scores[0] == pytest.approx(scores[1], rel=1e-6)


def test_configuration_round_trips_and_names_its_kinds():
    conf = conf_of()
    kinds = {s.conf.layer_type for s in conf.vertices.values() if s.is_layer}
    assert kinds == {"tokenembedding", "rmsnorm", "sparseattention", "moe",
                     "lmhead"}
    twin = type(conf).from_json(conf.to_json())
    assert twin.to_dict() == conf.to_dict()
    assert twin.global_conf["remat_segments"] is True
    text = ComputationGraph(conf).init().lower_step(
        mds_of(batch_of(0))).as_text(debug_info=True)
    for scope in ("sparseattention.l0_attn", "moe.l1_moe", "indexer",
                  "select", "experts", "rmsnorm.norm_f", "loss.head"):
        assert scope in text, scope


def test_the_experts_of_the_lowered_step_are_loops_under_both_names():
    """What `train_moe_device_ms` and `train_moe_roofline` read is a name
    on an operation's path: the walk's forward loop, the one the segment
    computes again and the hand-written backward's are all under
    `moe.<vertex>` and `experts`, and no `cond` is (a `while`'s own
    condition is `while/cond`)."""
    text = ComputationGraph(conf_of()).init().lower_step(
        mds_of(batch_of(0))).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*/[^"]*)"', text))
    experts = {p for p in paths if "/experts/" in p}
    assert not [p for p in experts
                if re.search(r"(?<!while)/cond(/|$)", p)]
    # a product in an outlined function would read a path of its own
    products = {p for p in paths if p.endswith("/ragged_dot_general")}
    assert products and products <= experts
    for vertex in ("l0_moe", "l1_moe"):
        mine = {p for p in products if f"moe.{vertex}" in p.split(
            "/experts/")[0]}
        assert {p.split("/experts/")[1] for p in mine} == {
            "while/body/ragged_dot_general",                # a forward
            "while/body/jvp()/ragged_dot_general",          # the block again
            "while/body/transpose(jvp())/ragged_dot_general"}, vertex
        assert any(p.startswith("jit(step)/transpose(") for p in mine)
    assert all("moe.l" in p for p in experts)


def test_the_backward_of_attend_in_the_lowered_step_is_one_kernel():
    """What `train_sparse_attn_device_ms` reads is the scope `attend`: the
    row's backward holds under it ONE kernel that makes dQ, dK and dV
    (PR 37: `sparse_attention_bwd`; the row's path carries no vertex, so
    both layers' read the same) where it held dQ's and dK/dV's, beside the
    forward kernel of the step and of the row's rematerialisation."""
    text = ComputationGraph(conf_of()).init().lower_step(
        mds_of(batch_of(0))).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*/[^"]*)"', text))
    mine = {p for p in paths if re.search(
        r"sparse_attention_(fwd|bwd|dq|dkv)/pallas_call$", p)}
    assert mine == {"attend/sparse_attention_fwd/pallas_call",
                    "checkpoint/attend/sparse_attention_fwd/pallas_call",
                    "checkpoint/attend/sparse_attention_bwd/pallas_call"}


SKEWED = dict(MODEL, num_hidden_layers=1,
              deployment={"router_width": 16, "first_held": 0})


@pytest.mark.parametrize("routing, blocks_run", [("even", 1.0),
                                                 ("every_pair_held", 2.0)])
def test_the_gauge_says_how_many_blocks_the_last_step_ran(routing,
                                                          blocks_run):
    """320 tokens, 2 of 16 experts each, 4 held: the default block is 512
    of the 640 sorted pairs, 2 in the static worst case. Seeded routing
    holds about 160 pairs here: one trip. A router of zeros ties every
    expert and `top_k` takes experts 0 and 1 for every token: all 640
    pairs on held experts, both blocks."""
    w = weights(SKEWED)
    if routing == "every_pair_held":
        w["l0_moe"]["Wr"] = jnp.zeros_like(w["l0_moe"]["Wr"])
    net = trainer(w, num_experts=16, n_layers=1, first_held=0)
    net.fit(mds_of(batch_of(0, SKEWED, t=160)))
    said = net.publish_layer_gauges()
    held = 4 * said["moe.l0_moe.held_pairs_mean"]
    assert said["moe.l0_moe.blocks_run"] == blocks_run == -(-held // 512)
    assert (held == 640) == (routing == "every_pair_held")


RESNET_STEP_SHA256 = \
    "8d3db65590b6ab7d0bb0d6d16b7bd530c70af3a51ec1a33bc55ad1eb6ffa75d7"


def test_resnet50s_step_lowers_to_the_text_it_lowered_to_before():
    """A graph without a layer loss, extra inputs or remat lowers to the
    same program as before the seam (PR 28): the hash is of the parent
    commit's text for this small ResNet-50 under the suite's x64. A PR that changes ResNet-50's
    step on purpose computes it anew (the three lines below)."""
    from deeplearning4j_tpu.models.zoo.resnet import resnet50_conf
    net = ComputationGraph(resnet50_conf(height=32, width=32,
                                         num_classes=10)).init()
    ds = DataSet(jnp.zeros((2, 32, 32, 3), jnp.bfloat16),
                 jnp.zeros((2, 10), jnp.float32))
    text = net.lower_step(ds).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == RESNET_STEP_SHA256
