"""The Kimi-delta-attention + latent-attention + group-limited-experts decoder
(``models/kda_mla_moe.py``, ``ops/kda.py``, the attention halves of
``models/mla_moe.py`` with a head-wise gate, ``ops/moe.py``'s grouped router)
and the engine's per-slot state beside a latent pool (``serve/decode.py``)
against the plain reference ``benchmark/reference_kda_mla_moe.py`` — the
repo's one copy of the equations — at tiny sizes on the CPU, Pallas kernels
interpreted.

The mathematics is checked in float32 (the same bodies run on a float32
tree), where the program must agree with the reference to rounding; the
bfloat16 run is then held to a bfloat16-sized tolerance.
"""
import functools
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_kda_mla_moe as ref
from mxnet_tpu import obs
from mxnet_tpu.models import kda_mla_moe, mla_moe, transformer
from mxnet_tpu.ops import kda, moe
from mxnet_tpu.serve import DecodeEngine, DecodeScheduler
from mxnet_tpu.serve.kvcache import SCRATCH_PAGE

pytestmark = pytest.mark.decode

SEED = 3000000019      # over 2**31, as the driver's are
# layer 0 (KDA, dense MLP) and two whole groups of 2 KDA + 1 MLA out of a
# model of 9; 32 experts in 4 groups of 8, a token keeps 2 groups; the share
# held is HALF of group 1 and does not start at 0
CFG = {
    "vocab_size": 96, "vocab_first": 0, "hidden_size": 64, "num_layers": 7,
    "layers": [0, 3, 4, 5, 6, 7, 8], "group_size": 3, "first_dense": 2,
    "num_heads": 4, "kda_key_dim": 16, "kda_value_dim": 16, "conv_width": 4,
    "gate_lower_bound": -5, "qk_nope": 16, "qk_rope": 8, "v_head": 16,
    "kv_rank": 32, "rope": {"theta": 6000000, "factor": 1},
    "dense_width": 128, "expert_width": 32, "router_experts": 32,
    "experts_first": 8, "experts_held": 4, "experts_per_token": 4,
    "routed_scale": 2.5, "router_groups": 4, "router_groups_kept": 2,
    "rms_eps": 1e-6, "max_length": 96}
PAGE, SLOTS, PIECE = 8, 2, 16
ROW = 128              # 32 + 8 values in a row of whole lane tiles
N_KDA, N_MLA, N_MOE = 5, 2, 6


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def params():
    return kda_mla_moe.init_params(CFG, SEED)


def _engine(params, dtype="float32", slots=SLOTS):
    model = kda_mla_moe.KDAMLAMoEDecodeModel(
        CFG, params=f32(params) if dtype == "float32" else params)
    return DecodeEngine(model, slots=slots, page_size=PAGE, num_pages=25,
                        prompt_buckets=[16, 32, 48])


# -- the configuration and the weights ----------------------------------------

def test_config_from_the_published_keys():
    """``config_from_hf`` of the catalog row's config (the file's
    ``published``) with the cut's share is the file's ``model`` block; the
    file's own top level is the published config but for the four keys
    reduced; the whole model's pattern is 35 KDA : 7 MLA; and the counts the
    deployment states are ``leaf_shapes``'."""
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "configs", "ling-3.0-flash-vl.json")
    with open(path) as f:
        file = json.load(f)
    model = dict(file["model"])
    assert model.pop("kind") == "kda_mla_moe_lm"
    held = [0] + list(range(6, 18))
    assert kda_mla_moe.config_from_hf(
        file["published"], experts_first=0, experts_held=32, vocab_first=0,
        vocab_rows=19648, layers=held, max_length=8192) == model
    assert file["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size", "max_position_embeddings"]
    for key, value in file["published"].items():
        assert file[key] == value or key in file["reduced"], key
    assert (file["num_hidden_layers"], file["num_experts"],
            file["vocab_size"]) == (13, 32, 19648)
    whole = dict(model, layers=list(range(42)), num_layers=42)
    n_kda, n_mla, routed = kda_mla_moe.layer_kinds(whole)
    assert (len(n_kda), n_mla, len(routed)) == (
        35, [5, 11, 17, 23, 29, 35, 41], 40)
    n_kda, n_mla, routed = kda_mla_moe.layer_kinds(model)
    assert (len(n_kda), n_mla, routed) == (11, [6, 12], list(range(1, 13)))
    lm = kda_mla_moe.KDAMLAMoEDecodeModel(
        model, params=jax.eval_shape(
            lambda: kda_mla_moe.init_params(model, 0)))
    total = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(lm.params))
    count = kda_mla_moe.count_params(model)
    assert total == count["total"] == 3256770784
    assert f"{total:,} parameters" in file["deployment"]
    assert (count["kda"], count["mla"], count["expert"]) == (
        63052448, 31968256, 5898240)
    assert lm.cache_row == (640,) and lm.paged_layers == 2
    assert lm.state == {"s": ((11, 32, 128, 128), jnp.float32),
                        "tail": ((11, 288, 128), jnp.bfloat16)}
    assert (lm.moe_row_tile(128), lm.moe_row_tile(2048)) == (16, 64)
    # the whole model's last layers clamp their experts: not written
    with pytest.raises(NotImplementedError):
        kda_mla_moe.config_from_hf(file["published"])
    with pytest.raises(NotImplementedError):
        kda_mla_moe.config_from_hf(dict(file["published"], n_group=8,
                                        kda_safe_gate=False), layers=held)


def test_program_and_reference_make_the_same_weights(params):
    """Every leaf, bit for bit: the two state the same scheme on their own,
    keyed by the layer's index in the WHOLE model."""
    _, mla, routed = kda_mla_moe.layer_kinds(CFG)
    for i, (lp, layer) in enumerate(zip(params["layers"], CFG["layers"])):
        w = ref.layer_weights(CFG, SEED, layer)
        names = ["attn_norm", "mlp_norm"]
        if i in mla:
            names += list(kda_mla_moe.MLA)
        else:
            names += list(kda_mla_moe.KDA)
            np.testing.assert_array_equal(
                f32(lp["in_w"]), jnp.concatenate(
                    [w[n] for n in kda_mla_moe.KDA_IN], axis=-1))
            assert lp["A_log"].dtype == jnp.float32
        names += list(kda_mla_moe.ROUTED if i in routed
                      else kda_mla_moe.DENSE)
        assert sorted(names + ([] if i in mla else ["in_w"])) == sorted(lp)
        for name in names:
            np.testing.assert_array_equal(f32(lp[name]), w[name])
        if i in routed:
            j = routed.index(i)
            for name in ("gate_w", "up_w", "down_w"):
                np.testing.assert_array_equal(
                    f32(params["experts"][name][4 * j:4 * j + 4]),
                    w["experts_" + name])
    for name in ("embed", "head"):
        np.testing.assert_array_equal(f32(params[name]),
                                      ref.vocab_weights(CFG, SEED, name))
    # an embedding row is N(0, 1), the taps near 1, the decays' bias near -4
    assert 0.8 < float(f32(params["embed"]).std()) < 1.2
    kda_layer = f32(params["layers"][0])
    assert abs(float(kda_layer["conv_w"].mean()) - 1) < 0.05
    assert abs(float(kda_layer["dt_bias"].mean()) + 4) < 0.1
    # what writes into the residual stream is drawn at an eighth of the rest
    assert 0.9 < float(kda_layer["ko_w"].std()) / (0.02 / 8) < 1.1
    assert 0.9 < float(f32(params["experts"]["down_w"]).std()) / (0.02 / 8) < 1.1
    assert 0.9 < float(f32(params["experts"]["up_w"]).std()) / 0.02 < 1.1


# -- the rule: chunked, in pieces, one token -------------------------------------

def _rule_inputs(seed, n, h=2, dk=16, dv=8, decays="mixed"):
    rng = np.random.default_rng(seed)

    def unit(*shape):
        x = rng.standard_normal(shape).astype(np.float32)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    g = {"mixed": -5.0 * rng.uniform(size=(n, h, dk)) ** 3,
         "uniform": -5.0 * rng.uniform(size=(n, h, dk)),
         "bound": np.full((n, h, dk), -5.0),
         "none": np.zeros((n, h, dk))}[decays].astype(np.float32)
    return (unit(n, h, dk) * dk ** -0.5, unit(n, h, dk),
            rng.standard_normal((n, h, dv)).astype(np.float32), g,
            rng.uniform(size=(n, h)).astype(np.float32),
            rng.standard_normal((h, dk, dv)).astype(np.float32))


# the chunked rule's two forms (``kda.chunked_form``), the kernel interpreted
# at 2 heads of 16 x 8; jitted once each, so that the cases of a length share
# a program
FORMS = {"xla": jax.jit(kda.kda_chunked),
         "kda_prefill": jax.jit(functools.partial(
             kda.kda_chunked, impl="pallas", interpret=True))}


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("decays", ["mixed", "uniform", "bound", "none"])
@pytest.mark.parametrize("length", [1, 15, 37, 64, 100, 200])
def test_chunked_rule_is_the_recurrence(length, decays, form):
    """``kda_chunked`` against ``kda_recurrent`` from a state that is not
    zero: lengths that are no multiple of the sub-chunk (16) or the chunk
    (64), decays spread over (-5, 0) by channel, no decay at all, and EVERY
    log decay at the bound -5 — where the form that factors ``exp(G_i) .
    exp(-G_j)`` over a chunk overflows float32 (320 > 88). 5e-6 of outputs
    of size ~1: float32 rounding through the solve."""
    q, k, v, g, beta, s0 = _rule_inputs(length, length, decays=decays)
    want_o, want_s = kda.kda_recurrent(q, k, v, g, beta, s0)
    o, s1 = FORMS[form](q, k, v, g, beta, s0)
    assert np.all(np.isfinite(o)) and np.all(np.isfinite(s1))
    np.testing.assert_allclose(o, want_o, atol=5e-6)
    np.testing.assert_allclose(s1, want_s, atol=5e-6)


@pytest.mark.parametrize("form", list(FORMS))
def test_chunked_rule_with_one_chunk_at_the_bound_among_others(form):
    """Tokens 64-127 — one whole chunk — decay every channel by e^-5 a
    token, the chunks around it hardly: the state that reaches token 128 is
    what the chunk wrote itself, and all of it finite."""
    q, k, v, g, beta, s0 = _rule_inputs(7, 192, decays="mixed")
    g = np.array(g)
    g[64:128] = -5.0
    want_o, want_s = kda.kda_recurrent(q, k, v, g, beta, s0)
    o, s1 = FORMS[form](q, k, v, g, beta, s0)
    assert np.all(np.isfinite(o))
    np.testing.assert_allclose(o, want_o, atol=5e-6)
    np.testing.assert_allclose(s1, want_s, atol=5e-6)
    with pytest.raises(ValueError):
        kda.kda_chunked(q, k, v, g, beta, s0, chunk=24)


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("piece,length", [(16, 43), (64, 150), (128, 200)])
def test_pieces_carrying_the_state_are_the_whole_prompt(piece, length, form):
    """The state a piece hands the next is all the next needs: pieces of 16
    (under a chunk), 64 and 128, the last one ragged, against the whole
    prompt in one call and against the recurrence; a position masked (beta
    0, g 0) behind the prompt's end writes nothing."""
    q, k, v, g, beta, s0 = _rule_inputs(length + piece, length)
    want_o, want_s = kda.kda_recurrent(q, k, v, g, beta, s0)
    chunked = FORMS[form]
    whole_o, whole_s = chunked(q, k, v, g, beta, s0)
    state, outs = jnp.asarray(s0), []
    padded = -(-length // piece) * piece
    live = np.arange(padded) < length

    def pad(a):
        return np.pad(a, ((0, padded - length),) + ((0, 0),) * (a.ndim - 1),
                      constant_values=7.0)        # garbage, masked below

    qp, kp, vp, gp, bp = (pad(a) for a in (q, k, v, g, beta))
    gp = np.where(live[:, None, None], gp, 0.0)
    bp = np.where(live[:, None], bp, 0.0)
    for start in range(0, padded, piece):
        cut = slice(start, start + piece)
        o, state = chunked(qp[cut], kp[cut], vp[cut], gp[cut], bp[cut],
                           state)
        outs.append(o)
    got = jnp.concatenate(outs)[:length]
    np.testing.assert_allclose(got, whole_o, atol=5e-6)
    np.testing.assert_allclose(state, whole_s, atol=5e-6)
    np.testing.assert_allclose(got, want_o, atol=5e-6)
    np.testing.assert_allclose(state, want_s, atol=5e-6)


def test_the_chunked_rules_form_follows_chunked_form(monkeypatch):
    """``kda_chunked`` takes the kernel exactly where
    ``gated_delta.chunked_form`` takes ``gdn_prefill``: ``impl`` pallas,
    chunks of ``CHUNK``, and heads of whole 128-lane blocks or interpreted;
    and it is ``kda.chunked_form`` the function asks, once a call."""
    from mxnet_tpu.ops import gated_delta

    assert kda.chunked_form(128, 128, impl="pallas") == "kda_prefill"
    assert kda.chunked_form(16, 8, impl="pallas") == "xla"
    assert kda.chunked_form(16, 8, impl="pallas",
                            interpret=True) == "kda_prefill"
    assert kda.chunked_form(128, 128, 32, "pallas") == "xla"
    assert kda.chunked_form(128, 128) == "xla"
    for args in ((128, 128, 64, "pallas", False), (128, 64, 64, "pallas", False),
                 (16, 8, 64, "pallas", True), (128, 128, 32, "pallas", True),
                 (128, 128, 64, "xla", True)):
        assert (kda.chunked_form(*args) == "kda_prefill") == (
            gated_delta.chunked_form(*args) == "gdn_prefill"), args
    q, k, v, g, beta, s0 = _rule_inputs(3, 64)

    def kernels(chunk, impl):
        def fn(*args):
            return kda.kda_chunked(*args, chunk=chunk, impl=impl,
                                   interpret=True)
        return str(jax.make_jaxpr(fn)(q, k, v, g, beta, s0)).count(
            "pallas_call")

    assert kernels(64, "pallas") == 1
    assert kernels(32, "pallas") == 0 and kernels(64, "xla") == 0
    asked, real = [], kda.chunked_form
    monkeypatch.setattr(kda, "chunked_form",
                        lambda *a, **kw: asked.append(a) or real(*a, **kw))
    assert kernels(64, "pallas") == 1
    assert asked == [(16, 8, 64, "pallas", True)]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_one_token_rule_in_place(impl):
    """``kda_step`` (the ``kda_decode`` kernel interpreted, and its XLA
    twin): layer 1 of every live slot's state is the recurrence's next
    state, a dead slot's state and the other layer are not touched, and the
    outputs of the live slots are the recurrence's."""
    b, h, dk, dv = 5, 2, 16, 8
    rng = np.random.default_rng(11)
    states = rng.standard_normal((b + 1, 3, h, dk, dv)).astype(np.float32)
    q, k, v, g, beta, _ = _rule_inputs(12, b, h, dk, dv)
    live = np.array([True, False, True, True, False])
    o, after = kda.kda_step(jnp.asarray(states), 1, q, k, v, g, beta,
                            jnp.asarray(live), impl=impl, interpret=True)
    assert after.shape == states.shape and o.shape == (b, h, dv)
    for i in range(b):
        want_o, want_s = kda.kda_recurrent(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                           g[i:i + 1], beta[i:i + 1],
                                           states[i, 1])
        if live[i]:
            np.testing.assert_allclose(o[i], want_o[0], atol=2e-6)
            np.testing.assert_allclose(after[i, 1], want_s, atol=2e-6)
        else:
            np.testing.assert_array_equal(after[i, 1], states[i, 1])
    np.testing.assert_array_equal(after[:b, 0], states[:b, 0])
    np.testing.assert_array_equal(after[:b, 2], states[:b, 2])


def test_the_folded_convolution_step_is_the_plain_one():
    """``_conv_step`` on a tail folded to rows of 128 lanes (the published
    size: 3 x 12288 as 288 x 128) and unfolded (the tiny sizes) against
    ``ops.gated_delta.causal_conv_step``."""
    from mxnet_tpu.ops import gated_delta

    rng = np.random.default_rng(13)
    for c, fold in ((512, (12, 128)), (48, (3, 48))):
        x = jnp.asarray(rng.standard_normal((3, c)), jnp.float32)
        tail = jnp.asarray(rng.standard_normal((3, 3, c)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((4, c)), jnp.float32)
        want, want_tail = gated_delta.causal_conv_step(x, tail, w)
        got, new = kda_mla_moe._conv_step(x, tail.reshape((3,) + fold), w)
        np.testing.assert_allclose(got, want, atol=1e-6)
        np.testing.assert_array_equal(new, want_tail.reshape((3,) + fold))


# -- the router ---------------------------------------------------------------------

def _plain_grouped(s, b, k, scale, groups, kept):
    """Top-k of the kept groups by a plain loop in numpy, ties to the lower
    id (of groups and of experts)."""
    chosen, gates, keeps = [], [], []
    for row in np.asarray(s, np.float64):
        c = row + b
        per = len(c) // groups
        score = [np.sort(c[i * per:(i + 1) * per])[-2:].sum()
                 for i in range(groups)]
        best = sorted(range(groups), key=lambda i: (-score[i], i))[:kept]
        among = [e for e in range(len(c)) if e // per in best]
        top = sorted(among, key=lambda e: (-c[e], e))[:k]
        chosen.append(top)
        gates.append(scale * row[top] / row[top].sum())
        keeps.append([i in best for i in range(groups)])
    return np.array(chosen), np.array(gates), np.array(keeps)


@pytest.mark.parametrize("ties", [False, True])
def test_grouped_router_against_a_plain_top_k_of_the_kept_groups(ties):
    """``route_grouped``: 32 experts in 4 groups of 8, 2 groups kept, 4
    chosen. With ``ties``: the router's weights are zero and the bias takes
    few values, so groups tie (the lower id is kept) and experts tie inside
    and across the kept groups (the lower id is chosen)."""
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    if ties:
        w = jnp.zeros((64, 32), jnp.float32)          # every score 0.5
        b = jnp.asarray(rng.integers(0, 2, 32) * 0.25, jnp.float32)
    else:
        w = jnp.asarray(0.3 * rng.standard_normal((64, 32)), jnp.float32)
        b = jnp.asarray(0.1 * rng.standard_normal(32), jnp.float32)
    chosen, gates, kept = moe.route_grouped(h, w, b, 4, 2.5, 4, 2)
    s = jax.nn.sigmoid(jnp.dot(h, w, precision="highest"))
    want = _plain_grouped(s, np.asarray(b, np.float64), 4, 2.5, 4, 2)
    np.testing.assert_array_equal(chosen, want[0])
    np.testing.assert_allclose(gates, want[1], atol=1e-6)
    np.testing.assert_array_equal(kept, want[2])
    np.testing.assert_allclose(gates.sum(-1), 2.5, atol=1e-5)
    assert np.all(kept.sum(-1) == 2)
    # every choice lies in a kept group
    assert np.all(np.take_along_axis(np.asarray(kept),
                                     np.asarray(chosen) // 8, axis=1))
    # the reference's router is the same function
    m = dict(CFG, router_experts=32)
    _, r_chosen, r_gates, r_kept = ref.route(
        m, {"router_w": w, "router_b": b}, h, "f32")
    np.testing.assert_array_equal(r_chosen, chosen)
    np.testing.assert_array_equal(r_kept, kept)
    np.testing.assert_allclose(r_gates, gates, atol=1e-6)


def test_an_attention_half_without_the_gate_leaf_traces_what_it_traced():
    """``mla_moe``'s halves with no ``og_w``: the jaxpr has no sigmoid — the
    programs of the cells that share them are the parent's —, with it one
    a layer."""
    cfg = {"num_heads": 4, "qk_nope": 16, "qk_rope": 8, "kv_rank": 32,
           "v_head": 16, "rms_eps": 1e-6, "rope": {"theta": 1e4, "factor": 1}}
    rng = np.random.default_rng(3)

    def leaf(*shape):
        return jnp.asarray(0.1 * rng.standard_normal(shape), jnp.float32)

    lp = {"attn_norm": leaf(64) + 1, "q_w": leaf(4 * 24, 64),
          "kva_w": leaf(64, 40), "kv_norm": leaf(32) + 1,
          "uk_w": leaf(4, 16, 32), "uv_w": leaf(4, 32, 16),
          "o_w": leaf(64, 64)}
    x, cos, sin = leaf(16, 64), jnp.ones((16, 4)), jnp.zeros((16, 4))
    plain = jax.make_jaxpr(
        lambda lp: mla_moe.prefill_attention(cfg, lp, x, cos, sin))(lp)
    gated = jax.make_jaxpr(
        lambda lp: mla_moe.prefill_attention(cfg, lp, x, cos, sin))(
            dict(lp, og_w=leaf(64, 4)))
    assert "logistic" not in str(plain) and str(gated).count("logistic") == 1
    # a gate of +40 on every head is no gate
    y, _ = mla_moe.prefill_attention(cfg, lp, x, cos, sin)
    open_gate = dict(lp, og_w=jnp.zeros((64, 4)))
    half, _ = mla_moe.prefill_attention(cfg, open_gate, x, cos, sin)
    np.testing.assert_allclose(half - x, 0.5 * (y - x), atol=1e-5)


# -- the model against the reference --------------------------------------------

@pytest.mark.parametrize("length", [3, 16, 43, 48])
def test_prefill_whole_is_the_reference(length, params):
    """``prefill`` over a padded prompt: the logits at its last position,
    the counters (6 expert layers x 4 choices a live token; 5 KDA layers a
    live token), and a state that is finite whatever the pad held."""
    model = kda_mla_moe.KDAMLAMoEDecodeModel(CFG, params=f32(params))
    tokens = np.full((1, 48), 95, np.int32)
    tokens[0, :length] = np.random.default_rng(length).integers(0, 96, length)
    logits, rows, counters, state = jax.jit(model.prefill)(
        model.params, tokens, length)
    want = np.asarray(ref.logits(CFG, SEED, tokens[0]))
    np.testing.assert_allclose(logits, want[length - 1], atol=2e-5)
    assert rows.shape == (N_MLA, 48, ROW)
    assert state["s"].shape == (N_KDA, 4, 16, 16)
    assert state["tail"].shape == (N_KDA, 3, 192)
    assert np.all(np.isfinite(state["s"]))
    c = dict(zip(model.counters, np.asarray(counters)))
    assert c["moe.assignments"] == N_MOE * length * 4 and c["moe.dropped"] == 0
    assert c["kda.tokens"] == N_KDA * length
    assert 0 < c["moe.held"] <= c["moe.assignments"]
    assert c["moe.held"] <= 4 * c["moe.group_hit"] <= 4 * N_MOE * length


def _in_pieces(model, tokens, length, piece, dirty):
    """``prefill_from`` over tokens (1, n x piece) a piece at a time, the
    state and the rows carried as the engine carries them (``dirty``: NaN in
    both before the first piece). (logits, rows, state) as ``prefill``."""
    total = tokens.shape[1]
    fill = np.nan if dirty else 0.0
    pool = jnp.full((model.paged_layers, total) + model.cache_row, fill,
                    model.cache_dtype)
    state = {name: jnp.full(shape, fill, dt)
             for name, (shape, dt) in model.state.items()}

    @jax.jit
    def one(tokens, start, pool, state):
        return model.prefill_from(model.params, tokens, start, length,
                                  lambda layer: pool[layer], state)

    for start in range(0, total, piece):
        if start >= length:
            break
        logits, rows, _, state = one(tokens[:, start:start + piece],
                                     jnp.int32(start), pool, state)
        pool = pool.at[:, start:start + piece].set(rows)
    return logits, pool, state


@pytest.mark.parametrize("piece,length,dirty", [
    (16, 43, True),       # an odd last piece: 11 of its 16 positions live
    (16, 43, False),
    (16, 34, True),       # ... 2 live: the convolution's tail spans pieces
    (48, 37, True),       # one piece
    (16, 48, True),       # the last piece full
])
def test_a_prompt_in_pieces_is_the_prompt_whole(piece, length, dirty, params):
    """Logits, every paged row and the whole state: a KDA layer carries on
    from the state and tail the piece before left, an MLA layer from the
    pool's rows — the numbers of ``prefill`` over the whole prompt, whatever
    (NaN) the slot's state and the pool held before the first piece."""
    model = kda_mla_moe.KDAMLAMoEDecodeModel(CFG, params=f32(params))
    tokens = np.zeros((1, 48), np.int32)
    tokens[0, :length] = np.random.default_rng(length).integers(0, 96, length)
    want_logits, want_rows, _, want_state = jax.jit(model.prefill)(
        model.params, tokens, length)
    logits, rows, state = _in_pieces(model, jnp.asarray(tokens), length,
                                     piece, dirty)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(rows[:, :length], want_rows[:, :length],
                               atol=2e-5)
    np.testing.assert_allclose(state["s"], want_state["s"], atol=2e-5)
    np.testing.assert_allclose(state["tail"], want_state["tail"], atol=2e-5)
    np.testing.assert_allclose(
        logits, np.asarray(ref.logits(CFG, SEED, tokens[0]))[length - 1],
        atol=2e-5)


def test_a_kda_layer_reads_nothing_of_the_pool(params):
    """``prefill_from`` asks ``prior`` for the MLA layers' rows alone: 2
    calls for 7 layers, by paged layer 0 and 1."""
    model = kda_mla_moe.KDAMLAMoEDecodeModel(CFG, params=f32(params))
    asked = []

    def prior(layer):
        asked.append(layer)
        return jnp.zeros((48, ROW), jnp.float32)

    jax.eval_shape(lambda t, s: model.prefill_from(
        model.params, t, jnp.int32(16), 40, prior, s),
        jnp.zeros((1, 16), jnp.int32),
        {name: jnp.zeros(shape, jnp.float32)
         for name, (shape, _) in model.state.items()})
    assert asked == [0, 1]


# -- the engine's two programs ----------------------------------------------------

@pytest.fixture
def seen(monkeypatch):
    """The logits every program of the test sampled from, in call order."""
    seen = []
    sample = transformer.sample_token

    def spy(logits, rng, temperature):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return sample(logits, rng, temperature)

    monkeypatch.setattr(transformer, "sample_token", spy)
    return seen


def _generate(engine, prompts, new_tokens, seen, slots=None):
    """Greedy generation through the engine's own programs, keeping the
    logits every program sampled from. ``slots[i]`` is prompt i's slot
    (default i). Returns (tokens, logits) per prompt."""
    slots = list(range(len(prompts))) if slots is None else slots
    out = [([], []) for _ in prompts]
    last = np.zeros((engine.slots,), np.int32)
    for i, prompt in enumerate(prompts):
        bucket = engine.bucket_for(len(prompt))
        engine.pool.alloc(("gen", i), bucket // PAGE)
        tok = engine.prefill(prompt, engine.pool.table(("gen", i)),
                             slot=slots[i])
        jax.effects_barrier()
        out[i][0].append(tok)
        out[i][1].append(seen.pop()[0])
        seen.clear()        # the pieces before the last sampled garbage
        last[slots[i]] = tok
    for step in range(1, new_tokens):
        positions = np.zeros((engine.slots,), np.int32)
        lengths = np.zeros((engine.slots,), np.int32)
        tables = np.full((engine.slots, engine.max_pages), SCRATCH_PAGE,
                         np.int32)
        for i, prompt in enumerate(prompts):
            pos = len(prompt) + step - 1
            while len(engine.pool.table(("gen", i))) * PAGE <= pos:
                engine.pool.alloc(("gen", i), 1)
            table = engine.pool.table(("gen", i))
            tables[slots[i], :len(table)] = table
            positions[slots[i]], lengths[slots[i]] = pos, pos + 1
        toks = engine.step(last, positions, tables, lengths,
                           np.zeros((engine.slots,), np.float32))
        jax.effects_barrier()
        logits = seen.pop()
        for i in range(len(prompts)):
            out[i][0].append(int(toks[slots[i]]))
            out[i][1].append(logits[slots[i]])
            last[slots[i]] = toks[slots[i]]
    for i in range(len(prompts)):
        engine.pool.free(("gen", i))
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_engine_prefill_then_decode_through_state_and_pages(
        dtype, params, seen, monkeypatch):
    """Prompts in pieces of 16 and then 30 decode steps — the latent layers
    walk four pages, the KDA state is carried from the pieces into the steps
    — through ``DecodeEngine``'s own two programs (kernels interpreted),
    against the reference's ONE full forward over prompt + generated ids,
    logits.

    float32: 2e-4 of logits of size ~0.5 (float32 rounding through 7
    layers: the chunked solve, the absorbed attention; a dropped gate, decay
    or tail moves them by 1e-2 and more). bfloat16: 0.05 absolute — the
    other models' tests have 0.03; this one's logits are 2-3 times theirs
    (an embedding row is N(0,1); every KDA layer added a normed 0.6 when the
    tolerance was set, 0.08 since its output projection is drawn at an eighth)."""
    assert _engine(params, dtype).stats()["delta_rule"] == "xla"
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")   # interpreted kernels
    engine = _engine(params, dtype)
    # the pieces go through the kda_prefill kernel, the steps through
    # kda_decode
    assert engine.model.delta_rule() == "kda_prefill"
    assert engine.stats()["delta_rule"] == "kda_prefill"
    assert engine.kv.shape == (25, N_MLA, PAGE, ROW) and engine.kv.dtype == dtype
    assert engine.paged_layers == N_MLA and engine.prefill_piece == PIECE
    stats = engine.stats()
    assert stats["state"] == {
        "s": {"shape": [SLOTS + 1, N_KDA, 4, 16, 16], "dtype": "float32"},
        "tail": {"shape": [SLOTS + 1, N_KDA, 3, 192], "dtype": dtype}}
    item = 4 if dtype == "float32" else 2
    assert stats["state_bytes"] == N_KDA * (4 * 16 * 16 * 4 + 3 * 192 * item)
    assert stats["moe_row_tile"]["step"] == moe.layer_row_tile(
        SLOTS, 4, 32, engine.model.cache_dtype)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (13, 37)]
    new = 31
    out = _generate(engine, prompts, new, seen)
    tol = 2e-4 if dtype == "float32" else 0.05
    worst, not_first = 0.0, 0
    for prompt, (tokens, logits) in zip(prompts, out):
        seq = np.concatenate([prompt, tokens[:-1]])
        want = np.asarray(ref.logits(CFG, SEED, seq))[len(prompt) - 1:]
        got = np.stack(logits)
        assert got.shape == want.shape == (new, 96)
        worst = max(worst, float(np.abs(got - want).max()))
        not_first += int((want.argmax(1) != np.array(tokens)).sum())
    print(f"{dtype}: widest logit difference {worst:.3g}; {not_first} of "
          f"{2 * new} served tokens are not the reference's first")
    assert worst < tol
    if dtype == "float32":
        assert not_first == 0
    # the counters came back with the tokens
    c = engine.last_counters
    assert c["moe.assignments"] == N_MOE * SLOTS * 4 and c["moe.dropped"] == 0
    assert c["kda.tokens"] == N_KDA * SLOTS
    assert c["moe.group_hit"] <= N_MOE * SLOTS


@pytest.mark.parametrize("before", ["another_request", "a_step_launched_ahead"])
def test_a_reused_slot_gives_the_request_what_it_gets_alone(
        before, params, seen):
    """A state is not addressed through a page table, so nothing masks what
    a slot's last owner left but the first piece's ``start == 0``: a second
    request in a slot that another request used — and in a slot that a step
    launched ahead wrote AFTER its stream had ended — reads the logits it
    reads in a fresh engine."""
    rng = np.random.default_rng(1)
    first, second = (rng.integers(0, 96, n).astype(np.int32) for n in (19, 5))
    alone = _generate(_engine(params), [second], 12, seen, slots=[1])
    engine = _engine(params)
    _generate(engine, [first], 6, seen, slots=[1])
    if before == "a_step_launched_ahead":
        tables = np.full((SLOTS, engine.max_pages), SCRATCH_PAGE, np.int32)
        engine.step(np.array([0, 5], np.int32), np.array([0, 24], np.int32),
                    tables, np.array([0, 25], np.int32),
                    np.zeros((SLOTS,), np.float32))
        jax.effects_barrier()
        seen.clear()
    assert np.abs(np.asarray(engine.state["s"][1])).max() > 0
    again = _generate(engine, [second], 12, seen, slots=[1])
    assert again[0][0] == alone[0][0]
    np.testing.assert_allclose(np.stack(again[0][1]), np.stack(alone[0][1]),
                               atol=1e-6)


def test_an_idle_slots_state_is_not_touched_by_the_step(params, seen,
                                                        monkeypatch):
    """A slot whose prompt is still going in rides the step idle: its state
    and tails hold the pieces so far and the step works on the scratch
    slot's."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")
    engine = _engine(params)
    rng = np.random.default_rng(2)
    _generate(engine, [rng.integers(0, 96, 9).astype(np.int32)], 2, seen,
              slots=[0])
    before = {k: np.asarray(v) for k, v in engine.state.items()}
    _generate(engine, [rng.integers(0, 96, 12).astype(np.int32)], 5, seen,
              slots=[1])
    for name, was in before.items():
        after = np.asarray(engine.state[name])
        np.testing.assert_array_equal(after[0], was[0])
        assert np.abs(after[1] - was[1]).max() > 0


# -- the shares ---------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_reference():
    """512 experts over 16 chips and the vocabulary an eighth to a chip, at
    32 experts over eight shares of 4 (two shares a routing group) and 96
    rows over four: the eight shares' routed parts plus the shared expert
    ONCE equal the uncut reference layer (float32, 1e-5); every live pair
    falls on exactly one share; a token reaches a share only if it kept the
    share's group, so ``group_hit`` summed over the shares is 2 shares a
    kept group a token; a vocabulary slice's logits are the uncut
    head's columns; a slice's embedding rows are the uncut table's. And the mistake
    this guards against does not add up: the chosen weights renormalised
    over the held experts only."""
    layer, tokens = 4, 24
    i = CFG["layers"].index(layer)
    h = 0.7 * jax.random.normal(jax.random.PRNGKey(2), (tokens, 64),
                                jnp.float32)
    live = jnp.ones((tokens,), bool)
    uncut = dict(CFG, experts_first=0, experts_held=32)
    w = ref.layer_weights(uncut, SEED, layer)
    want = ref.expert_layer(uncut, w, h, "f32")
    shared = ref.gated_mlp(h, w["shared_gate_w"], w["shared_up_w"],
                           w["shared_down_w"], "f32")
    j = kda_mla_moe.layer_kinds(CFG)[2].index(i)
    routed, renormed, held_pairs, hits = 0.0, 0.0, 0, 0
    # ONE uncut tree; a share's experts are its four of every layer's 32
    # (that a share's own ``init_params`` draws those very experts is
    # ``test_program_and_reference_make_the_same_weights``, at first = 8)
    p = f32(kda_mla_moe.init_params(uncut, SEED))
    lp = {k: p["layers"][i][k] for k in kda_mla_moe.ROUTED}
    for share in range(8):
        experts = {name: a.reshape((N_MOE, 32) + a.shape[1:])[
            :, 4 * share:4 * share + 4].reshape((-1,) + a.shape[1:])
            for name, a in p["experts"].items()}
        y, c = moe.expert_layer(
            h, lp, experts, live, first=4 * share, held=4, k=4, scale=2.5,
            groups=4, groups_kept=2, offset=j * 4)
        c = dict(zip(moe.GROUP_COUNTERS, np.asarray(c)))
        assert c["dropped"] == 0 and c["assignments"] == tokens * 4
        assert c["held"] <= 4 * c["group_hit"]
        held_pairs += c["held"]
        hits += c["group_hit"]
        routed = routed + (y - shared)
        chosen, gates, _ = moe.route_grouped(h, lp["router_w"],
                                             lp["router_b"], 4, 2.5, 4, 2)
        np.testing.assert_allclose(gates.sum(-1), 2.5, atol=1e-5)
        on = (chosen >= 4 * share) & (chosen < 4 * share + 4)
        wrong = 2.5 * gates / jnp.maximum(
            jnp.sum(jnp.where(on, gates, 0), -1, keepdims=True), 1e-9)
        renormed = renormed + moe.held_experts(
            h, chosen, wrong, live, experts["gate_w"], experts["up_w"],
            experts["down_w"], 4 * share, 4, j * 4, 32)[0]
    assert held_pairs == tokens * 4       # every pair on exactly one share
    assert hits == tokens * 2 * 2         # 2 kept groups x 2 shares a group
    np.testing.assert_allclose(routed + shared, want, atol=1e-5)
    assert float(jnp.abs(renormed + shared - want).max()) > 1e-3
    # the vocabulary: slices 1 and 3 of four are the uncut table's rows
    head = ref.vocab_weights(CFG, SEED, "head")
    gain = ref._draw(ref.base_key(SEED), "final_norm", (64,))
    whole = ref._head(h, gain, head, eps=1e-6, precision="f32")
    for piece in (1, 3):
        cfg = dict(CFG, vocab_first=24 * piece, vocab_size=24)
        p = f32(kda_mla_moe.init_params(cfg, SEED))
        rows = slice(24 * piece, 24 * piece + 24)
        np.testing.assert_allclose(
            kda_mla_moe.KDAMLAMoEDecodeModel(cfg, params=p)._head(p, h),
            whole[:, rows], atol=1e-5)
        np.testing.assert_array_equal(
            p["embed"], ref.vocab_weights(CFG, SEED, "embed")[rows])


def test_the_fp8_control_reads_apart_from_the_reference():
    """The reference's own lower precision moves the logits by more than the
    bfloat16 program does: what the cell's limits are set between."""
    tokens = np.random.default_rng(3).integers(0, 96, 40).astype(np.int32)
    exact = np.asarray(ref.logits(CFG, SEED, tokens))
    lower = np.asarray(ref.logits(CFG, SEED, tokens, precision="fp8"))
    assert 0.02 < np.abs(lower - exact).max() < 2.0


# -- the scheduler --------------------------------------------------------------------

@pytest.fixture
def scheduler(params):
    sched = DecodeScheduler(_engine(params), max_queue=8, default_timeout=60.0)
    yield sched
    sched.close()


def _baseline(sched):
    """No page, no slot and nothing in flight: what a finished stream has to
    leave behind."""
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        s = sched.stats()
        if not (s["active"] or s["queued"] or s["engine"]["pool"]["used"]):
            return all(g is None for g in sched._slots)
        time.sleep(0.01)
    return False


@pytest.mark.parametrize("ending", ["finish", "cancel", "cancel_in_prefill"])
def test_slot_state_and_pages_return_to_baseline(ending, scheduler):
    """A stream that finishes, one cancelled while it decodes and one
    cancelled in MID-PREFILL (two of its three pieces in: its state holds
    them) leave no page and no slot behind, and the slot then serves the
    next request as a fresh engine would."""
    prompt = np.arange(5, 45, dtype=np.int32)          # three pieces
    if ending == "finish":
        assert len(list(scheduler.generate(prompt, max_new_tokens=9))) == 9
    elif ending == "cancel":
        stream = scheduler.generate(prompt, max_new_tokens=40)
        assert [next(stream) for _ in range(3)]
        stream.close()
    else:
        assert len(list(scheduler.generate(prompt[:7], max_new_tokens=2))) == 2
        launch = scheduler.engine.launch_prefill
        handle = []

        def cancel_after_two(tokens, page_ids, **kw):
            out = launch(tokens, page_ids, **kw)
            if kw.get("start") == PIECE and handle:
                handle[0].cancel()
            return out

        scheduler.engine.launch_prefill = cancel_after_two
        try:
            handle.append(scheduler.submit(prompt, max_new_tokens=8))
            events = []
            while not events or events[-1][0] == "token":
                events.append(handle[0].get(timeout=60))
        finally:
            scheduler.engine.launch_prefill = launch
        assert events[-1][0] != "token" and len(events) <= 2
    assert _baseline(scheduler)
    again = list(scheduler.generate(prompt[:21], max_new_tokens=12))
    fresh = DecodeScheduler(_engine(scheduler.engine.model.params),
                            max_queue=8, default_timeout=60.0)
    try:
        assert again == list(fresh.generate(prompt[:21], max_new_tokens=12))
    finally:
        fresh.close()
    assert _baseline(scheduler)


def test_a_neighbour_prefilling_in_pieces_does_not_move_a_streams_tokens(
        scheduler):
    """The engine feeds this model pieces of its smallest bucket (16): a
    40-token prompt goes in three, one a turn, in front of the steps of the
    stream that is decoding beside it — whose tokens, and the prompt's own,
    are what each gets alone: the pieces' state lies in the prompt's slot
    and the steps between them work on the scratch slot's. The spans carry
    what the program counted: ``kda.tokens`` (live tokens x 5 KDA layers)
    and ``moe.group_hit``."""
    engine = scheduler.engine
    assert engine.prefill_piece == PIECE and engine.buckets == [PIECE]
    assert engine.stats()["max_prompt"] == 48
    first = np.arange(7, 12, dtype=np.int32)
    second = np.arange(40, 80, dtype=np.int32)
    alone = [list(scheduler.generate(p, max_new_tokens=n))
             for p, n in ((first, 30), (second, 10))]
    assert engine.stats()["num_programs"] == 2
    before = scheduler.stats()
    obs.enable()
    try:
        obs.trace.drain()
        a = scheduler.submit(first, max_new_tokens=30)
        got_a = [a.get(timeout=60) for _ in range(3)]    # A is decoding
        b = scheduler.submit(second, max_new_tokens=10)
        got_b = []
        for got, h in ((got_b, b), (got_a, a)):
            while not got or got[-1][0] == "token":
                got.append(h.get(timeout=60))
        assert _baseline(scheduler)
        spans = obs.trace.drain()
    finally:
        obs.disable()
    assert [ev[1] for ev in got_a[:-1]] == alone[0]
    assert [ev[1] for ev in got_b[:-1]] == alone[1]
    st = scheduler.stats()
    assert st["admitted"] - before["admitted"] == 2
    assert st["prefill_pieces"] - before["prefill_pieces"] == 4
    assert engine.stats()["num_programs"] == 2
    calls = sorted((s for s in spans
                    if s["name"] in ("decode.prefill", "decode.step")),
                   key=lambda s: s["ts"])
    pieces = [s["args"] for s in calls if s["name"] == "decode.prefill"]
    assert [(p["prompt_len"], p["start"], p["pieces"], p["bucket"])
            for p in pieces] == [(5, 0, 1, 16), (40, 0, 3, 16),
                                 (40, 16, 3, 16), (40, 32, 3, 16)]
    assert all(p["moe.dropped"] == 0 for p in pieces)
    # a piece's live positions x the 5 KDA layers, x 6 expert layers x 4
    assert [p["kda.tokens"] for p in pieces] == [
        N_KDA * n for n in (5, 16, 16, 8)]
    assert [p["moe.assignments"] for p in pieces] == [
        N_MOE * 4 * n for n in (5, 16, 16, 8)]
    assert all(0 <= p["moe.group_hit"] <= p["moe.assignments"] // 4
               and p["moe.held"] <= 4 * p["moe.group_hit"] for p in pieces)
    steps = [s["args"] for s in calls if s["name"] == "decode.step"]
    assert all(s["kda.tokens"] == N_KDA * s["active"] for s in steps)
    names = [s["name"] for s in calls]
    i = [k for k, n in enumerate(names) if n == "decode.prefill"]
    assert "decode.step" in names[i[1] + 1:i[2]]
    counted = scheduler.stats()["counted"]
    assert counted["kda.tokens"] > 0 and "moe.group_hit" in counted


def test_the_step_spans_carry_what_the_caches_cost(scheduler):
    """``cache.paged_bytes`` (rows read by the step's live contexts over the
    TWO latent layers), ``cache.state_bytes`` (the KDA state and tails of
    its live slots, read and written) on ``decode.step``; the gauges beside
    ``cache_row_bytes``; the counters of the same names as the spans'."""
    engine = scheduler.engine
    obs.enable()
    try:
        obs.trace.drain()
        was = {name: obs.metrics.registry.counter(name).value
               for name in ("kda.tokens", "moe.group_hit")}
        prompt = np.arange(1, 12, dtype=np.int32)
        assert len(list(scheduler.generate(prompt, max_new_tokens=5))) == 5
        assert _baseline(scheduler)
        spans = obs.trace.drain()
        gauges = {name: obs.metrics.registry.gauge(name).value for name in
                  ("decode.state_bytes", "decode.paged_layers",
                   "decode.cache_row_bytes")}
        now = {name: obs.metrics.registry.counter(name).value - w
               for name, w in was.items()}
    finally:
        obs.disable()
    steps = [s for s in spans if s["name"] == "decode.step"]
    assert len(steps) == 4
    row, state = engine.cache_row_bytes, engine.state_bytes
    assert (row, state) == (ROW * 4, N_KDA * (4 * 16 * 16 * 4 + 3 * 192 * 4))
    for i, s in enumerate(steps):        # contexts 12, 13, 14, 15
        assert s["args"]["cache.paged_bytes"] == (12 + i) * row * N_MLA
        assert s["args"]["cache.state_bytes"] == 2 * state
        assert s["args"]["kda.tokens"] == N_KDA
        assert s["args"]["moe.dropped"] == 0
    assert gauges == {"decode.state_bytes": state,
                      "decode.paged_layers": N_MLA,
                      "decode.cache_row_bytes": row}
    # the prefill's 11 positions and the four steps'
    assert now["kda.tokens"] == N_KDA * (11 + 4)
    assert 0 <= now["moe.group_hit"] <= N_MOE * (11 + 4)
