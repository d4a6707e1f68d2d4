"""The Mamba-2 + grouped-KV + relu^2-experts decoder (``models/ssm_moe.py``,
``ops/mamba2.py``, the two-product experts of ``ops/moe.py``) and the engine's
per-slot state (``serve/decode.py``) against the plain reference
``benchmark/reference_ssm_moe.py`` — the repo's one copy of the equations —
at tiny sizes on the CPU, Pallas kernels interpreted.

The mathematics is checked in float32 (the same bodies run on a float32
tree), where the program must agree with the reference to rounding; the
bfloat16 run is then held to a bfloat16-sized tolerance.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_ssm_moe as ref
from mxnet_tpu import obs
from mxnet_tpu.models import ssm_moe, transformer
from mxnet_tpu.ops import gated_delta, gqa_attention, mamba2, moe
from mxnet_tpu.serve import DecodeEngine, DecodeScheduler
from mxnet_tpu.serve.engine import DeadlineExceeded
from mxnet_tpu.serve.kvcache import SCRATCH_PAGE

pytestmark = pytest.mark.decode

SEED = 3000000019      # over 2**31, as the driver's are
CFG = {
    "vocab_size": 96, "vocab_first": 0, "hidden_size": 64,
    "pattern": "MEM*EME*", "num_heads": 8, "num_kv_heads": 2, "head_dim": 16,
    "ssm_heads": 4, "ssm_head_dim": 8, "ssm_groups": 2, "ssm_state": 16,
    "conv_width": 4, "chunk_size": 8, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 0.0001, "expert_width": 24,
    "shared_width": 48, "router_experts": 8, "experts_first": 2,
    "experts_held": 4, "experts_per_token": 3, "routed_scale": 2.5,
    "rms_eps": 1e-5, "max_length": 64}
PAGE, SLOTS = 8, 2
N_M, N_A, N_E = 3, 2, 3          # layers of each kind in the pattern
CHANNELS = 4 * 8 + 2 * 2 * 16    # what the convolution sees: x, B, C


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def params():
    return ssm_moe.init_params(CFG, SEED)


def _engine(params, dtype="float32", slots=SLOTS):
    model = ssm_moe.SSMMoEDecodeModel(
        CFG, params=f32(params) if dtype == "float32" else params)
    return DecodeEngine(model, slots=slots, page_size=PAGE, num_pages=17,
                        prompt_buckets=[16, 32])


# -- the configuration and the weights ----------------------------------------

PUBLISHED = {
    "chunk_size": 128, "conv_kernel": 4, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "layer_norm_epsilon": 1e-5, "mamba_head_dim": 64, "mamba_num_heads": 64,
    "max_position_embeddings": 6144, "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 32, "n_shared_experts": 1,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 18, "num_key_value_heads": 2,
    "routed_scaling_factor": 2.5, "ssm_state_size": 128,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "vocab_size": 32768}


def test_config_from_the_published_keys():
    cfg = ssm_moe.config_from_hf(PUBLISHED, router_experts=128)
    assert (cfg["experts_held"], cfg["router_experts"]) == (32, 128)
    # the first 18 of the published 52: 8 Mamba-2, 8 expert, 2 attention
    assert cfg["pattern"] == "MEMEM*EMEMEM*EMEME"
    kinds = ssm_moe.layer_kinds(cfg)
    assert [len(kinds[k]) for k in "ME*"] == [8, 8, 2]
    assert kinds["*"] == [5, 12]
    whole = ssm_moe.config_from_hf(dict(PUBLISHED, num_hidden_layers=52))
    assert [whole["pattern"].count(k) for k in "ME*"] == [23, 23, 6]
    model = ssm_moe.SSMMoEDecodeModel(
        cfg, params=jax.eval_shape(lambda: ssm_moe.init_params(cfg, 0)))
    # a position's k and v of both cached heads, flat: 1 KB; 2 of 18 paged
    assert model.cache_row == (512,) and model.paged_layers == 2
    # 64 x 64 x 128 float32 a layer as (8, 128, 512), 3 inputs of 6144 channels
    assert model.state["s"] == ((8, 8, 128, 512), jnp.float32)
    assert model.state["tail"] == ((8, 144, 128), jnp.bfloat16)   # 3 x 6144
    # the issue's arithmetic, by leaf
    count = {k: sum(int(np.prod(a.shape))
                    for a in jax.tree_util.tree_leaves(v))
             for k, v in model.params.items()}
    assert count["mamba"] == 8 * 38_744_896
    assert count["attn"] == 2 * (23_396_352 + 2688)
    assert count["moe"] == 8 * (344_064 + 128 + 19_955_712 + 2688)
    # the held experts are STORED 3072 x 2048 (whole tiles of 512), zeros
    # behind the published 2688 x 1856
    assert model.params["experts"]["up_w"].shape == (256, 3072, 2048)
    assert model.params["experts"]["down_w"].shape == (256, 2048, 3072)
    assert count["experts"] == 8 * 32 * 2 * 3072 * 2048
    assert count["embed"] + count["head"] == 2 * 32768 * 2688
    published = sum(count.values()) - 8 * 32 * 2 * (3072 * 2048 - 2688 * 1856)
    assert published == 3_249_672_576
    with pytest.raises(NotImplementedError, match="pattern"):
        ssm_moe.config_from_hf(dict(PUBLISHED,
                                    hybrid_override_pattern="M-M*"))


def test_the_cells_configuration_file_gives_its_own_model_block():
    """``benchmark/configs/nemotron-3-nano-30b-a3b.json``: the catalog's keys
    at the top level (the four ``reduced`` ones changed, the pattern string
    whole) are what ``config_from_hf`` makes the ``model`` block from."""
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        file = json.load(f)
    published = file["published"]
    changed = {k for k in published if file[k] != published[k]}
    assert changed == set(file["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    made = ssm_moe.config_from_hf(
        {k: file[k] for k in published},
        router_experts=published["n_routed_experts"])
    assert dict(made, kind="ssm_moe_lm") == file["model"]
    assert {k: PUBLISHED[k] for k in PUBLISHED} == {k: file[k]
                                                    for k in PUBLISHED}


def test_program_and_reference_make_the_same_weights(params):
    assert params["experts"]["up_w"].dtype == jnp.bfloat16
    assert "gate_w" not in params["experts"]
    assert params["mamba"]["A_log"].dtype == jnp.float32
    kinds = ssm_moe.layer_kinds(CFG)
    held = CFG["experts_held"]

    def same(got, *want):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.concatenate(want, axis=-1))

    for layer, kind in enumerate(CFG["pattern"]):
        w = ref.layer_weights(CFG, SEED, layer)
        j = kinds[kind].index(layer)
        p = {k: v[j] for k, v in params[ssm_moe.TREES[kind]].items()}
        same(p["norm"], w["norm"])
        if kind == "*":
            same(p["q_w"], w["q_w"])
            same(p["kv_w"], w["k_w"], w["v_w"])
            same(p["o_w"], w["o_w"])
        elif kind == "M":
            for name in ssm_moe.MAMBA:
                same(p[name], w[name])
            # u in [1, 16]; delta in [0.001, 0.1] through the inverse
            # softplus; D = 1; the gains ~ 1
            assert 0 <= float(p["A_log"].min()) <= float(p["A_log"].max()) < 2.78
            delta = np.log1p(np.exp(np.asarray(p["dt_bias"], np.float64)))
            assert 0.000999 < delta.min() <= delta.max() < 0.1001
            assert (np.asarray(p["D"]) == 1).all()
            assert abs(float(p["gnorm"].astype(jnp.float32).mean()) - 1) < 0.05
        else:
            for name in ssm_moe.ROUTED:
                same(p[name], w[name])
            for name in ("up_w", "down_w"):
                same(params["experts"][name][j * held:(j + 1) * held],
                     w["experts_" + name])     # under 128 wide: no pad
    same(params["embed"], ref.vocab_weights(CFG, SEED, "embed"))
    same(params["head"], ref.vocab_weights(CFG, SEED, "head"))


# -- the recurrence and the convolution -----------------------------------------

def _ssm_inputs(s, h=4, p=8, g=2, n=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(s, h, p)).astype(np.float32)
    # steps from a slow leak to forgetting nearly everything in a token
    delta = np.exp(rng.uniform(-6, 1, size=(s, h))).astype(np.float32)
    a = -np.exp(rng.uniform(0, 2.7, size=(h,))).astype(np.float32)
    b = rng.normal(size=(s, g, n)).astype(np.float32)
    c = rng.normal(size=(s, g, n)).astype(np.float32)
    return x, delta, a, b, c


def _by_the_equations(x, delta, a, b, c, state):
    """The issue's two assignments, token by token, in float64."""
    state = np.array(state, np.float64)
    rep = x.shape[1] // b.shape[1]
    out = []
    for t in range(len(x)):
        bt, ct = np.repeat(b[t], rep, 0), np.repeat(c[t], rep, 0)
        decay = np.exp(a.astype(np.float64) * delta[t])
        state = (state * decay[:, None, None]
                 + (delta[t][:, None] * x[t])[:, :, None] * bt[:, None, :])
        out.append(np.einsum("hpn,hn->hp", state, ct))
    return np.stack(out), state


@pytest.mark.parametrize("length", [1, 31, 32, 75])
def test_chunked_scan_is_the_recurrence(length):
    """Outputs and final state, at lengths off the chunk, from a state that
    is not zero: the chunked form, the scanned recurrence and the equations
    written out in float64 agree to float32 rounding."""
    x, delta, a, b, c = _ssm_inputs(length)
    state = np.random.default_rng(9).normal(size=(4, 8, 16)).astype(np.float32)
    want_y, want_s = _by_the_equations(x, delta, a, b, c, state)
    y_r, s_r = mamba2.ssd_recurrent(x, delta, a, b, c, state)
    y_c, s_c = mamba2.ssd_chunked(x, delta, a, b, c, state, chunk=32)
    # float32 rounding of values up to ~10: relative 3e-5, absolute 5e-5
    close = dict(rtol=3e-5, atol=5e-5)
    for got_y, got_s in ((y_r, s_r), (y_c, s_c)):
        np.testing.assert_allclose(got_y, want_y, **close)
        np.testing.assert_allclose(got_s, want_s, **close)
    # from no state, as a prefill starts; and padding behind the prompt
    # (delta 0) leaves the state as the prompt's last token left it
    y0, s0 = mamba2.ssd_chunked(x, delta, a, b, c, chunk=16)
    want_y, want_s = _by_the_equations(x, delta, a, b, c, np.zeros_like(state))
    np.testing.assert_allclose(y0, want_y, **close)
    np.testing.assert_allclose(s0, want_s, **close)
    pad = 7
    padded = [np.concatenate([t, np.ones((pad,) + t.shape[1:], t.dtype)])
              for t in (x, b, c)]
    dead = np.concatenate([delta, np.zeros((pad, 4), np.float32)])
    _, s_masked = mamba2.ssd_chunked(padded[0], dead, a, padded[1], padded[2],
                                     chunk=16)
    np.testing.assert_allclose(s_masked, s0, **close)


def test_a_slots_layout_is_the_heads_states():
    state = np.random.default_rng(1).normal(size=(3, 4, 8, 16)).astype(
        np.float32)
    slots = mamba2.to_slots(state, 2)
    assert slots.shape == (3, 2, 16, 2 * 8)
    # group 1, state index 5, head 1 of the group (head 3), channel 6
    assert slots[2, 1, 5, 8 + 6] == state[2, 3, 6, 5]
    np.testing.assert_array_equal(mamba2.from_slots(slots, 4), state)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_one_token_ssm_update_in_place(impl):
    """The step: one token a slot against the states array of every slot
    and layer. A live slot's state of THAT layer is the recurrence's; the
    other layer's, an idle slot's and the scratch slot's rows of the other
    layers are untouched."""
    n, layers, h, g = 3, 2, 4, 2
    x, delta, a, b, c = _ssm_inputs(n, h=h, g=g)
    rng = np.random.default_rng(4)
    heads = rng.normal(size=(n + 1, layers, h, 8, 16)).astype(np.float32)
    states = np.asarray(mamba2.to_slots(heads, g))
    live = np.array([True, False, True])
    y, new = mamba2.ssm_step(jnp.asarray(states), 1, x, delta, a, b, c,
                             jnp.asarray(live), impl=impl, interpret=True)
    new = np.asarray(new)
    for i in range(n):
        if live[i]:
            want_y, want_s = _by_the_equations(
                x[i:i + 1], delta[i:i + 1], a, b[i:i + 1], c[i:i + 1],
                heads[i, 1])
            np.testing.assert_allclose(y[i], want_y[0], atol=1e-5)
            np.testing.assert_allclose(mamba2.from_slots(new[i, 1], h),
                                       want_s, atol=1e-5)
        else:
            np.testing.assert_array_equal(new[i], states[i])
    np.testing.assert_array_equal(new[:, 0], states[:, 0])


def test_convolution_with_bias_and_a_carried_tail(params):
    """``SiLU(conv(xBC) + b_c)`` as the model applies it: over a padded
    prompt, then one input at a time from the tail taken at the prompt's
    end, against the whole sequence's."""
    lp = {k: v[0] for k, v in f32(params)["mamba"].items()}
    rng = np.random.default_rng(5)
    x = rng.normal(size=(23, CHANNELS)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(len(x)):
        for j in range(4):        # the last row weighs the current input
            if t - 3 + j >= 0:
                want[t] += np.asarray(lp["conv_w"])[j] * x[t - 3 + j]
    want = np.asarray(jax.nn.silu(want + np.asarray(lp["conv_b"])))
    assert np.abs(np.asarray(lp["conv_b"])).max() > 0

    def split(conv):
        return np.concatenate([np.asarray(t).reshape(len(conv), -1) for t in
                               ssm_moe._mamba_heads(CFG, lp, conv)], axis=1)

    out, tail = gated_delta.causal_conv(
        np.concatenate([x[:9], np.full((7, CHANNELS), 99, np.float32)]),
        lp["conv_w"], length=9)
    np.testing.assert_allclose(split(out)[:9], want[:9], atol=1e-6)
    np.testing.assert_array_equal(tail, x[6:9])
    for t in range(9, 23):
        step, tail = gated_delta.causal_conv_step(x[t][None], tail[None],
                                                  lp["conv_w"])
        tail = tail[0]
        np.testing.assert_allclose(split(step)[0], want[t], atol=1e-6)


# -- the experts ----------------------------------------------------------------

def test_held_experts_stored_in_whole_tiles_are_the_same_function():
    """At sizes over a tile of 512 and off it (hidden 576 -> 1024, width 520
    -> 1024) the stored pad is zeros, the published block is the
    reference's, and the expert layer through the padded storage gives what
    the reference gives at the published sizes."""
    assert [ssm_moe.stored_width(n) for n in (2688, 1856, 24, 512)] == [
        3072, 2048, 24, 512]
    cfg = dict(CFG, hidden_size=576, expert_width=520, pattern="M*E",
               experts_held=2)
    params = f32(ssm_moe.init_params(cfg, SEED))
    p, w = params["experts"], ref.layer_weights(cfg, SEED, 2)
    assert p["up_w"].shape == (2, 1024, 1024) == p["down_w"].shape
    np.testing.assert_array_equal(p["up_w"][:, :576, :520], w["experts_up_w"])
    np.testing.assert_array_equal(p["down_w"][:, :520, :576],
                                  w["experts_down_w"])
    for a, rows, cols in ((p["up_w"], 576, 520), (p["down_w"], 520, 576)):
        assert not np.asarray(a[:, rows:]).any()
        assert not np.asarray(a[:, :, cols:]).any()
    h = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (24, 576), jnp.float32)
    lp = {k: v[0] for k, v in params["moe"].items() if k != "norm"}
    got, c = moe.expert_layer(h, lp, p, jnp.ones((24,), bool), first=2, held=2,
                              k=3, scale=2.5)
    assert got.shape == (24, 576)
    np.testing.assert_allclose(got, ref.expert_layer(cfg, w, h, "f32"),
                               atol=2e-5)
    assert int(c[moe.COUNTERS.index("dropped")]) == 0


def test_two_product_held_experts_are_a_dense_loop():
    """``relu(h W_u)^2 W_d`` for the held experts through the sorted, grouped
    path (no gate matrix handed over) against every expert over all tokens,
    masked; idle tokens add nothing and are not counted."""
    t, d, f, held, first, k = 40, 64, 24, 4, 2, 3
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    up = jnp.asarray(0.1 * rng.normal(size=(2 * held, d, f)), jnp.float32)
    down = jnp.asarray(0.1 * rng.normal(size=(2 * held, f, d)), jnp.float32)
    chosen = jnp.asarray(np.stack([rng.permutation(8)[:k] for _ in range(t)]),
                         jnp.int32)
    gates = jnp.asarray(rng.uniform(size=(t, k)), jnp.float32)
    live = jnp.asarray(rng.uniform(size=(t,)) < 0.8)
    y, c = moe.held_experts(h, chosen, gates, live, None, up, down, first,
                            held, offset=held)
    want = np.zeros((t, d), np.float32)
    for e in range(held):
        g = np.where(np.asarray(chosen) == first + e, gates, 0).sum(1)
        a = np.maximum(np.asarray(h) @ np.asarray(up[held + e]), 0) ** 2
        want += (g * np.asarray(live))[:, None] * (a @ np.asarray(down[held + e]))
    np.testing.assert_allclose(y, want, atol=2e-5)
    counted = dict(zip(moe.COUNTERS, np.asarray(c)))
    on_held = ((np.asarray(chosen) >= first) & (np.asarray(chosen) < first + held)
               & np.asarray(live)[:, None])
    assert counted["assignments"] == int(np.asarray(live).sum()) * k
    assert counted["held"] == on_held.sum() and counted["dropped"] == 0
    # the gated form is another function of the same leaves
    gated, _ = moe.held_experts(h, chosen, gates, live, up, up, down, first,
                                held, offset=held)
    assert float(jnp.abs(gated - y).max()) > 1e-3
    np.testing.assert_allclose(
        moe.relu2_mlp(h, up[0], down[0]),
        np.maximum(np.asarray(h) @ np.asarray(up[0]), 0) ** 2
        @ np.asarray(down[0]), atol=2e-5)


def test_the_shares_add_up_to_the_uncut_reference():
    """128 experts over four chips and the vocabulary a quarter to a chip, at
    8 experts and 96 rows: the four shares' routed parts plus the shared
    expert counted ONCE equal the uncut reference layer (float32, 1e-5); the
    four slices' logits side by side are the uncut head's; a slice's
    embedding rows are the uncut table's. And the mistakes this guards
    against do not: the shared expert counted per share, the chosen weights
    renormalised over the held experts only."""
    layer = 4                                    # the second expert layer
    number = CFG["pattern"][:layer].count("E")
    h = 0.7 * jax.random.normal(jax.random.PRNGKey(2), (24, 64), jnp.float32)
    live = jnp.ones((24,), bool)
    uncut = dict(CFG, experts_first=0, experts_held=8)
    w_ref = ref.layer_weights(uncut, SEED, layer)
    want = ref.expert_layer(uncut, w_ref, h, "f32")
    routed, whole, renormed, logits, embeds = 0.0, 0.0, 0.0, [], []
    for share in range(4):
        cfg = dict(CFG, experts_first=2 * share, experts_held=2,
                   vocab_first=24 * share, vocab_size=24)
        p = f32(ssm_moe.init_params(cfg, SEED))
        lp = {k: w[number] for k, w in p["moe"].items() if k != "norm"}
        assert "router_b" in lp and "shared_gate_w" not in lp
        chosen, gates = moe.route(h, lp["router_w"], lp["router_b"], 3, 2.5)
        np.testing.assert_allclose(gates.sum(-1), 2.5, atol=1e-5)
        ew = (None, p["experts"]["up_w"], p["experts"]["down_w"])
        y, c = moe.held_experts(h, chosen, gates, live, *ew, 2 * share, 2,
                                number * 2)
        assert int(c[moe.COUNTERS.index("dropped")]) == 0
        routed = routed + y
        whole = whole + moe.expert_layer(
            h, lp, p["experts"], live, first=2 * share, held=2, k=3,
            scale=2.5, offset=number * 2)[0]
        held = (chosen >= 2 * share) & (chosen < 2 * share + 2)
        wrong = 2.5 * gates / jnp.maximum(
            jnp.sum(jnp.where(held, gates, 0), -1, keepdims=True), 1e-9)
        renormed = renormed + moe.held_experts(
            h, chosen, wrong, live, *ew, 2 * share, 2, number * 2)[0]
        model = ssm_moe.SSMMoEDecodeModel(cfg, params=p)
        logits.append(model._head(p, h))
        embeds.append(p["embed"])
    shared = moe.relu2_mlp(h, lp["shared_up_w"], lp["shared_down_w"])
    np.testing.assert_allclose(routed + shared, want, atol=1e-5)
    np.testing.assert_allclose(whole - 3 * shared, want, atol=1e-5)
    assert float(jnp.abs(whole - want).max()) > 1e-3        # shared x 4
    assert float(jnp.abs(renormed + shared - want).max()) > 1e-3
    whole_vocab = dict(CFG, vocab_size=96)
    head = ref.vocab_weights(whole_vocab, SEED, "head")
    gain = ref._draw(CFG, ref.base_key(SEED), "final_norm", (64,))
    np.testing.assert_allclose(
        jnp.concatenate(logits, axis=-1),
        ref._head(h, gain, head, eps=1e-5, precision="f32"), atol=1e-5)
    np.testing.assert_array_equal(
        jnp.concatenate(embeds), ref.vocab_weights(whole_vocab, SEED, "embed"))


# -- grouped-KV attention ---------------------------------------------------------

def test_sixteen_query_heads_a_cached_head_in_bfloat16():
    """The published geometry of a group — 16 query heads on each of 2 cached
    heads of 128, a flat 512-value ``[k || v]`` row — through the interpreted
    paged kernel in bfloat16, against the plain gather."""
    n, kvh, g, d, pages, layers, page = 3, 2, 16, 128, 9, 2, 16
    rng = np.random.default_rng(6)
    pool = jnp.asarray(rng.normal(size=(pages, layers, page, 2 * kvh * d)),
                       jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(n, kvh, g, d)), jnp.bfloat16)
    table = jnp.asarray(rng.integers(1, pages, size=(n, 3)), jnp.int32)
    lengths = jnp.asarray([21, 0, 48], jnp.int32)
    scale = d ** -0.5
    want = gqa_attention._gqa_decode_xla(q, pool, 1, table, lengths, scale)
    got = gqa_attention.flash_gqa_decode_attention(
        q, pool, 1, table, lengths, scale, interpret=True)
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert diff[[0, 2]].max() < 2e-2 and np.isfinite(diff).all()
    # by hand, query head 1 x 16 + 5 of sequence 0: it reads cached head 1
    rows = np.asarray(pool[table[0], 1], np.float32).reshape(-1, 2, kvh, d)[:21]
    sc = rows[:, 0, 1] @ np.asarray(q[0, 1, 5], np.float32) * scale
    p = np.exp(sc - sc.max())
    np.testing.assert_allclose(np.asarray(want[0, 1, 5], np.float32),
                               (p / p.sum()) @ rows[:, 1, 1], atol=2e-2)


# -- the whole model through the engine --------------------------------------------

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
def test_engine_prefill_then_decode_through_pages_and_state(
        dtype, params, seen, monkeypatch):
    """Prefill (the chunked scan, the grouped flash forward, every layer
    under one ``lax.scan`` that switches on its kind) and then 26 decode
    steps (the one-token kernel on the per-slot state, the paged grouped
    kernel on the pool) through ``DecodeEngine``'s own two programs, against
    the reference's ONE full forward over prompt + generated ids, logits.

    float32: 2e-4 of logits of size ~0.2 (float32 rounding through 8
    layers; a dropped gate, norm, bias or scale moves them by 1e-2 and
    more). bfloat16: 0.05 absolute where the other two models' tests have
    0.03 (read: 0.036; the squared activation and the 2.5 on the routed sum
    widen what a rounded input costs); the reference with fp8 operands
    differs from itself by 0.058 on such a sequence."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")   # interpreted kernels
    engine = _engine(params, dtype)
    # 2 of 8 layers are paged; a row is k and v of the two cached heads
    assert engine.kv.shape == (17, N_A, PAGE, 64) and engine.kv.dtype == dtype
    assert engine.paged_layers == N_A
    stats = engine.stats()
    assert stats["state"] == {
        "s": {"shape": [SLOTS + 1, N_M, 2, 16, 16], "dtype": "float32"},
        "tail": {"shape": [SLOTS + 1, N_M, 3, CHANNELS], "dtype": dtype}}
    assert stats["state_bytes"] == N_M * (4 * 8 * 16 * 4 + 3 * CHANNELS * (
        4 if dtype == "float32" else 2))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (13, 22)]
    new = 27
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
    # the counters came back with the tokens: 3 expert layers x 2 slots x 3
    c = engine.last_counters
    assert c["moe.assignments"] == N_E * SLOTS * 3 and c["moe.dropped"] == 0


@pytest.mark.parametrize("before", ["another_request", "a_step_launched_ahead"])
def test_a_reused_slot_gives_the_request_what_it_gets_alone(
        before, params, seen):
    """State is not addressed by position, so nothing masks what a slot's
    last owner left: the prefill has to overwrite it. A second request in a
    slot that another request used — and in a slot that a step launched
    ahead wrote AFTER its stream had ended — reads the logits it reads in a
    fresh engine."""
    rng = np.random.default_rng(1)
    first, second = (rng.integers(0, 96, n).astype(np.int32) for n in (19, 11))
    alone = _generate(_engine(params), [second], 8, seen, slots=[1])
    engine = _engine(params)
    _generate(engine, [first], 6, seen, slots=[1])
    if before == "a_step_launched_ahead":
        # the stream has ended and its pages are freed; the step that was
        # already in flight for it still runs, on the scratch page
        tables = np.full((SLOTS, engine.max_pages), SCRATCH_PAGE, np.int32)
        engine.step(np.array([0, 5], np.int32), np.array([0, 24], np.int32),
                    tables, np.array([0, 25], np.int32),
                    np.zeros((SLOTS,), np.float32))
        jax.effects_barrier()
        seen.clear()
    dirty = np.asarray(engine.state["s"][1])
    assert np.abs(dirty).max() > 0
    again = _generate(engine, [second], 8, seen, slots=[1])
    assert again[0][0] == alone[0][0]
    np.testing.assert_allclose(np.stack(again[0][1]), np.stack(alone[0][1]),
                               atol=1e-6)


def test_an_idle_slots_state_is_not_touched_by_the_step(params, seen,
                                                        monkeypatch):
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")
    engine = _engine(params)
    rng = np.random.default_rng(2)
    _generate(engine, [rng.integers(0, 96, 9).astype(np.int32)], 2, seen,
              slots=[0])
    before = {k: np.asarray(v) for k, v in engine.state.items()}
    _generate(engine, [rng.integers(0, 96, 12).astype(np.int32)], 5, seen,
              slots=[1])
    for name, was in before.items():
        np.testing.assert_array_equal(np.asarray(engine.state[name])[0],
                                      was[0])
        assert np.abs(np.asarray(engine.state[name])[1] - was[1]).max() > 0


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


@pytest.mark.parametrize("ending", ["finish", "cancel", "deadline"])
def test_pages_and_slots_return_to_baseline(ending, scheduler, monkeypatch):
    prompt = np.arange(5, 25, dtype=np.int32)
    if ending == "finish":
        assert len(list(scheduler.generate(prompt, max_new_tokens=9))) == 9
    elif ending == "cancel":
        stream = scheduler.generate(prompt, max_new_tokens=40)
        assert [next(stream) for _ in range(3)]
        stream.close()
    else:
        # the programs are built first: a stream waits its deadline and 5 s
        # more for a token, and a cold engine on a busy host compiles longer
        assert len(list(scheduler.generate(prompt, max_new_tokens=2))) == 2
        # and a result takes 10 ms to read, so that 40 tokens cannot come
        # inside the 150 ms however fast the host is: the deadline ends the
        # stream, not its length
        read = scheduler.engine.read

        def slow_read(launched):
            time.sleep(0.01)
            return read(launched)

        with monkeypatch.context() as slowed, pytest.raises(DeadlineExceeded):
            slowed.setattr(scheduler.engine, "read", slow_read)
            for _ in scheduler.generate(prompt, max_new_tokens=40,
                                        deadline_ms=150.0):
                pass
    assert _baseline(scheduler)
    # and the slot serves the next request as a fresh engine would
    again = list(scheduler.generate(prompt[:11], max_new_tokens=6))
    fresh = DecodeScheduler(_engine(scheduler.engine.model.params),
                            max_queue=8, default_timeout=60.0)
    try:
        assert again == list(fresh.generate(prompt[:11], max_new_tokens=6))
    finally:
        fresh.close()
    assert _baseline(scheduler)


def test_many_streams_at_128_slots_each_get_what_they_get_alone(params):
    """The cell's width of the batch at a tiny model: 128 slots, 48 streams
    of different prompts and lengths at once through the scheduler, joining
    and leaving between steps. Every stream reads the tokens it reads with
    the engine to itself (float32: a slot's row of every matmul does not
    depend on its neighbours), steps were launched ahead of the reads, and
    pages, slots and the queue are back to baseline."""
    import threading

    slots = 128
    model = ssm_moe.SSMMoEDecodeModel(CFG, params=f32(params))
    engine = DecodeEngine(model, slots=slots, page_size=PAGE,
                          num_pages=slots * (CFG["max_length"] // PAGE) + 1,
                          prompt_buckets=[16, 32])
    assert engine.blank_step().shape == (slots + 1, 3 + engine.max_pages)
    assert engine.state["s"].shape[0] == slots + 1
    sched = DecodeScheduler(engine, max_queue=4 * slots, default_timeout=120.0)
    rng = np.random.default_rng(5)
    asks = [(rng.integers(0, 96, int(n)).astype(np.int32), int(new))
            for n, new in zip(rng.integers(3, 31, 48), rng.integers(3, 14, 48))]
    try:
        alone = [list(sched.generate(prompt, max_new_tokens=new))
                 for prompt, new in asks[:12]]
        assert _baseline(sched)
        together = [None] * len(asks)

        def call(i):
            prompt, new = asks[i]
            together[i] = list(sched.generate(prompt, max_new_tokens=new))

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(asks))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert [len(t) for t in together] == [new for _, new in asks]
        assert together[:12] == alone
        assert _baseline(sched)
        stats = sched.stats()
        assert stats["launched_ahead_share"] > 0.5
        assert stats["counted"]["moe.dropped"] == 0
    finally:
        sched.close()


def test_a_stream_ended_by_eos_leaves_a_dropped_step_and_a_clean_slot(params):
    """``eos_id`` is learned a step late: the step in flight for the ended
    stream still writes its slot's state. The request that takes the slot
    next gets the tokens it gets alone."""
    prompt = np.arange(3, 20, dtype=np.int32)
    probe = DecodeScheduler(_engine(params, slots=1), default_timeout=60.0)
    try:
        tokens = list(probe.generate(prompt, max_new_tokens=8))
        alone = list(probe.generate(prompt[:9], max_new_tokens=8))
    finally:
        probe.close()
    eos = next(t for i, t in enumerate(tokens) if t not in tokens[:i] and i)
    sched = DecodeScheduler(_engine(params, slots=1), default_timeout=60.0,
                            eos_id=eos)
    try:
        assert list(sched.generate(prompt, max_new_tokens=8)) == tokens[
            :tokens.index(eos) + 1]
        assert _baseline(sched)
        deadline = time.monotonic() + 10.0   # read a turn after the retire
        while (not sched.stats()["dropped_speculative"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert sched.stats()["dropped_speculative"] >= 1
        sched.eos_id = None
        assert list(sched.generate(prompt[:9], max_new_tokens=8)) == alone
    finally:
        sched.close()


def test_the_step_spans_carry_what_the_caches_cost(scheduler):
    """``cache.paged_bytes`` (rows read by the step's live contexts over the
    paged layers) and ``cache.state_bytes`` (state read and written by its
    live slots) on ``decode.step``; the gauges beside ``cache_row_bytes``."""
    engine = scheduler.engine
    obs.enable()
    try:
        obs.trace.drain()
        prompt = np.arange(1, 12, dtype=np.int32)
        assert len(list(scheduler.generate(prompt, max_new_tokens=5))) == 5
        assert _baseline(scheduler)
        spans = obs.trace.drain()
        gauges = {name: obs.metrics.registry.gauge(name).value for name in
                  ("decode.state_bytes", "decode.paged_layers",
                   "decode.cache_row_bytes")}
    finally:
        obs.disable()
    steps = [s for s in spans if s["name"] == "decode.step"]
    assert len(steps) == 4
    row, state = engine.cache_row_bytes, engine.state_bytes
    assert (row, state) == (64 * 4, N_M * (4 * 8 * 16 * 4 + 3 * CHANNELS * 4))
    for i, s in enumerate(steps):        # contexts 12, 13, 14, 15
        assert s["args"]["cache.paged_bytes"] == (12 + i) * row * N_A
        assert s["args"]["cache.state_bytes"] == 2 * state
        assert s["args"]["moe.dropped"] == 0
    assert gauges == {"decode.state_bytes": state, "decode.paged_layers": N_A,
                      "decode.cache_row_bytes": row}


def test_callers_in_a_process_that_died_end_the_run_with_an_error():
    """The cell's runner starts its callers in a child; a child that is gone
    when the window closes raises at once and does not hang the run."""
    import socket

    from benchmark.runners.serve_ssm_moe import ChildCallers

    with socket.socket() as listening:      # accepts nothing, answers nothing
        listening.bind(("127.0.0.1", 0))
        listening.listen()
        callers = ChildCallers(listening.getsockname()[1],
                               [{"prompt": [1, 2], "max_new_tokens": 2}], 1,
                               5.0)
        callers.start()
        callers._child.kill()
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="without its records"):
            callers.finish(5.0)
        assert time.monotonic() - t0 < 30.0


@pytest.mark.parametrize("name", ssm_moe.EXPERTS)
def test_an_expert_is_written_as_its_whole_stored_slice(name):
    """``stored_experts`` pads an expert to its stored size BEFORE it writes
    it into the one array: XLA:TPU leaves a buffer that a loop fills slice by
    slice uncleared (``AllocateBuffer``), also where a slice is written in
    part, and the device's old memory stayed behind the published size
    (PR 40, on the chip: NaN in the experts' weights). Held here on the
    traced program: every update of the array is a whole ``(1, D, F)``."""
    cfg = dict(CFG, hidden_size=640, expert_width=520)   # stored 1024 x 1024

    def updates(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dynamic_update_slice":
                yield tuple(v.aval.shape for v in eqn.invars[:2])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from updates(sub)

    traced = jax.make_jaxpr(lambda key: ssm_moe.stored_experts(
        cfg, name, key))(jax.random.PRNGKey(0))
    n = len(ssm_moe.layer_kinds(cfg)["E"]) * cfg["experts_held"]
    assert traced.out_avals[0].shape == (n, 1024, 1024)
    found = [u for u in updates(traced.jaxpr) if u[0] == (n, 1024, 1024)]
    assert found and all(update == (1, 1024, 1024) for _, update in found)
    w = np.asarray(ssm_moe.stored_experts(cfg, name, jax.random.PRNGKey(0)),
                   np.float32)
    d, f = (640, 520) if name == "experts_up_w" else (520, 640)
    assert w[:, :d, :f].any()
    assert not w[:, d:].any() and not w[:, :, f:].any()
