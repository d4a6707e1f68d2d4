"""The gated-delta + grouped-KV + routed-experts decoder (``models/gdn_moe.py``,
``ops/gated_delta.py``, ``ops/gqa_attention.py``, the softmax router of
``ops/moe.py``) and the engine's per-slot state (``serve/decode.py``) against
the plain reference ``benchmark/reference_gdn_moe.py`` — the repo's one copy
of the equations — at tiny sizes on the CPU, Pallas kernels interpreted.

The mathematics is checked in float32 (the same bodies run on a float32
tree), where the program must agree with the reference to rounding; the
bfloat16 run is then held to a bfloat16-sized tolerance.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_gdn_moe as ref
from mxnet_tpu import obs
from mxnet_tpu.models import gdn_moe, transformer
from mxnet_tpu.ops import gated_delta, gqa_attention, moe
from mxnet_tpu.serve import DecodeEngine, DecodeScheduler
from mxnet_tpu.serve.engine import DeadlineExceeded
from mxnet_tpu.serve.kvcache import SCRATCH_PAGE

pytestmark = pytest.mark.decode

SEED = 3000000019      # over 2**31, as the driver's are
CFG = {
    "vocab_size": 96, "hidden_size": 64, "num_layers": 8, "full_interval": 4,
    "num_heads": 8, "num_kv_heads": 1, "head_dim": 16, "rotary_dim": 4,
    "rope_theta": 10000000, "linear_key_heads": 2, "linear_value_heads": 4,
    "linear_key_dim": 16, "linear_value_dim": 8, "conv_width": 4,
    "expert_width": 32, "router_experts": 8, "experts_first": 2,
    "experts_held": 4, "experts_per_token": 3, "rms_eps": 1e-6,
    "max_length": 64}
PAGE, SLOTS = 8, 2


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def params():
    return gdn_moe.init_params(CFG, SEED)


def _engine(params, dtype="float32", slots=SLOTS):
    model = gdn_moe.GDNMoEDecodeModel(
        CFG, params=f32(params) if dtype == "float32" else params)
    return DecodeEngine(model, slots=slots, page_size=PAGE, num_pages=17,
                        prompt_buckets=[16, 32])


# -- the configuration and the weights ----------------------------------------

def test_config_from_the_published_keys():
    hf = {"decoder_sparse_step": 1, "full_attention_interval": 4,
          "head_dim": 256, "hidden_size": 2048, "linear_conv_kernel_dim": 4,
          "linear_key_head_dim": 128, "linear_num_key_heads": 16,
          "linear_num_value_heads": 32, "linear_value_head_dim": 128,
          "max_position_embeddings": 17408, "mlp_only_layers": [],
          "moe_intermediate_size": 512, "num_attention_heads": 16,
          "num_experts": 128, "num_experts_per_tok": 10,
          "num_hidden_layers": 8, "num_key_value_heads": 2,
          "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-6,
          "rope_theta": 10000000, "vocab_size": 37984}
    cfg = gdn_moe.config_from_hf(hf, router_experts=512)
    assert (cfg["experts_held"], cfg["router_experts"]) == (128, 512)
    assert cfg["rotary_dim"] == 64 and cfg["full_interval"] == 4
    assert gdn_moe.layer_kinds(cfg) == ([0, 1, 2, 4, 5, 6], [3, 7])
    model = gdn_moe.GDNMoEDecodeModel(
        cfg, params=jax.eval_shape(lambda: gdn_moe.init_params(cfg, 0)))
    # a position's k and v of both cached heads, flat; 2 of 8 layers paged
    assert model.cache_row == (1024,) and model.paged_layers == 2
    # 32 x 128 x 128 float32 a delta layer, and 3 inputs of 8192 channels
    assert model.state["s"] == ((6, 32, 128, 128), jnp.float32)
    assert model.state["tail"] == ((6, 192, 128), jnp.bfloat16)   # 3 x 8192
    # the issue's arithmetic: 3.67 G parameters
    leaves = jax.tree_util.tree_leaves(model.params)
    assert sum(int(np.prod(a.shape)) for a in leaves) == 3_667_251_328
    with pytest.raises(ValueError, match="whole periods"):
        gdn_moe.layer_kinds(dict(cfg, num_layers=6))


def test_program_and_reference_make_the_same_weights(params):
    assert params["experts"]["gate_w"].dtype == jnp.bfloat16
    assert params["delta"]["A_log"].dtype == jnp.float32
    delta, full = gdn_moe.layer_kinds(CFG)
    held = CFG["experts_held"]

    def same(got, *want):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.concatenate(want, axis=-1))

    for layer in range(CFG["num_layers"]):
        w = ref.layer_weights(CFG, SEED, layer)
        for name in gdn_moe.COMMON:
            same(params["moe"][name][layer], w[name])
        if layer in full:
            p = {k: v[full.index(layer)] for k, v in params["full"].items()}
            same(p["q_w"], w["q_w"])
            same(p["kv_w"], w["k_w"], w["v_w"])
            same(p["q_norm"], w["q_norm"])
            same(p["o_w"], w["o_w"])
        else:
            p = {k: v[delta.index(layer)] for k, v in params["delta"].items()}
            same(p["qkv_w"], w["dq_w"], w["dk_w"], w["dv_w"])
            same(p["ba_w"], w["db_w"], w["da_w"])
            for name in ("z_w", "conv_w", "A_log", "dt_bias", "gnorm",
                         "out_w"):
                same(p[name], w["dz_w" if name == "z_w" else name])
            # u in [1, 16]: log u in [0, log 16]; the gated norm's gain ~ 1
            assert 0 <= float(p["A_log"].min()) <= float(p["A_log"].max()) < 2.78
            assert abs(float(p["gnorm"].astype(jnp.float32).mean()) - 1) < 0.05
        for name in ("gate_w", "up_w", "down_w"):
            same(params["experts"][name][layer * held:(layer + 1) * held],
                 w["experts_" + name])
    same(params["embed"], ref.vocab_weights(CFG, SEED, "embed"))
    same(params["head"], ref.vocab_weights(CFG, SEED, "head"))


# -- the delta rule and the convolution -----------------------------------------

def _delta_inputs(s, h=3, dk=16, dv=8, seed=0):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(s, h, dk))).astype(np.float32)
    k = unit(rng.normal(size=(s, h, dk))).astype(np.float32)
    v = rng.normal(size=(s, h, dv)).astype(np.float32)
    # log decays from a slow leak to forgetting everything in a token
    g = -np.exp(rng.uniform(-4, 3, size=(s, h))).astype(np.float32)
    beta = rng.uniform(size=(s, h)).astype(np.float32)
    return q, k, v, g, beta


def _by_the_equations(q, k, v, g, beta, state):
    """The issue's five assignments, token by token, in float64."""
    state = np.array(state, np.float64)
    out = []
    for t in range(len(q)):
        state = state * np.exp(g[t].astype(np.float64))[:, None, None]
        r = np.einsum("hkv,hk->hv", state, k[t])
        d = beta[t][:, None] * (v[t] - r)
        state = state + k[t][:, :, None] * d[:, None, :]
        out.append(np.einsum("hkv,hk->hv", state, q[t]))
    return np.stack(out), state


def _chunked(impl, *args, **kwargs):
    """The chunked rule in one of its two forms: XLA's einsums, or the
    ``gdn_prefill`` kernel interpreted."""
    return gated_delta.delta_rule_chunked(*args, impl=impl, interpret=True,
                                          **kwargs)


def test_the_form_follows_backend_and_shape():
    """The kernel where the caller's backend runs Pallas kernels, chunks
    are ``CHUNK`` and — compiled — a head is whole 128-lane blocks;
    everything else is XLA's."""
    form = gated_delta.chunked_form
    assert form(128, 128, impl="pallas") == "gdn_prefill"
    assert form(16, 8, impl="pallas", interpret=True) == "gdn_prefill"
    assert form(128, 128) == form(128, 128, impl="xla") == "xla"
    assert form(16, 8, impl="pallas") == form(128, 64, impl="pallas") == "xla"
    assert form(128, 128, chunk=32, impl="pallas") == "xla"
    assert gated_delta._heads_a_step(32, 2) == 4
    assert gated_delta._heads_a_step(3, 1) == 3
    assert gated_delta._heads_a_step(4, 8) == 8


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("length", [1, 63, 64, 150])
def test_chunked_delta_rule_is_the_recurrence(length, impl):
    """Outputs and final state, at lengths off the chunk, from a state that
    is not zero: the chunked form (XLA's and the kernel), the scanned
    recurrence and the equations written out in float64 agree to float32
    rounding."""
    q, k, v, g, beta = _delta_inputs(length)
    state = np.random.default_rng(9).normal(size=(3, 16, 8)).astype(np.float32)
    want_o, want_s = _by_the_equations(q, k, v, g, beta, state)
    o_r, s_r = gated_delta.delta_rule_recurrent(q, k, v, g, beta, state)
    o_c, s_c = _chunked(impl, q, k, v, g, beta, state)
    for got_o, got_s in ((o_r, s_r), (o_c, s_c)):
        np.testing.assert_allclose(got_o, want_o, atol=2e-5)
        np.testing.assert_allclose(got_s, want_s, atol=2e-5)
    # from no state, as a prefill starts (XLA's form in chunks of 32 too);
    # and a masked tail writes nothing
    chunk = 32 if impl == "xla" else gated_delta.CHUNK
    o0, s0 = _chunked(impl, q, k, v, g, beta, chunk=chunk)
    want_o, want_s = _by_the_equations(q, k, v, g, beta,
                                       np.zeros_like(state))
    np.testing.assert_allclose(o0, want_o, atol=2e-5)
    np.testing.assert_allclose(s0, want_s, atol=2e-5)
    pad = 7
    padded = [np.concatenate([a, np.ones((pad,) + a.shape[1:], a.dtype)])
              for a in (q, k, v)]
    dead = [np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
            for a in (g, beta)]
    _, s_masked = _chunked(impl, *padded, *dead)
    np.testing.assert_allclose(s_masked, s0, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_delta_rule_with_keys_that_repeat(impl):
    """Every key the same, beta 1, no decay: the chunk's triangular system
    is as far from the identity as it gets (a power series of it would
    cancel catastrophically). Forward substitution holds."""
    s, h, dk, dv = 64, 1, 16, 8
    rng = np.random.default_rng(3)
    k = np.tile(rng.normal(size=(1, h, dk)), (s, 1, 1)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(s, h, dv)).astype(np.float32)
    g, beta = np.zeros((s, h), np.float32), np.ones((s, h), np.float32)
    want_o, want_s = _by_the_equations(k, k, v, g, beta,
                                       np.zeros((h, dk, dv)))
    o, st = _chunked(impl, k, k, v, g, beta)
    np.testing.assert_allclose(o, want_o, atol=1e-4)
    np.testing.assert_allclose(st, want_s, atol=1e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_delta_rule_at_lane_wide_heads(impl):
    """The shape the chip runs: heads of 128 x 128, two value heads on ONE
    key head — both in a grid step of the kernel, sharing ``[k; q] k^T`` —,
    192 tokens (three chunks carry the state), the last 20 masked, from a
    state that is not zero."""
    s, hv, d = 192, 2, 128
    q, k, _, _, _ = _delta_inputs(s, h=1, dk=d, seed=5)
    _, _, v, g, beta = _delta_inputs(s, h=hv, dv=d, seed=6)
    g[-20:], beta[-20:] = 0.0, 0.0
    state = np.random.default_rng(7).normal(size=(hv, d, d)).astype(np.float32)
    assert gated_delta._heads_a_step(hv, hv) == 2
    want_o, want_s = _by_the_equations(
        np.repeat(q, hv, axis=1), np.repeat(k, hv, axis=1), v, g, beta, state)
    o, st = _chunked(impl, q, k, v, g, beta, state)
    np.testing.assert_allclose(o, want_o, atol=5e-5)
    np.testing.assert_allclose(st, want_s, atol=5e-5)
    np.testing.assert_array_equal(st, _chunked(impl, q[:-20], k[:-20],
                                               v[:-20], g[:-20], beta[:-20],
                                               state)[1])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_one_token_delta_rule_in_place(impl):
    """The step: one token a slot against the states array of every slot
    and layer. A live slot's state of THAT layer is the recurrence's; the
    other layer's, an idle slot's and the scratch slot's rows of the other
    layers are untouched."""
    b, layers, h, dk, dv = 3, 2, 4, 16, 8
    q, k, v, g, beta = _delta_inputs(b, h=h)
    rng = np.random.default_rng(4)
    states = rng.normal(size=(b + 1, layers, h, dk, dv)).astype(np.float32)
    live = np.array([True, False, True])
    o, new = gated_delta.delta_rule_step(
        jnp.asarray(states), 1, q, k, v, g, beta, jnp.asarray(live),
        impl=impl, interpret=True)
    new = np.asarray(new)
    for i in range(b):
        if live[i]:
            want_o, want_s = _by_the_equations(
                q[i:i + 1], k[i:i + 1], v[i:i + 1], g[i:i + 1],
                beta[i:i + 1], states[i, 1])
            np.testing.assert_allclose(o[i], want_o[0], atol=1e-5)
            np.testing.assert_allclose(new[i, 1], want_s, atol=1e-5)
        else:
            np.testing.assert_array_equal(new[i], states[i])
    np.testing.assert_array_equal(new[:, 0], states[:, 0])


def test_convolution_with_a_carried_tail_is_the_whole_sequence_one():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(23, 6)).astype(np.float32)
    w = rng.normal(size=(4, 6)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(len(x)):
        for j in range(4):        # the last row weighs the current input
            if t - 3 + j >= 0:
                want[t] += w[j] * x[t - 3 + j]
    whole, tail = gated_delta.causal_conv(x, w)
    np.testing.assert_allclose(whole, want, atol=1e-6)
    np.testing.assert_array_equal(tail, x[-3:])
    # a prefill of 9 positions (padded to 16), then one input at a time
    out, tail = gated_delta.causal_conv(
        np.concatenate([x[:9], np.full((7, 6), 99, np.float32)]), w, length=9)
    np.testing.assert_allclose(out[:9], want[:9], atol=1e-6)
    np.testing.assert_array_equal(tail, x[6:9])
    for t in range(9, 23):
        step, tail = gated_delta.causal_conv_step(x[t][None], tail[None], w)
        tail = tail[0]
        np.testing.assert_allclose(step[0], want[t], atol=1e-6)
    # a prompt shorter than the tail: zeros stand for what was not there
    _, short = gated_delta.causal_conv(x[:8], w, length=2)
    np.testing.assert_array_equal(short, np.concatenate(
        [np.zeros((1, 6), np.float32), x[:2]]))


# -- grouped-KV attention ---------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_grouped_kv_paged_kernel_against_a_plain_gather(dtype, tol):
    """8 query heads on each of 2 cached heads, a flat ``[k || v]`` row:
    the interpreted kernel against the gather, lengths on and off a page's
    edge, an idle row (length 0) finite."""
    b, kvh, g, d, pages, layers = 3, 2, 8, 16, 12, 2
    rng = np.random.default_rng(6)
    pool = jnp.asarray(rng.normal(size=(pages, layers, PAGE, 2 * kvh * d)),
                       dtype)
    q = jnp.asarray(rng.normal(size=(b, kvh, g, d)), dtype)
    table = jnp.asarray(rng.integers(1, pages, size=(b, 4)), jnp.int32)
    lengths = jnp.asarray([13, 0, 32], jnp.int32)
    for layer in range(layers):
        want = gqa_attention._gqa_decode_xla(q, pool, layer, table, lengths,
                                             0.25)
        got = gqa_attention.flash_gqa_decode_attention(
            q, pool, layer, table, lengths, 0.25, interpret=True)
        diff = np.abs(np.asarray(got, np.float32)
                      - np.asarray(want, np.float32))
        assert diff[[0, 2]].max() < tol and np.isfinite(diff).all()
    # by hand, one head of one sequence: row = k of both heads, then v
    rows = np.asarray(pool[table[0], 1], np.float32).reshape(-1, 2, kvh, d)[:13]
    sc = rows[:, 0, 1] @ np.asarray(q[0, 1, 3], np.float32) * 0.25
    p = np.exp(sc - sc.max())
    np.testing.assert_allclose(np.asarray(want[0, 1, 3], np.float32),
                               (p / p.sum()) @ rows[:, 1, 1], atol=tol)


@pytest.mark.parametrize("blocks", [(16, 32), (32, 16), (64, 64), (8, 64)])
def test_grouped_kv_flash_forward_is_causal_attention(blocks):
    kvh, g, s, d = 2, 8, 64, 16
    rng = np.random.default_rng(7)
    q = rng.normal(size=(kvh, g, s, d)).astype(np.float32)
    k = rng.normal(size=(kvh, s, d)).astype(np.float32)
    v = rng.normal(size=(kvh, s, d)).astype(np.float32)
    sc = np.einsum("hgqd,hkd->hgqk", q, k) / np.sqrt(d)
    sc = np.where(np.arange(s)[:, None] >= np.arange(s)[None], sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("hgqk,hkd->hgqd", p / p.sum(-1, keepdims=True), v)
    got = gqa_attention.gqa_flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), block_q=blocks[0],
        block_k=blocks[1])
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("start", [0, 32, 96])
@pytest.mark.parametrize("blocks", [(16, 32), (32, 16), (8, 64)])
def test_grouped_kv_flash_forward_of_a_piece_is_the_rows_of_the_whole(
        start, blocks):
    """Query rows ``start .. start + 32`` against the keys of every position
    from 0: the rows the whole forward gives at those positions — the same
    blocks in the same order, so bit for bit — and dense causal attention;
    what lies behind the piece in the key array (finite, as the model hands
    it over) is not looked at."""
    kvh, g, total, c, d = 2, 8, 128, 32, 16
    rng = np.random.default_rng(7)
    q = rng.normal(size=(kvh, g, total, d)).astype(np.float32)
    k = rng.normal(size=(kvh, total, d)).astype(np.float32)
    v = rng.normal(size=(kvh, total, d)).astype(np.float32)
    whole = gqa_attention.gqa_flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), block_q=blocks[0],
        block_k=blocks[1])
    behind = np.arange(total)[None, :, None] >= start + c
    got = gqa_attention.gqa_flash_attention_from(
        jnp.asarray(q[:, :, start:start + c]),
        jnp.asarray(np.where(behind, 1e4, k)),
        jnp.asarray(np.where(behind, -1e4, v)), jnp.int32(start),
        block_q=blocks[0], block_k=blocks[1])
    np.testing.assert_array_equal(got, whole[:, :, start:start + c])
    sc = np.einsum("hgqd,hkd->hgqk", q[:, :, start:start + c], k) / np.sqrt(d)
    sc = np.where(start + np.arange(c)[:, None] >= np.arange(total)[None], sc,
                  -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("hgqk,hkd->hgqd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_convolution_of_a_piece_carries_on_from_the_tail_before_it():
    """Pieces of 8 of a 23-token sequence, each from the tail the one before
    left: the whole sequence's outputs, and the tail at ``length`` inside the
    last piece."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(24, 6)).astype(np.float32)
    w = rng.normal(size=(4, 6)).astype(np.float32)
    want, want_tail = gated_delta.causal_conv(x, w, length=23)
    tail = None
    for start in (0, 8, 16):
        out, tail = gated_delta.causal_conv(x[start:start + 8], w,
                                            23 - start, tail)
        np.testing.assert_allclose(out, want[start:start + 8], atol=1e-6)
    np.testing.assert_array_equal(tail, want_tail)
    np.testing.assert_array_equal(tail, x[20:23])


# -- a prompt continued from a position ----------------------------------------------

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
        logits, rows, _, state = one(tokens[:, start:start + piece],
                                     jnp.int32(start), pool, state)
        pool = pool.at[:, start:start + piece].set(rows)
    return logits, pool, state


@pytest.mark.parametrize("piece,length,dirty", [
    (64, 150, True),      # an odd last piece: 22 of its 64 positions live
    (64, 150, False),
    (128, 200, True),
    (64, 128, True),      # the last piece full
])
def test_a_prompt_in_pieces_is_the_prompt_whole(piece, length, dirty, params):
    """Logits, every paged row, ``s`` and ``tail``: pieces of 64 and of 128
    cut the prompt where the delta rule's own scan cuts it, the convolution
    carries on from the tail and attention reads the rows before the piece —
    the numbers of ``prefill`` over the whole prompt, whatever (NaN) the
    slot's state and the pool held before the first piece."""
    model = gdn_moe.GDNMoEDecodeModel(CFG, params=f32(params))
    total = -(-length // piece) * piece
    tokens = np.zeros((1, total), np.int32)
    tokens[0, :length] = np.random.default_rng(length).integers(
        0, CFG["vocab_size"], length)
    want_logits, want_rows, _, want_state = jax.jit(model.prefill)(
        model.params, tokens, length)
    logits, rows, state = _in_pieces(model, jnp.asarray(tokens), length,
                                     piece, dirty)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(rows[:, :length], want_rows[:, :length],
                               atol=2e-5)
    np.testing.assert_allclose(state["s"], want_state["s"], atol=2e-5)
    np.testing.assert_array_equal(state["tail"], want_state["tail"])
    assert np.all(np.isfinite(rows)) and np.all(np.isfinite(state["s"]))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("piece,length", [(64, 150), (128, 200)])
def test_a_prompt_in_pieces_is_the_recurrence_token_by_token(
        piece, length, impl, params, monkeypatch):
    """The same pieces — their delta layers in XLA's chunked form, and in the
    ``gdn_prefill`` kernel (interpreted; the model picks it as on a TPU) —
    against a whole prefill whose delta layers run the plain recurrence, one
    token after another."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", impl)
    model = gdn_moe.GDNMoEDecodeModel(CFG, params=f32(params))
    assert model.delta_rule() == {"xla": "xla", "pallas": "gdn_prefill"}[impl]
    total = -(-length // piece) * piece
    tokens = np.zeros((1, total), np.int32)
    tokens[0, :length] = np.random.default_rng(length).integers(
        0, CFG["vocab_size"], length)
    logits, rows, state = _in_pieces(model, jnp.asarray(tokens), length,
                                     piece, True)

    def recurrent(q, k, v, g, beta, state, **_):
        q, k = (jnp.repeat(a, v.shape[1] // a.shape[1], axis=1)
                for a in (q, k))
        return gated_delta.delta_rule_recurrent(q, k, v, g, beta, state)

    monkeypatch.setattr(gated_delta, "delta_rule_chunked", recurrent)
    want_logits, want_rows, _, want_state = jax.jit(
        lambda p, t: model.prefill(p, t, length))(model.params, tokens)
    np.testing.assert_allclose(logits, want_logits, atol=1e-4)
    np.testing.assert_allclose(rows[:, :length], want_rows[:, :length],
                               atol=1e-4)
    np.testing.assert_allclose(state["s"], want_state["s"], atol=1e-4)


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
    """Prefill (the chunked delta rule, the grouped flash forward) and then
    26 decode steps (the one-token kernel on the per-slot state, the paged
    grouped kernel on the pool) through ``DecodeEngine``'s own two programs,
    against the reference's ONE full forward over prompt + generated ids,
    logits.

    float32: 2e-4 of logits of size ~0.2 (float32 rounding through 8
    layers; a dropped gate, norm or scale moves them by 1e-2 and more).
    bfloat16: 0.03 absolute, as the latent model's test has it."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")   # interpreted kernels
    engine = _engine(params, dtype)
    assert engine.stats()["delta_rule"] == "gdn_prefill"
    # 2 of 8 layers are paged; a row is k and v of the one cached head
    assert engine.kv.shape == (17, 2, PAGE, 32) and engine.kv.dtype == dtype
    assert engine.paged_layers == 2
    stats = engine.stats()
    assert stats["state"] == {
        "s": {"shape": [SLOTS + 1, 6, 4, 16, 8], "dtype": "float32"},
        "tail": {"shape": [SLOTS + 1, 6, 3, 96], "dtype": dtype}}
    assert stats["state_bytes"] == 6 * (4 * 16 * 8 * 4 + 3 * 96 * (
        4 if dtype == "float32" else 2))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (13, 22)]
    new = 27
    out = _generate(engine, prompts, new, seen)
    tol = 2e-4 if dtype == "float32" else 0.03
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
    # the counters came back with the tokens: 8 expert layers x 2 slots x 3
    c = engine.last_counters
    assert c["moe.assignments"] == 8 * SLOTS * 3 and c["moe.dropped"] == 0


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


# -- the shares ---------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_reference():
    """512 experts over four chips and the vocabulary a quarter to a chip, at
    8 experts and 96 rows: the four shares' routed parts plus the gated
    shared expert counted ONCE equal the uncut reference layer (float32,
    1e-5); the four slices' logits side by side are the uncut head's; a
    slice's embedding rows are the uncut table's. And the mistakes this
    guards against do not: the shared expert counted per share, the chosen
    weights renormalised over the held experts only."""
    layer = 5
    h = 0.7 * jax.random.normal(jax.random.PRNGKey(2), (24, 64), jnp.float32)
    live = jnp.ones((24,), bool)
    uncut = dict(CFG, experts_first=0, experts_held=8)
    w_ref = ref.layer_weights(uncut, SEED, layer)
    want = ref.expert_layer(uncut, w_ref, h, "f32")
    routed, whole, renormed, logits, embeds = 0.0, 0.0, 0.0, [], []
    for share in range(4):
        cfg = dict(CFG, experts_first=2 * share, experts_held=2,
                   vocab_first=24 * share, vocab_size=24)
        p = f32(gdn_moe.init_params(cfg, SEED))
        lp = {k: w[layer] for k, w in p["moe"].items()}
        lp = {k: lp[k] for k in lp if k not in ("attn_norm", "mlp_norm")}
        assert "router_b" not in lp and "shared_s_w" in lp
        chosen, gates = moe.route_softmax(h, lp["router_w"], 3)
        np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)
        ew = (p["experts"]["gate_w"], p["experts"]["up_w"],
              p["experts"]["down_w"])
        y, c = moe.held_experts(h, chosen, gates, live, *ew, 2 * share, 2,
                                layer * 2)
        assert int(c[moe.COUNTERS.index("dropped")]) == 0
        routed = routed + y
        whole = whole + moe.expert_layer(
            h, lp, p["experts"], live, first=2 * share, held=2, k=3,
            offset=layer * 2)[0]
        held = (chosen >= 2 * share) & (chosen < 2 * share + 2)
        wrong = gates / jnp.maximum(
            jnp.sum(jnp.where(held, gates, 0), -1, keepdims=True), 1e-9)
        renormed = renormed + moe.held_experts(
            h, chosen, wrong, live, *ew, 2 * share, 2, layer * 2)[0]
        model = gdn_moe.GDNMoEDecodeModel(cfg, params=p)
        logits.append(model._head(p, h))
        embeds.append(p["embed"])
    shared = (jax.nn.sigmoid(h @ lp["shared_s_w"])[:, None] * moe.gated_mlp(
        h, lp["shared_gate_w"], lp["shared_up_w"], lp["shared_down_w"]))
    np.testing.assert_allclose(routed + shared, want, atol=1e-5)
    np.testing.assert_allclose(whole - 3 * shared, want, atol=1e-5)
    assert float(jnp.abs(whole - want).max()) > 1e-3        # shared x 4
    assert float(jnp.abs(renormed + shared - want).max()) > 1e-3
    whole_vocab = dict(CFG, vocab_size=96)
    head = ref.vocab_weights(whole_vocab, SEED, "head")
    gain = ref._draw(ref.base_key(SEED), "final_norm", (64,))
    np.testing.assert_allclose(
        jnp.concatenate(logits, axis=-1),
        ref._head(h, gain, head, eps=1e-6, precision="f32"), atol=1e-5)
    np.testing.assert_array_equal(
        jnp.concatenate(embeds), ref.vocab_weights(whole_vocab, SEED, "embed"))


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
        # stream, not its length (a reader that sleeps does not slow the
        # scheduler: on an idle host all 40 came in under 150 ms, and the
        # stream ended by length)
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


def test_a_neighbour_prefilling_in_pieces_does_not_move_a_streams_tokens(
        scheduler):
    """The engine feeds this model pieces of its smallest bucket (16): a
    30-token prompt goes in two, one a turn, in front of the steps of the
    stream that is decoding beside it — whose tokens, and the prompt's own,
    are what each gets alone; one ``decode.prefill`` span a piece."""
    engine = scheduler.engine
    assert engine.prefill_piece == 16 and engine.buckets == [16]
    assert engine.stats()["max_prompt"] == 32
    first = np.arange(7, 12, dtype=np.int32)
    second = np.arange(40, 70, dtype=np.int32)
    alone = [list(scheduler.generate(p, max_new_tokens=n))
             for p, n in ((first, 24), (second, 6))]
    assert engine.stats()["num_programs"] == 2
    before = scheduler.stats()
    obs.enable()
    try:
        obs.trace.drain()
        a = scheduler.submit(first, max_new_tokens=24)
        got_a = [a.get(timeout=60) for _ in range(3)]    # A is decoding
        b = scheduler.submit(second, max_new_tokens=6)
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
    assert st["prefill_piece"] == 16
    assert st["admitted"] - before["admitted"] == 2
    assert st["prefill_pieces"] - before["prefill_pieces"] == 3
    assert engine.stats()["num_programs"] == 2
    calls = sorted((s for s in spans
                    if s["name"] in ("decode.prefill", "decode.step")),
                   key=lambda s: s["ts"])
    pieces = [s["args"] for s in calls if s["name"] == "decode.prefill"]
    assert [(p["prompt_len"], p["start"], p["pieces"], p["bucket"])
            for p in pieces] == [(5, 0, 1, 16), (30, 0, 2, 16), (30, 16, 2, 16)]
    assert all(p["moe.dropped"] == 0 for p in pieces)
    # a step between the prompt's two pieces: A did not wait for both
    names = [s["name"] for s in calls]
    i = [k for k, n in enumerate(names) if n == "decode.prefill"]
    assert "decode.step" in names[i[1] + 1:i[2]]


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
    sched = DecodeScheduler(_engine(params, slots=1), default_timeout=60.0,
                            eos_id=tokens[3])
    try:
        assert list(sched.generate(prompt, max_new_tokens=8)) == tokens[:4]
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
    assert (row, state) == (32 * 4, 6 * (4 * 16 * 8 * 4 + 3 * 96 * 4))
    for i, s in enumerate(steps):        # contexts 12, 13, 14, 15
        assert s["args"]["cache.paged_bytes"] == (12 + i) * row * 2
        assert s["args"]["cache.state_bytes"] == 2 * state
        assert s["args"]["moe.dropped"] == 0
    assert gauges == {"decode.state_bytes": state, "decode.paged_layers": 2,
                      "decode.cache_row_bytes": row}
