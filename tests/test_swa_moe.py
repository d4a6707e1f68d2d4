"""The sliding-window + global grouped-KV + routed-experts decoder
(``models/swa_moe.py``, ``ops/swa_attention.py``, the sigmoid router of
``ops/moe.py``) and the engine's per-slot rings (``serve/decode.py``) against
the plain reference ``benchmark/reference_swa_moe.py`` — the repo's one copy
of the equations — at tiny sizes on the CPU, Pallas kernels interpreted.

The mathematics is checked in float32 (the same bodies run on a float32
tree), where the program must agree with the reference to rounding; the
bfloat16 run is then held to a bfloat16-sized tolerance.
"""
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_swa_moe as ref
from mxnet_tpu import obs
from mxnet_tpu.models import swa_moe, transformer
from mxnet_tpu.ops import moe, swa_attention
from mxnet_tpu.serve import DecodeEngine, DecodeScheduler
from mxnet_tpu.serve.kvcache import SCRATCH_PAGE

pytestmark = pytest.mark.decode

SEED = 3000000019      # over 2**31, as the driver's are
# both kinds of layer twice, a window (8) shorter than every prompt, keys
# wider than values, 2 and 4 cached heads, a share of the experts that does
# not start at 0
CFG = {
    "vocab_size": 96, "vocab_first": 0, "hidden_size": 64, "num_layers": 6,
    "layer_pattern": [0, 1, 1, 0, 1, 1], "moe_pattern": [0, 1, 1, 1, 1, 1],
    "num_heads": 8, "head_dim": 24, "v_head_dim": 16, "kv_heads": 2,
    "swa_kv_heads": 4, "window": 8, "swa_sink": True, "rotary_dim": 8,
    "rope_theta": 5000000, "swa_rope_theta": 10000, "value_scale": 0.707,
    "dense_width": 128, "expert_width": 32, "router_experts": 16,
    "experts_first": 4, "experts_held": 4, "experts_per_token": 4,
    "routed_scale": 1.0, "rms_eps": 1e-5, "max_length": 96}
PAGE, SLOTS, PIECE = 8, 2, 16
ROW, RING_ROW = 2 * (24 + 16), 4 * (24 + 16)


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def params():
    return swa_moe.init_params(CFG, SEED)


def _engine(params, dtype="float32", slots=SLOTS):
    model = swa_moe.SWAMoEDecodeModel(
        CFG, params=f32(params) if dtype == "float32" else params)
    return DecodeEngine(model, slots=slots, page_size=PAGE, num_pages=25,
                        prompt_buckets=[16, 32, 48])


# -- the configuration and the weights ----------------------------------------

def test_config_from_the_published_keys():
    """``config_from_hf`` of the benchmark's configuration file (the catalog
    row's keys, four of them reduced) is its ``model`` block, and the counts
    the deployment states are ``leaf_shapes``'."""
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "configs", "mimo-v2-flash.json")
    with open(path) as f:
        file = json.load(f)
    model = dict(file["model"])
    assert model.pop("kind") == "swa_moe_lm"
    assert swa_moe.config_from_hf(
        file, experts_held=8, router_experts=256) == model
    assert file["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size", "max_position_embeddings"]
    for key, value in file["published"].items():
        assert file[key] == value or key in file["reduced"], key
    window, glob, routed = swa_moe.layer_kinds(model)
    assert (len(window), glob, len(routed)) == (9, [0, 5, 11], 11)
    assert model["rotary_dim"] == 64
    count = {w: sum(int(np.prod(swa_moe.leaf_shapes(model, w)[n]))
                    for n in ("q_w", "k_w", "v_w", "o_w") + (("sink",) * w))
             for w in (False, True)}
    assert count == {False: 89128960, True: 94371904}
    lm = swa_moe.SWAMoEDecodeModel(
        model, params=jax.eval_shape(lambda: swa_moe.init_params(model, 0)))
    leaves = jax.tree_util.tree_leaves(lm.params)
    total = sum(int(np.prod(a.shape)) for a in leaves)
    assert total == 3700530496 and f"{total:,} parameters" in file["deployment"]
    assert lm.cache_row == (1280,) and lm.paged_layers == 3
    assert lm.state == {"window": ((9, 128, 2560), jnp.bfloat16)}
    assert (lm.moe_row_tile(64), lm.moe_row_tile(1024)) == (16, 64)
    with pytest.raises(NotImplementedError):
        swa_moe.config_from_hf(dict(file, n_shared_experts=1))


def test_program_and_reference_make_the_same_weights(params):
    """Every leaf, bit for bit: the two state the same scheme on their own."""
    window, _, routed = swa_moe.layer_kinds(CFG)
    for i, lp in enumerate(params["layers"]):
        w = ref.layer_weights(CFG, SEED, i)
        for name in ("attn_norm", "mlp_norm", "q_w", "o_w"):
            np.testing.assert_array_equal(f32(lp[name]), w[name])
        np.testing.assert_array_equal(
            f32(lp["kv_w"]), jnp.concatenate([w["k_w"], w["v_w"]], axis=-1))
        assert ("sink" in lp) == (i in window) == ("sink" in w)
        if i in window:
            np.testing.assert_array_equal(lp["sink"], w["sink"])
            assert lp["sink"].dtype == jnp.float32 and lp["sink"].shape == (8,)
            assert lp["kv_w"].shape == (64, RING_ROW)
        names = (("router_w", "router_b") if i in routed
                 else ("gate_w", "up_w", "down_w"))
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
    # sinks are N(0, 1), not the leaves' 0.02: they move the softmax
    sinks = np.concatenate([np.asarray(lp["sink"]) for lp in params["layers"]
                            if "sink" in lp])
    assert 0.5 < sinks.std() < 1.5


# -- the kernels, interpreted, against their plain twins ------------------------

def _rnd(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def test_key_slices_are_whole_lane_tiles_at_the_published_widths():
    """192-wide keys: a pair of heads fills three lane tiles, an even head is
    read from its first lane and an odd one up to its last, 256 lanes each;
    a query zero-padded on the other side meets only its own head's lanes."""
    assert swa_attention.key_slices(4, 192) == [
        (0, 256, 0), (128, 256, 64), (384, 256, 0), (512, 256, 64)]
    assert swa_attention.key_slices(2, 128) == [(0, 128, 0), (128, 128, 0)]
    assert swa_attention.key_slices(4, 24) == [(24 * h, 24, 0)
                                               for h in range(4)]
    q = _rnd(0, 3, 4, 2, 192)
    padded = swa_attention._padded_query(q, 4, 192)
    assert padded.shape == (3, 4, 2, 256)
    keys = _rnd(1, 5, 4 * 192)
    for h, (first, wide, _) in enumerate(swa_attention.key_slices(4, 192)):
        np.testing.assert_allclose(
            padded[:, h] @ keys[:, first:first + wide].T,
            q[:, h] @ keys[:, h * 192:(h + 1) * 192].T, atol=1e-4)


@pytest.mark.parametrize("kvh,g,dk,dv", [(8, 8, 192, 128), (4, 16, 192, 128),
                                         (4, 2, 24, 16)])
def test_window_decode_kernel_against_its_plain_twin(kvh, g, dk, dv,
                                                     monkeypatch):
    """One position a slot against its ring (kernel ``swa_decode``,
    interpreted): a ring not yet full (position 5: indices 0-5 live, NaN
    behind them), one exactly full, one that has wrapped; the sink in the
    denominator; the layer picked out of the rings' array."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")
    w, b = 16, 3
    ring = np.array(_rnd(2, b + 1, 2, w, kvh * (dk + dv)))
    ring[0, 1, 6:] = np.nan                 # never written: position 5
    ring = jnp.asarray(ring)
    q, sink = _rnd(3, b, kvh, g, dk), _rnd(4, kvh * g)
    pos = jnp.asarray([5, w - 1, 5 * w + 3], jnp.int32)
    got = swa_attention.swa_decode_attention(q, ring, 1, pos, sink, dv)
    want = swa_attention._swa_decode_xla(q, ring, 1, pos,
                                         sink.reshape(kvh, g), dk ** -0.5, dv)
    assert got.shape == (b, kvh, g, dv) and np.all(np.isfinite(want))
    np.testing.assert_allclose(got[1:], want[1:], atol=2e-5)
    # (the kernel multiplies a dead row's value by zero: NaN there stays NaN,
    # the twin's where() hides it; slot 0 is compared with the ring cleaned)
    clean = ring.at[0, 1, 6:].set(0.0)
    np.testing.assert_allclose(
        swa_attention.swa_decode_attention(q, clean, 1, pos, sink, dv),
        want, atol=2e-5)
    # by hand, slot 1, query head (2, 1): every index live
    k = ring[1, 1, :, 2 * dk:3 * dk]
    v = ring[1, 1, :, kvh * dk + 2 * dv:kvh * dk + 3 * dv]
    s = (k @ q[1, 2, 1]) * dk ** -0.5
    top = jnp.maximum(s.max(), sink[2 * g + 1])
    p = jnp.exp(s - top) / (jnp.exp(s - top).sum()
                            + jnp.exp(sink[2 * g + 1] - top))
    np.testing.assert_allclose(got[1, 2, 1], p @ v, atol=2e-5)


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_a_sink_of_minus_infinity_is_the_plain_softmax(path, monkeypatch):
    """... in the decode kernel and in the piece's, and a large sink moves
    every output (towards zero: it takes the mass)."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", path)
    kvh, g, dk, dv, w = 2, 4, 24, 16, 8
    none = jnp.full((kvh * g,), -jnp.inf)
    big = jnp.full((kvh * g,), 8.0)
    ring, q = _rnd(5, 3, 1, w, kvh * (dk + dv)), _rnd(6, 2, kvh, g, dk)
    pos = jnp.asarray([3, 20], jnp.int32)
    got = swa_attention.swa_decode_attention(q, ring, 0, pos, none, dv)
    for b, n in ((0, 4), (1, w)):
        k, v = swa_attention._split(ring[b, 0, :n], kvh, dk, dv)
        p = jax.nn.softmax(jnp.einsum("hgd,whd->hgw", q[b], k) * dk ** -0.5)
        np.testing.assert_allclose(got[b], jnp.einsum("hgw,whd->hgd", p, v),
                                   atol=2e-5)
    sunk = swa_attention.swa_decode_attention(q, ring, 0, pos, big, dv)
    assert float(jnp.abs(sunk).max()) < 0.2 * float(jnp.abs(got).max())
    c = 2 * w
    qq, k, v = (_rnd(7, kvh, g, c, dk), _rnd(8, kvh, w + c, dk),
                _rnd(9, kvh, w + c, dv))
    for start in (0, c):
        plain = swa_attention.attention_from(qq, k, v, start, window=w)
        np.testing.assert_allclose(
            swa_attention.attention_from(qq, k, v, start, window=w, sink=none),
            plain, atol=1e-6)
        pos_ = start + np.arange(c)[:, None]
        col = start - w + np.arange(w + c)[None, :]
        seen = (col <= pos_) & (col > pos_ - w) & (col >= 0)
        sc = jnp.where(seen, jnp.einsum("hgqd,hkd->hgqk", qq, k) * dk ** -0.5,
                       -jnp.inf)
        np.testing.assert_allclose(
            plain, jnp.einsum("hgqk,hkd->hgqd", jax.nn.softmax(sc), v),
            atol=2e-5)
        sunk = swa_attention.attention_from(qq, k, v, start, window=w, sink=big)
        assert float(jnp.abs(sunk).max()) < 0.2 * float(jnp.abs(plain).max())


@pytest.mark.parametrize("kvh,g,dk,dv", [(4, 16, 192, 128), (2, 4, 24, 16)])
def test_paged_kernel_with_keys_wider_than_values(kvh, g, dk, dv, monkeypatch):
    """``gqa_decode_dv`` (interpreted) against gather-then-attend: lengths
    of one position, of a page's edge and past it, an idle row."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")
    page, b = 16, 4
    pool = _rnd(10, 9, 2, page, kvh * (dk + dv))
    q = _rnd(11, b, kvh, g, dk)
    table = jnp.asarray(np.random.default_rng(12).integers(1, 9, (b, 4)),
                        jnp.int32)
    lengths = jnp.asarray([1, 16, 41, 0], jnp.int32)
    got = swa_attention.gqa_decode_attention_dv(q, pool, 1, table, lengths, dv)
    want = swa_attention._gqa_decode_dv_xla(q, pool, 1, table, lengths,
                                            dk ** -0.5, dv)
    assert got.shape == (b, kvh, g, dv)
    np.testing.assert_allclose(got[:3], want[:3], atol=2e-5)
    assert np.all(np.isfinite(got[3]))


@pytest.mark.parametrize("kvh,g,dk,dv,w", [(8, 8, 192, 128, 128),
                                           (4, 2, 24, 16, 8)])
@pytest.mark.parametrize("start", [0, 2])
def test_window_piece_kernel_is_its_plain_twin(kvh, g, dk, dv, w, start,
                                               monkeypatch):
    """``swa_prefill_from`` (interpreted): a query block of W rows meets the
    block before it and its own; row i sees ``i - W + 1 .. i`` (its first
    row of a block ALL of the block before but one, its last row none), and
    nothing before position 0 whatever lies there."""
    c = 2 * w
    start = start * c
    q, k, v = (_rnd(13, kvh, g, c, dk), _rnd(14, kvh, w + c, dk),
               _rnd(15, kvh, w + c, dv))
    sink = _rnd(16, kvh * g)
    want = swa_attention._from_xla(start, q, k, v, sink, dk ** -0.5, w)
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")
    got = swa_attention.attention_from(q, k, v, start, window=w, sink=sink)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the window's edge: moving the key at i - W changes nothing of row i,
    # moving the one at i - W + 1 does (row i = w + 3 of the piece)
    i = w + 3
    for back, moved in ((w, False), (w - 1, True)):
        t = w + i - back                    # its row among the W + C
        other = swa_attention.attention_from(
            q, k.at[:, t].add(1.0), v.at[:, t].add(1.0), start, window=w,
            sink=sink)
        assert bool(jnp.abs(other[:, :, i] - got[:, :, i]).max() > 1e-4) == moved


@pytest.mark.parametrize("start", [0, 32, 96])
def test_global_piece_kernel_is_the_rows_of_the_whole(start, monkeypatch):
    """``gqa_prefill_from_dv`` (interpreted), keys 24 wide over values 16:
    the piece's rows of causal attention over all 128 positions, whatever
    (finite) lies behind the piece."""
    kvh, g, dk, dv, c, total = 2, 4, 24, 16, 32, 128
    q, k, v = (_rnd(17, kvh, g, total, dk), _rnd(18, kvh, total, dk),
               _rnd(19, kvh, total, dv))
    sc = jnp.einsum("hgqd,hkd->hgqk", q, k) * dk ** -0.5
    sc = jnp.where(np.tril(np.ones((total, total), bool)), sc, -jnp.inf)
    whole = jnp.einsum("hgqk,hkd->hgqd", jax.nn.softmax(sc), v)
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")
    behind = jnp.arange(total)[None, :, None] >= start + c
    got = swa_attention.attention_from(
        q[:, :, start:start + c], jnp.where(behind, 7.0, k),
        jnp.where(behind, -3.0, v), start, block_rows=64, block_k=32)
    np.testing.assert_allclose(got, whole[:, :, start:start + c], atol=2e-5)


# -- the model against the reference --------------------------------------------

@pytest.mark.parametrize("length", [3, 16, 43, 48])
def test_prefill_whole_is_the_reference(length, params):
    """``prefill`` over a padded prompt: the logits at its last position,
    and the rings — index ``p mod 8`` holds the newest position p of the
    prompt, the row the reference's k and v give."""
    model = swa_moe.SWAMoEDecodeModel(CFG, params=f32(params))
    tokens = np.zeros((1, 48), np.int32)
    tokens[0, :length] = np.random.default_rng(length).integers(0, 96, length)
    logits, rows, counters, state = jax.jit(model.prefill)(
        model.params, tokens, length)
    want = np.asarray(ref.logits(CFG, SEED, tokens[0]))
    np.testing.assert_allclose(logits, want[length - 1], atol=2e-5)
    assert rows.shape == (2, 48, ROW)
    assert state["window"].shape == (4, 8, RING_ROW)
    c = dict(zip(model.counters, np.asarray(counters)))
    assert c["moe.assignments"] == 5 * length * 4 and c["moe.dropped"] == 0
    seen = np.arange(1, length + 1)
    assert c["attn.window_rows"] == 4 * np.minimum(seen, 8).sum()
    assert c["attn.global_rows"] == 2 * seen.sum()
    # index r of a ring holds the newest position p < length with p mod 8 = r
    held = [r for r in range(8) if length - 1 - (length - 1 - r) % 8 >= 0]
    assert len(held) == min(length, 8)
    assert np.all(np.isfinite(state["window"][:, held]))


def _in_pieces(model, tokens, length, piece, dirty):
    """``prefill_from`` over tokens (1, n x piece) a piece at a time, the
    rings and the rows carried as the engine carries them (``dirty``: NaN in
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


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("piece,length,dirty", [
    (16, 43, True),       # an odd last piece: 11 of its 16 positions live
    (16, 43, False),
    (16, 34, True),       # ... 2 live: the ring spans two pieces
    (48, 37, True),       # one piece
    (16, 48, True),       # the last piece full
])
def test_a_prompt_in_pieces_is_the_prompt_whole(piece, length, dirty, path,
                                                params, monkeypatch):
    """Logits, every paged row and every ring: a window layer carries on
    from the ring the piece before left (and reads nothing of the pool), a
    global layer from the pool's rows — the numbers of ``prefill`` over the
    whole prompt, whatever (NaN) the slot's rings and the pool held before
    the first piece, through the plain twins and the interpreted kernels."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", path)
    model = swa_moe.SWAMoEDecodeModel(CFG, params=f32(params))
    tokens = np.zeros((1, 48), np.int32)
    tokens[0, :length] = np.random.default_rng(length).integers(0, 96, length)
    want_logits, want_rows, _, want_state = jax.jit(model.prefill)(
        model.params, tokens, length)
    logits, rows, state = _in_pieces(model, jnp.asarray(tokens), length,
                                     piece, dirty)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(rows[:, :length], want_rows[:, :length],
                               atol=2e-5)
    live = min(length, 8)          # ring indices that hold a position
    newest = [length - 1 - (length - 1 - r) % 8 for r in range(8)]
    held = [r for r in range(8) if newest[r] >= 0]
    assert len(held) == live
    np.testing.assert_allclose(state["window"][:, held],
                               want_state["window"][:, held], atol=2e-5)
    assert np.all(np.isfinite(state["window"][:, held]))
    np.testing.assert_allclose(
        logits, np.asarray(ref.logits(CFG, SEED, tokens[0]))[length - 1],
        atol=2e-5)


def test_a_window_layer_reads_nothing_of_the_pool(params):
    """``prefill_from`` asks ``prior`` for the global layers' rows alone: 2
    calls for 6 layers, by paged layer 0 and 1."""
    model = swa_moe.SWAMoEDecodeModel(CFG, params=f32(params))
    asked = []

    def prior(layer):
        asked.append(layer)
        return jnp.zeros((48, ROW), jnp.float32)

    jax.eval_shape(lambda t, s: model.prefill_from(
        model.params, t, jnp.int32(16), 40, prior, s),
        jnp.zeros((1, 16), jnp.int32),
        {"window": jnp.zeros((4, 8, RING_ROW), jnp.float32)})
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
def test_engine_prefill_then_decode_through_ring_and_pages(
        dtype, params, seen, monkeypatch):
    """Prompts in pieces of 16 and then 30 decode steps — over three windows,
    so every ring wraps three times and the global layers walk four pages —
    through ``DecodeEngine``'s own two programs (kernels interpreted),
    against the reference's ONE full forward over prompt + generated ids,
    logits.

    float32: 2e-4 of logits of size ~0.2 (float32 rounding through 6
    layers; a dropped sink, scale or mask moves them by 1e-2 and more).
    bfloat16: 0.03 absolute, as the other models' tests have it."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")   # interpreted kernels
    engine = _engine(params, dtype)
    # 2 of 6 layers are paged; a row is k and v of the 2 cached heads
    assert engine.kv.shape == (25, 2, PAGE, ROW) and engine.kv.dtype == dtype
    assert engine.paged_layers == 2 and engine.prefill_piece == PIECE
    stats = engine.stats()
    assert stats["state"] == {"window": {
        "shape": [SLOTS + 1, 4, 8, RING_ROW], "dtype": dtype}}
    item = 4 if dtype == "float32" else 2
    assert stats["state_bytes"] == 4 * 8 * RING_ROW * item
    assert stats["prefill_piece"] == PIECE
    assert stats["moe_row_tile"]["step"] == moe.layer_row_tile(
        SLOTS, 4, 16, engine.model.cache_dtype)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (13, 37)]
    new = 31
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
    # the counters came back with the tokens: 5 expert layers x 2 slots x 4,
    # and the rows the last step's attention saw (positions 42 and 66)
    c = engine.last_counters
    assert c["moe.assignments"] == 5 * SLOTS * 4 and c["moe.dropped"] == 0
    assert c["attn.window_rows"] == 4 * 2 * 8
    assert c["attn.global_rows"] == 2 * (43 + 67)


@pytest.mark.parametrize("before", ["another_request", "a_step_launched_ahead"])
def test_a_reused_slot_gives_the_request_what_it_gets_alone(
        before, params, seen):
    """A ring is not addressed through a page table, so nothing masks what a
    slot's last owner left but the prompt's own positions: a second request
    in a slot that another request used — and in a slot that a step launched
    ahead wrote AFTER its stream had ended — reads the logits it reads in a
    fresh engine."""
    rng = np.random.default_rng(1)
    first, second = (rng.integers(0, 96, n).astype(np.int32) for n in (19, 5))
    alone = _generate(_engine(params), [second], 12, seen, slots=[1])
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
    assert np.abs(np.asarray(engine.state["window"][1])).max() > 0
    again = _generate(engine, [second], 12, seen, slots=[1])
    assert again[0][0] == alone[0][0]
    np.testing.assert_allclose(np.stack(again[0][1]), np.stack(alone[0][1]),
                               atol=1e-6)


def test_an_idle_slots_rings_are_not_touched_by_the_step(params, seen,
                                                         monkeypatch):
    """A slot whose prompt is still going in rides the step idle: its rings
    hold the pieces so far and the step writes the scratch slot's."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")
    engine = _engine(params)
    rng = np.random.default_rng(2)
    _generate(engine, [rng.integers(0, 96, 9).astype(np.int32)], 2, seen,
              slots=[0])
    before = np.asarray(engine.state["window"])
    _generate(engine, [rng.integers(0, 96, 12).astype(np.int32)], 5, seen,
              slots=[1])
    after = np.asarray(engine.state["window"])
    np.testing.assert_array_equal(after[0], before[0])
    assert np.abs(after[1] - before[1]).max() > 0


# -- the shares ---------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_reference():
    """256 experts over 32 chips and the vocabulary an eighth to a chip, at
    16 experts over four shares and 96 rows: the four shares' routed parts
    equal the uncut reference layer (float32, 1e-5; there is no shared
    expert to count once); the four slices' logits side by side are the
    uncut head's; a slice's embedding rows are the uncut table's. And the
    mistake this guards against does not: the chosen weights renormalised
    over the held experts only."""
    layer = 4
    h = 0.7 * jax.random.normal(jax.random.PRNGKey(2), (24, 64), jnp.float32)
    live = jnp.ones((24,), bool)
    uncut = dict(CFG, experts_first=0, experts_held=16)
    want = ref.expert_layer(uncut, ref.layer_weights(uncut, SEED, layer), h,
                            "f32")
    whole, renormed, logits, embeds, held_pairs = 0.0, 0.0, [], [], 0
    j = swa_moe.layer_kinds(CFG)[2].index(layer)
    for share in range(4):
        cfg = dict(CFG, experts_first=4 * share, experts_held=4,
                   vocab_first=24 * share, vocab_size=24)
        p = f32(swa_moe.init_params(cfg, SEED))
        lp = {k: p["layers"][layer][k] for k in ("router_w", "router_b")}
        chosen, gates = moe.route(h, lp["router_w"], lp["router_b"], 4, 1.0)
        np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)
        y, c = moe.expert_layer(h, lp, p["experts"], live, first=4 * share,
                                held=4, k=4, offset=j * 4)
        c = dict(zip(moe.COUNTERS, np.asarray(c)))
        assert c["dropped"] == 0 and c["assignments"] == 24 * 4
        held_pairs += c["held"]
        whole = whole + y
        held = (chosen >= 4 * share) & (chosen < 4 * share + 4)
        wrong = gates / jnp.maximum(
            jnp.sum(jnp.where(held, gates, 0), -1, keepdims=True), 1e-9)
        renormed = renormed + moe.held_experts(
            h, chosen, wrong, live, p["experts"]["gate_w"],
            p["experts"]["up_w"], p["experts"]["down_w"], 4 * share, 4,
            j * 4)[0]
        model = swa_moe.SWAMoEDecodeModel(cfg, params=p)
        logits.append(model._head(p, h))
        embeds.append(p["embed"])
    assert held_pairs == 24 * 4          # every pair on exactly one share
    np.testing.assert_allclose(whole, want, atol=1e-5)
    assert float(jnp.abs(renormed - want).max()) > 1e-3
    whole_vocab = dict(CFG, vocab_size=96)
    head = ref.vocab_weights(whole_vocab, SEED, "head")
    gain = ref._draw(ref.base_key(SEED), "final_norm", (64,))
    np.testing.assert_allclose(
        jnp.concatenate(logits, axis=-1),
        ref._head(h, gain, head, eps=1e-5, precision="f32"), atol=1e-5)
    np.testing.assert_array_equal(
        jnp.concatenate(embeds), ref.vocab_weights(whole_vocab, SEED, "embed"))


def test_the_fp8_control_reads_apart_from_the_reference():
    """The reference's own lower precision moves the logits by more than the
    bfloat16 program does: what the cell's limits are set between."""
    tokens = np.random.default_rng(3).integers(0, 96, 40).astype(np.int32)
    exact = np.asarray(ref.logits(CFG, SEED, tokens))
    lower = np.asarray(ref.logits(CFG, SEED, tokens, precision="fp8"))
    assert 0.01 < np.abs(lower - exact).max() < 1.0


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
def test_slot_ring_and_pages_return_to_baseline(ending, scheduler):
    """A stream that finishes, one cancelled while it decodes and one
    cancelled in MID-PREFILL (two of its three pieces in: its rings hold
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
    are what each gets alone: the pieces' rings lie in the prompt's slot and
    the steps between them write the scratch slot's."""
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
    # what a piece's attention saw: 4 window layers x min(p + 1, 8) rows,
    # 2 global layers x (p + 1), over its live positions
    assert [(p["attn.window_rows"], p["attn.global_rows"]) for p in pieces] == [
        (4 * 15, 2 * 15), (4 * 100, 2 * 136),
        (4 * 128, 2 * sum(range(17, 33))), (4 * 64, 2 * sum(range(33, 41)))]
    names = [s["name"] for s in calls]
    i = [k for k, n in enumerate(names) if n == "decode.prefill"]
    assert "decode.step" in names[i[1] + 1:i[2]]


def test_the_step_spans_carry_what_the_caches_cost(scheduler):
    """``cache.paged_bytes`` (rows read by the step's live contexts over the
    GLOBAL layers), ``cache.state_bytes`` (the rings of its live slots) and
    the rows its attention saw on ``decode.step``; the gauges beside
    ``cache_row_bytes``; the counters of the same names."""
    engine = scheduler.engine
    obs.enable()
    try:
        obs.trace.drain()
        rows = {name: obs.metrics.registry.counter(name).value
                for name in ("attn.window_rows", "attn.global_rows")}
        prompt = np.arange(1, 12, dtype=np.int32)
        assert len(list(scheduler.generate(prompt, max_new_tokens=5))) == 5
        assert _baseline(scheduler)
        spans = obs.trace.drain()
        gauges = {name: obs.metrics.registry.gauge(name).value for name in
                  ("decode.state_bytes", "decode.paged_layers",
                   "decode.cache_row_bytes")}
        rows = {name: obs.metrics.registry.counter(name).value - was
                for name, was in rows.items()}
    finally:
        obs.disable()
    steps = [s for s in spans if s["name"] == "decode.step"]
    assert len(steps) == 4
    row, state = engine.cache_row_bytes, engine.state_bytes
    assert (row, state) == (ROW * 4, 4 * 8 * RING_ROW * 4)
    for i, s in enumerate(steps):        # contexts 12, 13, 14, 15
        assert s["args"]["cache.paged_bytes"] == (12 + i) * row * 2
        assert s["args"]["cache.state_bytes"] == 2 * state
        assert s["args"]["attn.window_rows"] == 4 * 8
        assert s["args"]["attn.global_rows"] == 2 * (12 + i)
        assert s["args"]["moe.dropped"] == 0
    assert gauges == {"decode.state_bytes": state, "decode.paged_layers": 2,
                      "decode.cache_row_bytes": row}
    # the prefill's 11 positions and the four steps'
    assert rows == {
        "attn.window_rows": 4 * (sum(range(1, 9)) + 3 * 8) + 4 * 4 * 8,
        "attn.global_rows": 2 * sum(range(1, 12)) + 2 * (12 + 13 + 14 + 15)}
