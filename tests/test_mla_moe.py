"""The latent-attention + routed-experts decoder (``models/mla_moe.py``,
``ops/moe.py``, the latent kernels of ``ops/flash_attention.py``) against
the plain reference ``benchmark/reference_mla_moe.py`` — the repo's one copy
of the equations — at tiny sizes on the CPU, Pallas kernels interpreted.

The mathematics is checked in float32 (the same bodies run on a float32
tree), where the program must agree with the reference to rounding: any
tolerance that would hide a missing term (the router's bias, the
normalisation, the routed scale, YaRN's softmax scale, the latent norm) is
too wide. The bfloat16 run is then held to a bfloat16-sized tolerance, and
the expert choices that flip on near ties are counted and printed.
"""
import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_mla_moe as ref
from mxnet_tpu.models import mla_moe
from mxnet_tpu.models import transformer
from mxnet_tpu.ops import flash_attention, moe
from mxnet_tpu.serve import DecodeEngine
from mxnet_tpu.serve.kvcache import SCRATCH_PAGE

pytestmark = pytest.mark.decode

SEED = 3000000019      # over 2**31, as the driver's are
CFG = {
    "vocab_size": 96, "hidden_size": 64, "num_layers": 3, "first_dense": 1,
    "num_heads": 4, "qk_nope": 16, "qk_rope": 8, "v_head": 16, "kv_rank": 32,
    "dense_width": 128, "expert_width": 32, "router_experts": 8,
    "experts_first": 2, "experts_held": 4, "experts_per_token": 3,
    "routed_scale": 2.5, "rms_eps": 1e-6, "max_length": 64,
    "rope": {"theta": 10000, "factor": 40,
             "original_max_position_embeddings": 16, "beta_fast": 32,
             "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}}
PAGE, SLOTS = 8, 2


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def test_config_from_the_published_keys():
    hf = {"vocab_size": 65536, "hidden_size": 4096, "num_hidden_layers": 6,
          "first_k_dense_replace": 1, "num_attention_heads": 64,
          "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
          "kv_lora_rank": 512, "intermediate_size": 16384,
          "moe_intermediate_size": 2048, "num_experts": 32,
          "num_experts_per_tok": 8, "routed_scaling_factor": 2.5,
          "rms_norm_eps": 1e-6, "rope_theta": 10000,
          "max_position_embeddings": 8192,
          "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                           "mscale": 1, "mscale_all_dim": 1,
                           "original_max_position_embeddings": 4096,
                           "type": "deepseek_yarn"}}
    cfg = mla_moe.config_from_hf(hf, router_experts=128)
    assert (cfg["experts_held"], cfg["router_experts"]) == (32, 128)
    assert cfg["kv_rank"] + cfg["qk_rope"] == 576
    # sigma = 192**-0.5 (0.1 ln 40 + 1)**2
    assert mla_moe.softmax_scale(cfg) == pytest.approx(0.1352338, rel=1e-6)
    assert ref.softmax_scale(cfg) == mla_moe.softmax_scale(cfg)
    np.testing.assert_array_equal(ref.yarn_inv_freq(cfg["rope"], 64),
                                  mla_moe.yarn_inv_freq(cfg["rope"], 64))
    # YaRN: the fastest pairs keep their frequency, the slowest are / 40
    inv = mla_moe.yarn_inv_freq(cfg["rope"], 64)
    assert inv[0] == 1.0 and inv[-1] == pytest.approx(
        10000 ** (-62 / 64) / 40, rel=1e-6)


def test_program_and_reference_make_the_same_weights():
    params = mla_moe.init_params(CFG, SEED)
    assert params["experts"]["gate_w"].dtype == jnp.bfloat16
    held = CFG["experts_held"]
    for layer in range(CFG["num_layers"]):
        w = ref.layer_weights(CFG, SEED, layer)
        stack = params["dense"] if layer < 1 else params["moe"]
        for name, value in w.items():
            if name.startswith("experts_"):   # every layer's on one axis
                got = params["experts"][name[8:]][(layer - 1) * held:
                                                  layer * held]
            else:
                got = stack[name][layer - (layer >= 1)]
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(value), name)
    for name in ("embed", "head"):
        np.testing.assert_array_equal(
            np.asarray(params[name], np.float32),
            np.asarray(ref.vocab_weights(CFG, SEED, name)))


def _generate(engine, prompts, new_tokens, monkeypatch):
    """Greedy generation through the engine's own programs, keeping the
    logits every program sampled from. Returns (tokens, logits) per prompt."""
    seen = []
    sample = transformer.sample_token

    def spy(logits, rng, temperature):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return sample(logits, rng, temperature)

    monkeypatch.setattr(transformer, "sample_token", spy)
    out = [([], []) for _ in prompts]
    tables, last = [], []
    for i, prompt in enumerate(prompts):
        bucket = engine.bucket_for(len(prompt))
        engine.pool.alloc(i, bucket // PAGE)
        tok = engine.prefill(prompt, engine.pool.table(i))
        jax.effects_barrier()
        out[i][0].append(tok)
        out[i][1].append(seen.pop()[0])
        last.append(tok)
    for step in range(1, new_tokens):
        positions = np.array([len(p) + step - 1 for p in prompts], np.int32)
        tables = np.full((SLOTS, engine.max_pages), SCRATCH_PAGE, np.int32)
        for i in range(len(prompts)):
            while len(engine.pool.table(i)) * PAGE <= positions[i]:
                engine.pool.alloc(i, 1)
            table = engine.pool.table(i)
            tables[i, :len(table)] = table
        toks = engine.step(np.array(last, np.int32), positions, tables,
                           positions + 1, np.zeros((SLOTS,), np.float32))
        jax.effects_barrier()
        logits = seen.pop()
        for i in range(len(prompts)):
            out[i][0].append(int(toks[i]))
            out[i][1].append(logits[i])
        last = [int(t) for t in toks]
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_engine_prefill_then_paged_decode_against_the_reference(
        dtype, monkeypatch):
    """(a) Prefill (expanded attention through the flash forward) and then
    paged decode (absorbed attention through the latent kernel, rows read
    from the page pool) through ``DecodeEngine``'s own two programs, against
    the reference's ONE full forward over prompt + generated ids.

    float32: agreement to 2e-4 of logits of size ~0.2 (float32 rounding
    through 3 layers; a dropped bias, scale or norm moves them by 1e-2 and
    more). bfloat16: 0.03 absolute — one bfloat16 rounding is 2**-9 of a
    value, the logits sum 64 such products after 3 layers of them; the
    float32 run above is what vouches for the mathematics."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")   # interpreted kernels
    params = mla_moe.init_params(CFG, SEED)
    if dtype == "float32":
        params = f32(params)
    model = mla_moe.MLAMoEDecodeModel(CFG, params=params)
    engine = DecodeEngine(model, slots=SLOTS, page_size=PAGE, num_pages=17,
                          prompt_buckets=[16, 32])
    # 32 + 8 values a row, in one whole lane tile
    assert engine.kv.shape == (17, 3, PAGE, 128) and engine.kv.dtype == dtype
    assert engine.cache_row_bytes == 128 * (4 if dtype == "float32" else 2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (13, 22)]
    new = 12
    out = _generate(engine, prompts, new, monkeypatch)
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
    # the counters came back with the tokens: 2 expert layers x 2 slots x 3
    c = engine.last_counters
    assert set(c) == {"moe." + name for name in moe.COUNTERS}
    assert c["moe.assignments"] == 2 * SLOTS * 3 and c["moe.dropped"] == 0
    assert 0 <= c["moe.held"] <= c["moe.assignments"]


def test_absorbed_attention_is_the_expanded_attention():
    """(b) One layer, float32: the last position of ``prefill_layer``
    (expanded: per-head keys and values made from the latent) equals
    ``decode_layer`` for that position over the cached rows (absorbed:
    queries taken into the latent's space, values the latent itself). 1e-5:
    the two forms reassociate the same float32 products."""
    params = f32(mla_moe.init_params(CFG, SEED))
    lp = {k: w[0] for k, w in params["dense"].items()}
    model = mla_moe.MLAMoEDecodeModel(CFG, params=params)
    s = 16
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (s, 64), jnp.float32)
    cos, sin = model._angles(jnp.arange(s))
    live = jnp.ones((s,), bool)
    want, rows, _ = mla_moe.prefill_layer(CFG, lp, x, cos, sin, live, None)

    def attend(query, row):          # dense softmax over the cached rows
        np.testing.assert_allclose(row[0], rows[-1], atol=1e-6)
        assert row.shape == (1, 128) and not np.asarray(row[:, 40:]).any()
        sc = mla_moe.softmax_scale(CFG) * jnp.einsum("bhr,sr->bhs", query, rows)
        return jnp.einsum("bhs,sc->bhc", jax.nn.softmax(sc, -1),
                          rows[:, :CFG["kv_rank"]])

    got, _ = mla_moe.decode_layer(CFG, lp, x[-1:], cos[-1:], sin[-1:],
                                  live[-1:], None, attend)
    np.testing.assert_allclose(got[0], want[-1], atol=1e-5)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_paged_latent_kernel_against_a_dense_gather(dtype, tol):
    """(c) The Pallas kernel (interpreted) against gather-then-attend, on a
    pool of several layers with shuffled pages, lengths that end inside a
    page, on a page boundary and at 0 (an inactive slot: finite garbage).
    bfloat16: the kernel rounds p to bfloat16 before p.c, as the flash
    kernels do; 2e-2 of values of size ~1."""
    rng = np.random.default_rng(0)
    b, h, r, dv, page, layers, pages = 4, 8, 40, 32, 8, 3, 24
    pool = jnp.asarray(rng.standard_normal((pages, layers, page, r)), dtype)
    q = jnp.asarray(rng.standard_normal((b, h, r)), dtype)
    table = jnp.asarray(rng.permutation(np.arange(1, pages))[:b * 5]
                        .reshape(b, 5), jnp.int32)
    lengths = jnp.asarray([37, 16, 0, 1], jnp.int32)
    for layer in (0, 2):
        got = flash_attention.flash_latent_decode_attention(
            q, pool, layer, table, lengths, dv, 0.3, interpret=True)
        want = flash_attention._latent_decode_attention_xla(
            q, pool, layer, table, lengths, dv, 0.3)
        assert got.shape == (b, h, dv) and np.isfinite(np.asarray(
            got, np.float32)).all()
        live = np.asarray(lengths) > 0
        np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                                   np.asarray(want, np.float32)[live],
                                   atol=tol)


def test_flash_forward_with_narrower_values():
    """The flash forward with d_qk 24 and d_v 16 against dense softmax."""
    rng = np.random.default_rng(1)
    q, k = (jnp.asarray(rng.standard_normal((1, 2, 32, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), jnp.float32)
    got = flash_attention.flash_attention(q, k, v, causal=True, scale=0.2,
                                          block_q=16, block_k=8)
    sc = 0.2 * jnp.einsum("bhqd,bhkd->bhqk", q, k)
    sc = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), sc, -jnp.inf)
    want = jnp.einsum("bhqk,bhkv->bhqv", jax.nn.softmax(sc, -1), v)
    np.testing.assert_allclose(got, want, atol=1e-5)


def _share_cfg(first):
    return dict(CFG, experts_first=first, experts_held=2)


def _expert_layer_params(cfg, layer):
    """(the layer's own leaves, every layer's experts, this layer's offset
    among them), float32."""
    params = f32(mla_moe.init_params(cfg, SEED))
    j = layer - cfg["first_dense"]
    return ({k: w[j] for k, w in params["moe"].items()}, params["experts"],
            j * cfg["experts_held"])


def test_the_shares_add_up_to_the_uncut_layer():
    """(d) 8 experts over 4 shares of 2: the four shares' routed parts plus
    the shared expert counted ONCE equal the uncut reference layer (float32,
    1e-5). And the two mistakes this guards against do not: the shared
    expert counted per share, gates normalised over the held experts only.
    Layer 2's experts lie behind layer 1's in the one array: ``offset``."""
    layer = 2
    h = 0.7 * jax.random.normal(jax.random.PRNGKey(2), (24, 64), jnp.float32)
    live = jnp.ones((24,), bool)
    uncut = dict(CFG, experts_first=0, experts_held=8)
    want = ref.expert_layer(uncut, ref.layer_weights(uncut, SEED, layer), h,
                            "f32")
    routed, whole, renormed = 0.0, 0.0, 0.0
    for first in (0, 2, 4, 6):
        p, experts, offset = _expert_layer_params(_share_cfg(first), layer)
        assert offset == 2
        chosen, gates = moe.route(h, p["router_w"], p["router_b"], 3, 2.5)
        w = (experts["gate_w"], experts["up_w"], experts["down_w"])
        y, c = moe.held_experts(h, chosen, gates, live, *w, first, 2, offset)
        routed = routed + y
        y_layer, _ = moe.expert_layer(h, p, experts, live, first=first, held=2,
                                      k=3, scale=2.5, offset=offset)
        whole = whole + y_layer
        held = (chosen >= first) & (chosen < first + 2)
        wrong = 2.5 * gates / jnp.maximum(
            jnp.sum(jnp.where(held, gates, 0), -1, keepdims=True), 1e-9)
        renormed = renormed + moe.held_experts(h, chosen, wrong, live, *w,
                                               first, 2, offset)[0]
        assert int(c[moe.COUNTERS.index("dropped")]) == 0
    shared = moe.gated_mlp(h, p["shared_gate_w"], p["shared_up_w"],
                           p["shared_down_w"])
    np.testing.assert_allclose(routed + shared, want, atol=1e-5)
    np.testing.assert_allclose(whole - 3 * shared, want, atol=1e-5)
    assert float(jnp.abs(whole - want).max()) > 1e-3        # shared x 4
    assert float(jnp.abs(renormed + shared - want).max()) > 1e-3


def test_no_token_is_dropped_when_every_token_picks_one_expert():
    """(e) A bias of +10 on held expert 3 sends all 40 tokens to it (and a
    bias never weighs: the gates are still from the scores): its group is
    the whole batch, nothing is dropped, the result is the reference's."""
    layer, t = 2, 40
    w = ref.layer_weights(CFG, SEED, layer)
    w["router_b"] = w["router_b"].at[3].add(10.0)
    h = 0.7 * jax.random.normal(jax.random.PRNGKey(3), (t, 64), jnp.float32)
    want = ref.expert_layer(CFG, w, h, "f32")
    p, experts, offset = _expert_layer_params(CFG, layer)
    p["router_b"] = p["router_b"].at[3].add(10.0)
    live = jnp.ones((t,), bool).at[-4:].set(False)   # 4 pad positions
    y, c = moe.expert_layer(h, p, experts, live, first=2, held=4, k=3,
                            scale=2.5, offset=offset)
    c = dict(zip(moe.COUNTERS, (int(v) for v in c)))
    assert c["assignments"] == 36 * 3 and c["load_max"] == 36
    assert c["dropped"] == 0 and 36 <= c["held"] <= 36 * 3
    np.testing.assert_allclose(y[:36], want[:36], atol=1e-5)
    # pad positions are routed nowhere: only the shared expert answers
    shared = moe.gated_mlp(h, p["shared_gate_w"], p["shared_up_w"],
                           p["shared_down_w"])
    np.testing.assert_allclose(y[36:], shared[36:], atol=1e-6)


def _loaded_pairs(load, t, k, held, first, tm):
    """(chosen (t, k), live (t,)) that put the held experts' rows where the
    slots (of ``tm`` rows) of ``held_experts`` have to decide: ``first .. first + held - 1``
    are held, experts ``0 .. first - 1`` are not."""
    chosen = np.zeros((t, k), np.int32)           # expert 0: held elsewhere
    flat = chosen.reshape(-1)
    live = np.ones((t,), bool)
    if load in ("one_expert", "two_products"):    # every pair on ONE expert
        chosen[:] = first + 1
    elif load == "exactly_tm":        # whole tiles: nothing is padded
        flat[:tm] = first
        flat[tm:3 * tm] = first + 2
    elif load == "tm_plus_1":         # one row into a second tile; a lone row
        flat[:tm + 1] = first
        flat[-1] = first + 2
    elif load == "most_tiles":        # every pair held, every expert one row
        sizes = [1 + tm * ((t * k - held) // tm // held)] * (held - 1)
        flat[:] = first + held - 1                # into a tile of its own
        flat[:sum(sizes)] = first + np.repeat(np.arange(held - 1), sizes)
        flat[:] = flat[np.random.default_rng(0).permutation(t * k)]
    elif load == "partly_live":
        chosen[:] = (np.arange(t * k).reshape(t, k) * 7) % (first + held)
        live = np.arange(t) % 3 != 1
    else:
        assert load == "no_held_pair"
    return jnp.asarray(chosen), jnp.asarray(live)


@pytest.mark.parametrize("case", [
    "TOKEN_CHUNK-16",
    # tokens, k (10 is not a whole sublane tile, 8 is), load[, the experts
    # the router is said to score: 64 unless given]
    "32-10-one_expert", "32-10-no_held_pair", "32-10-exactly_tm",
    "32-10-tm_plus_1", "32-10-most_tiles", "32-10-partly_live",
    "32-10-two_products",
    "32-8-one_expert", "32-8-no_held_pair", "32-8-exactly_tm",
    "32-8-tm_plus_1", "32-8-most_tiles", "32-8-partly_live",
    "200-10-exactly_tm", "200-10-tm_plus_1", "200-10-most_tiles",
    "200-8-partly_live", "200-8-two_products",
    # a decode step's few rows an expert: slots smaller than the kernel's tile
    "32-10-partly_live-512", "32-10-most_tiles-512", "32-8-tm_plus_1-256"])
def test_expert_layer_in_chunks_and_row_blocks_is_the_layer(case, monkeypatch):
    """Tokens routed ``TOKEN_CHUNK`` at a time give the same sum and the
    same counters. And the sorted rows laid out in slots of ``row_slot``
    rows, every held expert's on slots of its own, and multiplied a block
    at a time, as many blocks as hold such a slot, give the reference's
    plain masked loop (float32, ``benchmark/reference_mla_moe.py``) with
    nothing dropped and ``rows_run`` = the slots the held experts have x the
    slot, for the loads that decide layout and loop: every pair on one held
    expert (every slot is full), none on a held expert (no block runs, ``y``
    exactly 0), an expert with exactly a slot of rows and with one more, a
    lone row, every pair on a held expert and every expert a row into a
    slot of its own (the most slots the buffer's bound allows), tokens that
    are not live, experts of two products; and slots of 2 and 4 rows, under
    the kernel's row tile, as a decode step has them."""
    if case == "TOKEN_CHUNK-16":
        p, experts, offset = _expert_layer_params(CFG, 1)
        h = 0.7 * jax.random.normal(jax.random.PRNGKey(4), (48, 64),
                                    jnp.float32)
        live = jnp.arange(48) < 41
        args = dict(first=2, held=4, k=3, scale=2.5, offset=offset)
        want, c_want = moe.expert_layer(h, p, experts, live, **args)
        monkeypatch.setattr(moe, "TOKEN_CHUNK", 16)
        got, c_got = moe.expert_layer(h, p, experts, live, **args)
        np.testing.assert_allclose(got, want, atol=1e-6)
        c_want, c_got = (dict(zip(moe.COUNTERS, np.asarray(c).tolist()))
                         for c in (c_want, c_got))
        for name in ("assignments", "held", "dropped"):
            assert c_got[name] == c_want[name]
        assert c_got["load_max"] <= c_want["load_max"]
        assert c_got["rows_run"] >= c_got["held"]
        return
    t, k, load, scored = (case.split("-") + ["64"])[:4]
    t, k, first, held, d, f, scored = int(t), int(k), 3, 5, 64, 32, int(scored)
    tm = moe.row_slot(t * k, scored)
    assert tm == {(320, 64): 16, (256, 64): 8, (2000, 64): 64,
                  (1600, 64): 64, (320, 512): 2, (256, 256): 4}[t * k, scored]
    assert moe.row_tile(t * k, scored, jnp.float32) == max(tm, 8)
    chosen, live = _loaded_pairs(load, t, k, held, first, tm)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    h = 0.7 * jax.random.normal(keys[0], (t, d), jnp.float32)
    gates = jax.random.uniform(keys[1], (t, k), jnp.float32, 0.1, 1.0)
    w = {name: 0.2 * jax.random.normal(key, (held,) + shape, jnp.float32)
         for name, key, shape in (("experts_gate_w", keys[2], (d, f)),
                                  ("experts_up_w", keys[3], (d, f)),
                                  ("experts_down_w", keys[4], (f, d)))}
    # the reference's loop over the same pairs: its router is stood in for
    monkeypatch.setattr(ref, "route", lambda *_: (None, chosen, gates))
    if load == "two_products":
        monkeypatch.setattr(
            ref, "gated_mlp", lambda h, gate, up, down, precision:
            jnp.square(jax.nn.relu(h @ up)) @ down)
    want = ref.expert_layer({}, w, h, "f32", held=(first, held), shared=False)
    want = jnp.where(live[:, None], want, 0.0)
    # the held experts' groups lie behind another layer's in the one array
    stacked = [jnp.concatenate([jnp.zeros_like(w[name]), w[name]])
               for name in ("experts_gate_w", "experts_up_w",
                            "experts_down_w")]
    if load == "two_products":
        stacked[0] = None
    y, counted = moe.held_experts(h, chosen, gates, live, *stacked, first,
                                  held, held, scored)
    counted = dict(zip(moe.COUNTERS, np.asarray(counted).tolist()))
    on_held = np.asarray((chosen >= first) & live[:, None])
    sizes = np.bincount(np.asarray(chosen)[on_held] - first, minlength=held)
    tiles = -(-sizes // tm)
    assert counted["held"] == on_held.sum() and counted["dropped"] == 0
    assert counted["load_max"] == sizes.max()
    assert counted["touched"] == (sizes > 0).sum()
    assert counted["rows_run"] == tiles.sum() * tm
    assert tiles.sum() == {
        "one_expert": -(-t * k // tm), "two_products": -(-t * k // tm),
        "no_held_pair": 0, "exactly_tm": 3, "tm_plus_1": 3,
        "most_tiles": (t * k - held) // tm + held}.get(load, tiles.sum())
    if load == "exactly_tm":
        assert counted["rows_run"] == counted["held"]
    if load == "no_held_pair":
        assert not np.asarray(y).any()
    else:
        assert float(jnp.abs(want).max()) > 0.1
    # (relative where relu^2 of eight summed gates reads 6 and more)
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)


def test_the_scheduler_hangs_the_models_counters_on_its_spans():
    """What the expert layers counted comes back with the tokens and lands
    on the ``decode.prefill`` / ``decode.step`` spans, in the counters of
    the same names, in ``stats()["counted"]`` (kept with obs off too), and
    ``decode.cache_row_bytes`` is a gauge. The per-head model's spans carry
    no such attribute (``tests/test_decode_spans.py`` holds their keys)."""
    from mxnet_tpu import obs
    from mxnet_tpu.serve import DecodeScheduler

    model = mla_moe.MLAMoEDecodeModel(CFG, seed=SEED)
    engine = DecodeEngine(model, slots=SLOTS, page_size=PAGE, num_pages=17,
                          prompt_buckets=[16])
    obs.enable()
    try:
        sched = DecodeScheduler(engine)
        try:
            tokens = list(sched.generate(list(range(1, 12)), max_new_tokens=4))
        finally:
            sched.close()
        spans = obs.trace.drain()
        gauges = obs.metrics.registry.snapshot()
    finally:
        obs.disable()
        obs.reset()
    assert len(tokens) == 4
    prefill = [s for s in spans if s["name"] == "decode.prefill"]
    steps = [s for s in spans if s["name"] == "decode.step"]
    assert len(prefill) == 1 and len(steps) == 3
    keys = {"moe." + name for name in moe.COUNTERS}
    for s in prefill + steps:
        assert keys <= set(s["args"]) and s["args"]["moe.dropped"] == 0
    # 11 live prompt positions x 3 choices x 2 expert layers; a step: 1 slot
    assert prefill[0]["args"]["moe.assignments"] == 11 * 3 * 2
    assert all(s["args"]["moe.assignments"] == 3 * 2 for s in steps)
    counted = sched.stats()["counted"]
    assert counted["moe.assignments"] == 11 * 6 + 3 * 6
    assert counted["moe.held"] == sum(s["args"]["moe.held"]
                                      for s in prefill + steps)
    assert counted["moe.dropped"] == 0
    assert counted["moe.rows_run"] == sum(
        s["args"]["moe.rows_run"] for s in prefill + steps)
    assert counted["moe.rows_run"] >= counted["moe.held"] > 0
    # the row tile those rows were laid out in, from the shapes alone: 2
    # slots, and 16 prompt positions, x 3 choices over 8 experts, bfloat16
    assert engine.stats()["moe_row_tile"] == {"step": 16, "prefill": {16: 16}}
    # in slots of 16 rows over the prompt, of 4 in a step (under its tile)
    assert (moe.row_slot(16 * 3, 8), moe.row_slot(2 * 3, 8)) == (16, 4)
    assert counted["moe.rows_run"] % 4 == 0
    flat = json.dumps(gauges)
    assert "decode.cache_row_bytes" in flat and "moe.assignments" in flat


def test_counters_sit_on_the_step_that_produced_them():
    """With the next step launched before the last one is read, a result's
    counters still land on the span of the call that produced them: two
    streams of 6 and 3 tokens, so steps over two slots and then over one,
    and every ``decode.step`` reads 3 choices x 2 expert layers for each
    slot IT stepped. The totals take in the last step in flight."""
    from mxnet_tpu import obs
    from mxnet_tpu.serve import DecodeScheduler

    model = mla_moe.MLAMoEDecodeModel(CFG, seed=SEED)
    engine = DecodeEngine(model, slots=SLOTS, page_size=PAGE, num_pages=17,
                          prompt_buckets=[16])
    obs.enable()
    try:
        sched = DecodeScheduler(engine)
        try:
            handles = [sched.submit(list(range(1, 12)), max_new_tokens=6),
                       sched.submit(list(range(3, 9)), max_new_tokens=3)]
            assert sched.drain(timeout=60)
            stats = sched.stats()
        finally:
            sched.close()
        spans = obs.trace.drain()
    finally:
        obs.disable()
        obs.reset()
    assert stats["tokens_out"] == 9 and stats["dropped_speculative"] == 0
    assert handles[0].get(timeout=1)[0] == "token"
    prefills = [s["args"] for s in spans if s["name"] == "decode.prefill"]
    steps = [s["args"] for s in spans if s["name"] == "decode.step"]
    assert sorted(p["moe.assignments"] for p in prefills) == [6 * 6, 11 * 6]
    assert {s["active"] for s in steps} == {1, 2}
    assert sum(s["active"] for s in steps) == 5 + 2
    for s in steps:
        assert s["moe.assignments"] == 6 * s["active"]
    assert stats["steps_launched"] == len(steps)
    assert stats["launched_ahead"] == sum(s["ahead"] for s in steps) > 0
    for name in ("moe.assignments", "moe.held", "moe.dropped"):
        assert stats["counted"][name] == sum(s[name]
                                             for s in prefills + steps)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_expert_choices_that_flip_on_near_ties_are_counted(dtype, monkeypatch):
    """The router runs in float32 in the program, on activations that are
    bfloat16: a token whose 3rd and 4th scores nearly tie can choose another
    expert than the float32 reference does. Counted here over 40 positions x
    2 expert layers, and printed: none in float32 (the same choices, or the
    mathematics differs), a few in bfloat16 — a flipped choice swaps one
    expert for one of nearly the same score, which is inside what the logit
    tolerance of the engine test allows."""
    chosen = []
    route = moe.route

    def spy(*args):
        out = route(*args)
        jax.debug.callback(lambda c: chosen.append(np.asarray(c)), out[0],
                           ordered=True)
        return out

    monkeypatch.setattr(moe, "route", spy)
    params = mla_moe.init_params(CFG, SEED)
    if dtype == "float32":
        params = f32(params)
    model = mla_moe.MLAMoEDecodeModel(CFG, params=params)
    tokens = np.random.default_rng(5).integers(0, 96, 40).astype(np.int32)
    jax.jit(model.prefill)(params, jnp.asarray(tokens)[None], 40)
    jax.effects_barrier()
    assert len(chosen) == 2 and chosen[0].shape == (40, 3)

    x = ref.vocab_weights(CFG, SEED, "embed")[tokens]
    flips = 0
    for layer in range(CFG["num_layers"]):
        w = ref.layer_weights(CFG, SEED, layer)
        if layer >= CFG["first_dense"]:
            h = ref.rms_norm(x, w["attn_norm"], CFG["rms_eps"])
            after = x + ref.attention(CFG, w, h, "f32")
            _, want, _ = ref.route(CFG, w, ref.rms_norm(
                after, w["mlp_norm"], CFG["rms_eps"]), "f32")
            got = chosen[layer - CFG["first_dense"]]
            flips += int((np.sort(got, 1) != np.sort(np.asarray(want), 1))
                         .any(axis=1).sum())
        x = ref.layer_forward(CFG, w, x, layer)
    print(f"{dtype}: {flips} of 80 (position, layer) choices differ from "
          "the float32 reference's")
    assert flips == 0 if dtype == "float32" else flips <= 16


# -- a prompt continued from a position ----------------------------------------------

@pytest.mark.parametrize("start", [0, 32, 96])
@pytest.mark.parametrize("blocks", [(32, 16), (16, 32), (8, 8)])
def test_continued_latent_flash_forward_is_the_rows_of_the_whole(start, blocks):
    """Query rows ``start .. start + 32`` with 24-wide keys (16 expanded + 8
    rotary) and 16-wide values, expanded inside the kernel from the cached
    rows: the rows the whole flash forward gives at those positions over the
    same keys and values — the same blocks in the same order, so bit for bit;
    latents and expansion weights are small integers, so that the expansion
    is exact however its sums are ordered — and dense causal attention. What
    lies behind the piece in the rows (NaN) is never looked at."""
    h, total, c, nope, rope, dv, rank, width = 3, 128, 32, 16, 8, 16, 32, 128
    rng = np.random.default_rng(7)
    q = rng.normal(size=(h, total, nope + rope)).astype(np.float32)
    rows = np.zeros((total, width), np.float32)
    rows[:, :rank] = rng.integers(-2, 3, (total, rank))
    rows[:, rank:rank + rope] = rng.normal(size=(total, rope))
    uk_w = rng.integers(-1, 2, (h, nope, rank)).astype(np.float32)
    uv_w = rng.integers(-1, 2, (h, rank, dv)).astype(np.float32)
    k = np.concatenate([np.einsum("sc,hnc->hsn", rows[:, :rank], uk_w),
                        np.broadcast_to(rows[None, :, rank:rank + rope],
                                        (h, total, rope))], axis=-1)
    v = np.einsum("sc,hcv->hsv", rows[:, :rank], uv_w)
    whole = flash_attention.flash_attention(
        jnp.asarray(q)[None], jnp.asarray(k)[None], jnp.asarray(v)[None],
        causal=True, scale=0.05, block_q=blocks[0], block_k=blocks[1])[0]
    behind = np.arange(total)[:, None] >= start + c
    got = flash_attention.latent_flash_attention_from(
        jnp.asarray(q[:, start:start + c]),
        jnp.asarray(np.where(behind, np.nan, rows)), jnp.asarray(uk_w),
        jnp.asarray(uv_w), jnp.int32(start), 0.05, block_q=blocks[0],
        block_k=blocks[1])
    np.testing.assert_array_equal(got, whole[:, start:start + c])
    sc = 0.05 * np.einsum("hqd,hkd->hqk", q[:, start:start + c], k)
    sc = np.where(start + np.arange(c)[:, None] >= np.arange(total)[None], sc,
                  -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("hqk,hkv->hqv", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(got, want, atol=1e-5)


def _in_pieces(model, tokens, length, piece):
    """``prefill_from`` over tokens (1, n x piece) a piece at a time, the rows
    carried as the engine carries them; the rows' array holds NaN before the
    first piece and behind every piece. (logits, rows) as ``prefill``."""
    total = tokens.shape[1]
    pool = jnp.full((model.layers, total) + model.cache_row, np.nan,
                    model.cache_dtype)

    @jax.jit
    def one(tokens, start, pool):
        return model.prefill_from(model.params, tokens, start, length,
                                  lambda layer: pool[layer], {})

    for start in range(0, total, piece):
        logits, rows, _, state = one(tokens[:, start:start + piece],
                                     jnp.int32(start), pool)
        assert state == {}
        if start + piece < length:      # the head runs in the last piece only
            assert not np.asarray(logits).any()
        pool = pool.at[:, start:start + piece].set(rows)
    return logits, pool


@pytest.mark.parametrize("piece,length,dtype", [
    (16, 41, "float32"),      # an odd last piece: 9 of its 16 positions live
    (16, 48, "float32"),      # the last piece full
    (32, 50, "float32"),
    (16, 7, "float32"),       # one piece: start 0 alone
    (16, 41, "bfloat16"),
])
def test_a_prompt_in_pieces_is_the_prompt_whole(piece, length, dtype):
    """Logits and every cached row of a prompt fed in pieces of 16 and of 32
    — attention over the rows before the piece, expanded again — against
    ``prefill`` over the whole prompt (float32: 2e-5, two orders of blocks
    over the same float32 products; bfloat16: the engine test's 0.03) and
    against the reference's one forward (2e-4, as the engine test), whatever
    (NaN) the rows' array held before the first piece and behind each."""
    params = mla_moe.init_params(CFG, SEED)
    if dtype == "float32":
        params = f32(params)
    model = mla_moe.MLAMoEDecodeModel(CFG, params=params)
    total = -(-length // piece) * piece
    tokens = np.zeros((1, total), np.int32)
    tokens[0, :length] = np.random.default_rng(length).integers(
        0, CFG["vocab_size"], length)
    want_logits, want_rows, _ = jax.jit(model.prefill)(model.params, tokens,
                                                       length)
    logits, rows = _in_pieces(model, jnp.asarray(tokens), length, piece)
    tol = 2e-5 if dtype == "float32" else 0.03
    got, want = (np.asarray(a, np.float32) for a in (rows, want_rows))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[:, :length], want[:, :length], atol=tol)
    np.testing.assert_allclose(logits, want_logits, atol=tol)
    reference = np.asarray(ref.logits(CFG, SEED, tokens[0, :length]))[-1]
    np.testing.assert_allclose(logits, reference,
                               atol=2e-4 if dtype == "float32" else 0.03)


@pytest.fixture
def scheduler():
    """Pieces of 16, prompts up to 48, float32 (tokens compared exactly)."""
    from mxnet_tpu.serve import DecodeScheduler

    model = mla_moe.MLAMoEDecodeModel(
        CFG, params=f32(mla_moe.init_params(CFG, SEED)))
    engine = DecodeEngine(model, slots=SLOTS, page_size=PAGE, num_pages=17,
                          prompt_buckets=[16, 32, 48])
    sched = DecodeScheduler(engine, max_queue=8, default_timeout=60.0)
    yield sched
    sched.close()


def _baseline(sched):
    """No page, no slot and nothing in flight."""
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        s = sched.stats()
        if not (s["active"] or s["queued"] or s["engine"]["pool"]["used"]):
            return all(g is None for g in sched._slots)
        time.sleep(0.01)
    return False


def _events(handle):
    events = []
    while not events or events[-1][0] == "token":
        events.append(handle.get(timeout=60))
    return events


def test_a_neighbour_prefilling_in_pieces_does_not_move_a_streams_tokens(
        scheduler):
    """The engine feeds this model pieces of its smallest bucket (16): a
    41-token prompt goes in three, one a turn, in front of the steps of the
    stream that is decoding beside it — whose tokens, and the prompt's own,
    are what each gets alone; one ``decode.prefill`` span a piece, pieces ÷
    admissions as reckoned, two programs in all."""
    from mxnet_tpu import obs

    engine = scheduler.engine
    assert engine.prefill_piece == 16 and engine.buckets == [16]
    assert engine.stats()["max_prompt"] == 48
    first = np.arange(7, 12, dtype=np.int32)
    second = np.arange(40, 81, dtype=np.int32)
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
        got_b = _events(b)
        got_a += _events(a)
        assert _baseline(scheduler)
        spans = obs.trace.drain()
    finally:
        obs.disable()
        obs.reset()
    assert [ev[1] for ev in got_a[:-1]] == alone[0]
    assert [ev[1] for ev in got_b[:-1]] == alone[1]
    st = scheduler.stats()
    assert st["prefill_piece"] == 16
    assert st["admitted"] - before["admitted"] == 2
    assert st["prefill_pieces"] - before["prefill_pieces"] == 1 + 3
    assert engine.stats()["num_programs"] == 2
    calls = sorted((s for s in spans
                    if s["name"] in ("decode.prefill", "decode.step")),
                   key=lambda s: s["ts"])
    pieces = [s["args"] for s in calls if s["name"] == "decode.prefill"]
    assert [(p["prompt_len"], p["start"], p["pieces"], p["bucket"])
            for p in pieces] == [(5, 0, 1, 16)] + [(41, s, 3, 16)
                                                   for s in (0, 16, 32)]
    assert all(p["moe.dropped"] == 0 for p in pieces)
    # 3 choices x 2 expert layers for every LIVE position of a piece
    assert [p["moe.assignments"] for p in pieces] == [30, 96, 96, 54]
    # a step between the prompt's pieces: A did not wait for all three
    names = [s["name"] for s in calls]
    i = [k for k, n in enumerate(names) if n == "decode.prefill"]
    assert "decode.step" in names[i[1] + 1:i[2]]
    assert "decode.step" in names[i[2] + 1:i[3]]


def test_slot_and_pages_come_back_after_a_cancel_in_mid_prefill(scheduler,
                                                                monkeypatch):
    """A caller that hangs up after the first of its prompt's three pieces:
    the other two are never launched, slot and pages come back, the stream
    beside it gets the tokens it gets alone, and the slot — whose pages now
    hold a third of a prompt — serves the next request as a fresh engine."""
    engine = scheduler.engine
    beside = np.arange(7, 12, dtype=np.int32)
    prompt = np.arange(30, 71, dtype=np.int32)
    alone = list(scheduler.generate(beside, max_new_tokens=20))
    after = list(scheduler.generate(prompt[:20], max_new_tokens=5))
    launch, starts, handle = engine.launch_prefill, [], []

    def hang_up_after_the_first_piece(tokens, page_ids, **kw):
        launched = launch(tokens, page_ids, **kw)
        if len(tokens) == len(prompt):
            starts.append(kw["start"])
            handle[0].cancel()
        return launched

    a = scheduler.submit(beside, max_new_tokens=20)
    assert a.get(timeout=60)[0] == "token"
    monkeypatch.setattr(engine, "launch_prefill",
                        hang_up_after_the_first_piece)
    handle.append(scheduler.submit(prompt, max_new_tokens=5))
    assert _events(handle[0])[-1] == ("end", "cancelled", 0)
    assert starts == [0]
    assert [ev[1] for ev in [("token", alone[0])] + _events(a)[:-1]] == alone
    assert _baseline(scheduler)
    assert scheduler.stats()["cancelled"] == 1
    assert list(scheduler.generate(prompt[:20], max_new_tokens=5)) == after
    assert _baseline(scheduler)
