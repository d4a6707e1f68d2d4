"""The shortcut-connected double-layer decoder (``models/mla_scmoe.py``: two
latent-attention sub-layers and two dense MLPs around one expert branch with
identity experts, ``ops/moe.py``) against the plain reference
``benchmark/reference_mla_scmoe.py`` at tiny sizes on the CPU, Pallas kernels
interpreted.

As in ``tests/test_mla_moe.py`` the mathematics is checked in float32 (the
same bodies run on a float32 tree), where the program must agree with the
reference to rounding: any tolerance that would hide a missing term (the
query latent's norm, either latent scale, the choosing bias, the routed
scale, a renormalisation that must NOT be there, the identity experts' term,
the shortcut's place) is too wide. The bfloat16 run is then held to a
bfloat16-sized tolerance.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_mla_scmoe as ref
from mxnet_tpu.models import mla_moe, mla_scmoe, transformer
from mxnet_tpu.ops import moe
from mxnet_tpu.serve import DecodeEngine
from mxnet_tpu.serve.kvcache import SCRATCH_PAGE

pytestmark = pytest.mark.decode

SEED = 3000000019      # over 2**31, as the driver's are
# 12 real experts (4 held here, from the third) + 6 identity experts, top-4
CFG = {
    "vocab_size": 96, "hidden_size": 64, "num_layers": 2, "num_heads": 4,
    "qk_nope": 16, "qk_rope": 8, "v_head": 16, "kv_rank": 32, "q_rank": 48,
    "latent_scales": True, "dense_width": 128, "expert_width": 32,
    "router_experts": 18, "zero_experts": 6, "experts_first": 2,
    "experts_held": 4, "experts_per_token": 4, "routed_scale": 6.0,
    "rms_eps": 1e-5, "max_length": 64,
    "rope": {"theta": 10000000, "factor": 1}}
REAL = CFG["router_experts"] - CFG["zero_experts"]
PAGE, SLOTS = 8, 2
# the catalog row's config keys (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12}


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _count(tree):
    return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))


def test_config_from_the_published_keys():
    cfg = mla_scmoe.config_from_hf(PUBLISHED)
    assert (cfg["router_experts"], cfg["zero_experts"]) == (768, 256)
    assert (cfg["experts_held"], cfg["experts_per_token"]) == (512, 12)
    assert cfg["kv_rank"] + cfg["qk_rope"] == 576 and cfg["q_rank"] == 1536
    assert mla_moe.cache_row_width(cfg) == 640
    # no rope_scaling: the published frequencies, sigma = 192**-0.5
    assert mla_moe.softmax_scale(cfg) == ref.softmax_scale(cfg) == 192 ** -0.5
    inv = mla_moe.yarn_inv_freq(cfg["rope"], 64)
    np.testing.assert_array_equal(inv, ref.inv_freq(cfg))
    assert inv[0] == 1.0 and inv[-1] == pytest.approx(1e7 ** (-62 / 64),
                                                      rel=1e-6)
    # the benchmark's cut: 16 of 512 real experts, 4 of 28 double layers, an
    # eighth of the vocabulary; the router keeps its 768 outputs
    cut = mla_scmoe.config_from_hf(
        dict(PUBLISHED, num_layers=4, n_routed_experts=16, vocab_size=16384,
             max_position_embeddings=2048), real_experts=512)
    assert (cut["router_experts"], cut["experts_held"]) == (768, 16)
    shapes = jax.eval_shape(lambda: mla_scmoe.init_params(cut, 0))
    per_double = _count({k: v for k, v in shapes["sub"].items()}) // 4 + (
        _count(shapes["router"]) // 4)
    assert per_double == 638_874_368   # 2 x (90.57 M + 226.49 M + norms) + router
    assert _count(shapes) == 5_172_749_312  # the configuration file's count
    with pytest.raises(NotImplementedError):
        mla_scmoe.config_from_hf(dict(PUBLISHED, zero_expert_type="copy"))


def test_program_and_reference_make_the_same_weights():
    params = mla_scmoe.init_params(CFG, SEED)
    assert params["experts"]["gate_w"].dtype == jnp.bfloat16
    assert params["sub"]["gate_w"].shape == (2, 2, 64, 128)
    held = CFG["experts_held"]
    for layer in range(CFG["num_layers"]):
        w = ref.layer_weights(CFG, SEED, layer)
        for i in (0, 1):
            for name, value in w["sub"][i].items():
                np.testing.assert_array_equal(
                    np.asarray(params["sub"][name][layer, i], np.float32),
                    np.asarray(value), f"{name} {layer}.{i}")
        for name in ("router_w", "router_b"):
            np.testing.assert_array_equal(
                np.asarray(params["router"][name][layer], np.float32),
                np.asarray(w[name]), name)
        for name in ("gate_w", "up_w", "down_w"):
            np.testing.assert_array_equal(
                np.asarray(params["experts"][name][layer * held:
                                                   (layer + 1) * held],
                           np.float32),
                np.asarray(w["experts_" + name]), name)
    # the two sub-layers of a double layer differ, as do two layers
    assert float(jnp.abs(params["sub"]["o_w"][0, 0].astype(jnp.float32)
                         - params["sub"]["o_w"][0, 1].astype(jnp.float32)
                         ).max()) > 0.01
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
    last = []
    for i, prompt in enumerate(prompts):
        engine.pool.alloc(i, engine.bucket_for(len(prompt)) // PAGE)
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
    """Prefill (expanded attention through the flash forward, the double
    layers under one scan) and then paged decode (absorbed attention through
    the latent kernel, rows read from a pool of 2 x 2 layers) through
    ``DecodeEngine``'s own two programs, against the reference's ONE full
    forward over prompt + generated ids.

    float32: agreement to 1e-5 of logits of size ~0.2 (reads 1.2e-7:
    float32 rounding through 2 double layers = 4 attentions, 4 MLPs and 2
    expert branches; a dropped scale, norm, bias or identity term moves them
    by 1e-3 and more — the later tests show by how much). bfloat16: 0.03
    absolute, as ``tests/test_mla_moe.py`` (reads 0.0027) — one bfloat16
    rounding is 2**-9 of a value and the logits sum 64 such products; the
    float32 run is what vouches for the mathematics."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")   # interpreted kernels
    params = mla_scmoe.init_params(CFG, SEED)
    if dtype == "float32":
        params = f32(params)
    model = mla_scmoe.MLAScMoEDecodeModel(CFG, params=params)
    engine = DecodeEngine(model, slots=SLOTS, page_size=PAGE, num_pages=17,
                          prompt_buckets=[16, 32])
    # a pool layer a SUB-layer: 2 x 2; 32 + 8 values a row in one lane tile
    assert model.layers == 4 and CFG["num_layers"] == 2
    assert engine.kv.shape == (17, 4, PAGE, 128) and engine.kv.dtype == dtype
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (13, 22)]
    new = 12
    out = _generate(engine, prompts, new, monkeypatch)
    tol = 1e-5 if dtype == "float32" else 0.03
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
    # the counters came back with the tokens: 2 expert layers x 2 slots x 4
    c = engine.last_counters
    assert tuple(c) == tuple("moe." + name for name in moe.ZERO_COUNTERS)
    assert c["moe.assignments"] == 2 * SLOTS * 4 and c["moe.dropped"] == 0
    assert 0 <= c["moe.held"] + c["moe.zero"] <= c["moe.assignments"]


def test_absorbed_attention_is_the_expanded_attention_with_a_query_latent():
    """One sub-layer, float32, the query latent and both latent scales on:
    the last position of ``prefill_attention`` (expanded) equals
    ``decode_attention`` for that position over the cached rows (absorbed).
    The cached row holds c AFTER norm and scale — (64/32)^0.5 here — which is
    why both forms hold the same numbers. 1e-5: the two forms reassociate
    the same float32 products. And the scales are really on: without them
    the result differs by far more."""
    params = f32(mla_scmoe.init_params(CFG, SEED))
    lp = {k: w[1, 0] for k, w in params["sub"].items()}
    model = mla_scmoe.MLAScMoEDecodeModel(CFG, params=params)
    s = 16
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (s, 64), jnp.float32)
    cos, sin = model._angles(jnp.arange(s))
    want, rows = mla_moe.prefill_attention(CFG, lp, x, cos, sin)
    # the row's latent is normed (gain ~1) and scaled: rms 2^0.5
    rms = float(jnp.sqrt(jnp.mean(rows[:, :32] ** 2)))
    assert rms == pytest.approx(2 ** 0.5, rel=0.05)

    def attend(query, row):          # dense softmax over the cached rows
        np.testing.assert_allclose(row[0], rows[-1], atol=1e-6)
        assert row.shape == (1, 128) and not np.asarray(row[:, 40:]).any()
        sc = mla_moe.softmax_scale(CFG) * jnp.einsum("bhr,sr->bhs", query, rows)
        return jnp.einsum("bhs,sc->bhc", jax.nn.softmax(sc, -1),
                          rows[:, :CFG["kv_rank"]])

    got = mla_moe.decode_attention(CFG, lp, x[-1:], cos[-1:], sin[-1:], attend)
    np.testing.assert_allclose(got[0], want[-1], atol=1e-5)
    # against the reference's sub-layer (which states the equations anew)
    w = ref.layer_weights(CFG, SEED, 1)["sub"][0]
    h = ref.rms_norm(x, w["attn_norm"], CFG["rms_eps"])
    np.testing.assert_allclose(want, x + ref.attention(CFG, w, h, "f32"),
                               atol=1e-5)
    unscaled, _ = mla_moe.prefill_attention(dict(CFG, latent_scales=False),
                                            lp, x, cos, sin)
    assert float(jnp.abs(unscaled - want).max()) > 1e-3


def _branch_params(cfg, layer):
    """(the layer's router leaves, every layer's experts, this layer's
    offset among them), float32."""
    params = f32(mla_scmoe.init_params(cfg, SEED))
    return ({k: w[layer] for k, w in params["router"].items()},
            params["experts"], layer * cfg["experts_held"])


def _expert_layer(cfg, h, live, layer=1, **router):
    p, experts, offset = _branch_params(cfg, layer)
    p.update(router)
    y, c = moe.expert_layer(
        h, p, experts, live, first=cfg["experts_first"],
        held=cfg["experts_held"], k=cfg["experts_per_token"],
        scale=cfg["routed_scale"], offset=offset,
        zero_experts=cfg["zero_experts"])
    return y, dict(zip(moe.ZERO_COUNTERS, (int(v) for v in c)))


def test_the_shares_add_up_to_the_uncut_layer():
    """12 real experts over 3 shares of 4: the three shares' held parts plus
    the identity experts' term counted ONCE equal the uncut reference layer
    (float32, 1e-5). Every share's ``expert_layer`` carries that term, as
    every chip computes it for its own tokens: summing the three whole
    layers counts it three times. And gates renormalised over the chosen
    (what this router does NOT do) do not give the reference."""
    layer = 1
    h = 0.7 * jax.random.normal(jax.random.PRNGKey(2), (24, 64), jnp.float32)
    live = jnp.ones((24,), bool)
    uncut = dict(CFG, experts_first=0, experts_held=REAL)
    w = ref.layer_weights(uncut, SEED, layer)
    want = ref.expert_branch(uncut, w, h, "f32")
    zero_term = want - ref.expert_branch(uncut, w, h, "f32", zero=False)
    assert float(jnp.abs(zero_term).max()) > 0.05     # it is there to miss
    routed, whole, counted = 0.0, 0.0, []
    for first in (0, 4, 8):
        cfg = dict(CFG, experts_first=first)
        p, experts, offset = _branch_params(cfg, layer)
        assert offset == 4
        chosen, gates = moe.route_softmax_scaled(
            h, p["router_w"], p["router_b"], 4, 6.0)
        y, c = moe.held_experts(h, chosen, gates, live, experts["gate_w"],
                                experts["up_w"], experts["down_w"], first, 4,
                                offset, CFG["router_experts"])
        assert int(c[moe.COUNTERS.index("dropped")]) == 0
        routed = routed + y
        y_layer, c = _expert_layer(cfg, h, live, layer)
        whole = whole + y_layer
        counted.append(c)
    np.testing.assert_allclose(routed + zero_term, want, atol=1e-5)
    np.testing.assert_allclose(whole - 2 * zero_term, want, atol=1e-5)
    assert float(jnp.abs(whole - want).max()) > 0.05        # zero term x 3
    # every live pair is on a share's held experts or on an identity expert
    assert all(c["assignments"] == 24 * 4 and c["dropped"] == 0
               for c in counted)
    assert len({c["zero"] for c in counted}) == 1 and counted[0]["zero"] > 0
    assert sum(c["held"] for c in counted) + counted[0]["zero"] == 24 * 4
    # not renormalised: the chosen p do not sum to 1, so 6 p / sum p differs
    _, chosen, gates = ref.route(uncut, w, h, "f32")
    assert float(jnp.abs(gates.sum(-1) - 6.0).min()) > 0.5


def test_a_token_on_identity_experts_alone_gets_its_gates_times_itself():
    """A choosing bias of +10 on four identity experts sends every token's 4
    choices there (and a bias never weighs): the layer's result is ``6 . (sum
    of the four chosen p) . h``, no row goes through the grouped products
    (``rows_run`` 0, ``held`` 0, nothing touched), every live pair is counted
    in ``zero`` — and a pad position gets nothing."""
    t = 20
    h = 0.7 * jax.random.normal(jax.random.PRNGKey(3), (t, 64), jnp.float32)
    live = jnp.ones((t,), bool).at[-3:].set(False)
    p, _, _ = _branch_params(CFG, 1)
    bias = p["router_b"].at[REAL + 1:REAL + 5].add(10.0)
    y, c = _expert_layer(CFG, h, live, router_b=bias)
    prob = jax.nn.softmax(jnp.dot(h, p["router_w"],
                                  precision=jax.lax.Precision.HIGHEST), -1)
    want = 6.0 * prob[:, REAL + 1:REAL + 5].sum(-1, keepdims=True) * h
    np.testing.assert_allclose(y[:17], want[:17], atol=1e-6)
    assert not np.asarray(y[17:]).any()
    assert c == {"assignments": 17 * 4, "held": 0, "load_max": 0,
                 "touched": 0, "dropped": 0, "rows_run": 0, "zero": 17 * 4}
    w = dict(ref.layer_weights(CFG, SEED, 1), router_b=bias)
    np.testing.assert_allclose(y[:17], ref.expert_branch(CFG, w, h, "f32")[:17],
                               atol=1e-6)


def test_every_live_pair_is_held_zero_or_elsewhere():
    """``moe.zero + moe.held + (pairs on real experts held elsewhere) =
    moe.assignments`` with nothing dropped, against the reference's own
    choices; and the layer is the reference's branch for this share."""
    t = 40
    h = 0.7 * jax.random.normal(jax.random.PRNGKey(4), (t, 64), jnp.float32)
    live = jnp.arange(t) % 5 != 2
    y, c = _expert_layer(CFG, h, live)
    w = ref.layer_weights(CFG, SEED, 1)
    _, chosen, _ = ref.route(CFG, w, h, "f32")
    chosen = np.asarray(chosen)[np.asarray(live)]
    first, held = CFG["experts_first"], CFG["experts_held"]
    here = (chosen >= first) & (chosen < first + held)
    zero = chosen >= REAL
    assert c["assignments"] == chosen.size and c["dropped"] == 0
    assert (c["held"], c["zero"]) == (here.sum(), zero.sum())
    assert c["held"] > 0 and c["zero"] > 0
    elsewhere = (~here & ~zero).sum()
    assert elsewhere > 0
    assert c["zero"] + c["held"] + elsewhere == c["assignments"]
    np.testing.assert_allclose(
        y[np.asarray(live)],
        ref.expert_branch(CFG, w, h, "f32")[np.asarray(live)], atol=1e-5)


def test_the_shortcut_leaves_after_the_first_attention():
    """One double layer of the program against the reference's, float32, and
    against the two misplacements the structure invites: the expert branch
    computed from the SECOND sub-layer's normed state (an ordinary MoE
    block), or added before the second attention instead of after the second
    MLP."""
    params = f32(mla_scmoe.init_params(CFG, SEED))
    model = mla_scmoe.MLAScMoEDecodeModel(CFG, params=params)
    s = 16
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(5), (s, 64), jnp.float32)
    cos, sin = model._angles(jnp.arange(s))
    live = jnp.ones((s,), bool)
    subs = [{k: w[0, i] for k, w in params["sub"].items()} for i in (0, 1)]
    router = {k: w[0] for k, w in params["router"].items()}

    def attention(_, lp, x):
        return mla_moe.prefill_attention(CFG, lp, x, cos, sin)[0]

    got, _ = mla_scmoe.double_layer(CFG, *subs, router,
                                    (params["experts"], 0), x, live, attention)
    w = ref.layer_weights(CFG, SEED, 0)
    want = ref.layer_forward(CFG, w, x)
    np.testing.assert_allclose(got, want, atol=5e-6)     # reads 5e-7
    # the same layer with the branch taken from, or put back, elsewhere
    eps = CFG["rms_eps"]
    first, second = w["sub"]

    def mlp(sw, h):
        return ref.gated_mlp(h, sw["gate_w"], sw["up_w"], sw["down_w"], "f32")

    a0 = x + ref.attention(CFG, first, ref.rms_norm(x, first["attn_norm"], eps),
                           "f32")
    h0 = ref.rms_norm(a0, first["mlp_norm"], eps)
    b0 = a0 + mlp(first, h0)
    early = b0 + ref.expert_branch(CFG, w, h0, "f32")      # rejoins too soon
    a1 = early + ref.attention(CFG, second, ref.rms_norm(
        early, second["attn_norm"], eps), "f32")
    early = a1 + mlp(second, ref.rms_norm(a1, second["mlp_norm"], eps))
    # (4e-4: with 0.02 N(0,1) weights the second attention moves little)
    assert float(jnp.abs(early - want).max()) > 1e-4
    a1 = b0 + ref.attention(CFG, second, ref.rms_norm(b0, second["attn_norm"],
                                                      eps), "f32")
    h1 = ref.rms_norm(a1, second["mlp_norm"], eps)
    late = a1 + mlp(second, h1) + ref.expert_branch(CFG, w, h1, "f32")
    assert float(jnp.abs(late - want).max()) > 0.1


def test_the_scheduler_hangs_the_zero_counter_on_its_spans():
    """``moe.zero`` comes back with the tokens beside the six counters the
    other expert models have, lands on the ``decode.prefill`` and
    ``decode.step`` spans and in ``stats()["counted"]``; the engine sizes
    the pool by the model's 4 sub-layers while the counters sum 2 expert
    layers; the row tile is named from the router's whole width."""
    from mxnet_tpu import obs
    from mxnet_tpu.serve import DecodeScheduler

    model = mla_scmoe.MLAScMoEDecodeModel(CFG, seed=SEED)
    engine = DecodeEngine(model, slots=SLOTS, page_size=PAGE, num_pages=17,
                          prompt_buckets=[16])
    assert engine.paged_layers == 4
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
    keys = {"moe." + name for name in moe.ZERO_COUNTERS}
    for s in prefill + steps:
        a = s["args"]
        assert keys <= set(a) and a["moe.dropped"] == 0
        assert a["moe.zero"] + a["moe.held"] <= a["moe.assignments"]
    # 11 live prompt positions x 4 choices x 2 expert layers; a step: 1 slot
    assert prefill[0]["args"]["moe.assignments"] == 11 * 4 * 2
    assert all(s["args"]["moe.assignments"] == 4 * 2 for s in steps)
    counted = sched.stats()["counted"]
    assert counted["moe.assignments"] == 11 * 8 + 3 * 8
    for name in ("moe.zero", "moe.held"):
        assert counted[name] == sum(s["args"][name] for s in prefill + steps)
    # 6 of 18 router outputs are identity experts: about a third of the pairs
    assert 0 < counted["moe.zero"] < counted["moe.assignments"]
    assert engine.stats()["moe_row_tile"] == {
        "step": moe.layer_row_tile(SLOTS, 4, 18, jnp.bfloat16),
        "prefill": {16: moe.layer_row_tile(16, 4, 18, jnp.bfloat16)}}
    assert "moe.zero" in json.dumps(gauges)


def test_the_double_layer_model_is_prefilled_whole():
    """``MLAScMoEDecodeModel`` inherits from ``MLAMoEDecodeModel``, whose
    ``prefill_from`` continues THAT class's ``prefill``: with a ``prefill`` of
    its own and no ``prefill_from`` of its own this model offers none, so its
    engine keeps a program a bucket and the scheduler launches one prefill
    call an admission, whatever the prompt's length."""
    from mxnet_tpu.serve import DecodeScheduler

    model = mla_scmoe.MLAScMoEDecodeModel(CFG, seed=SEED)
    assert not hasattr(model, "prefill_from")
    engine = DecodeEngine(model, slots=SLOTS, page_size=PAGE, num_pages=17,
                          prompt_buckets=[16, 32])
    assert engine.prefill_piece is None and engine.buckets == [16, 32]
    sched = DecodeScheduler(engine)
    try:
        for n in (11, 27):
            assert len(list(sched.generate(list(range(1, n + 1)),
                                           max_new_tokens=2))) == 2
        st = sched.stats()
    finally:
        sched.close()
    assert st["prefill_piece"] is None
    assert st["prefill_pieces"] == st["admitted"] == 2
    assert engine.stats()["num_programs"] == 3      # two buckets + the step
