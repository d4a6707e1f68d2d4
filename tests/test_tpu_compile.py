"""What the TPU's compiler makes of the decode programs, without the chip.

libtpu is installed on a CPU-only host and compiles for a chip that is
described and not attached (docs: .claude/skills/verify/SKILL.md "Checking
a kernel or a step against Mosaic WITHOUT the chip"). Nothing runs, so no
result or time is checked here — only what the optimised program *is*: that
the paged kernel goes through Mosaic, and that no decode program slices,
copies or lays out again any part of the KV pool.

A CPU program cannot show the second point. XLA:CPU fuses a per-layer slice
of the pool into the gather that reads it, so its ``temp_bytes`` were tiny
before the pool was addressed in place and are tiny after (the last test
says how tiny); and the page-axis-minor layout the TPU client picks for a
pool-shaped array, which cost two whole-pool conversions a program, does
not exist off the TPU.

The topology is described inside a fixture and in this file alone: only one
process may hold libtpu, and a worker must not load it while it imports or
collects (on-chip-measurement guide, section 2).
"""
import numpy as np
import pytest

from mxnet_tpu import obs, progcache
from mxnet_tpu.ops import flash_attention, moe
from mxnet_tpu.serve import DecodeEngine, decode

pytestmark = pytest.mark.decode

# 12 heads: not a multiple of the 8-row tile, so the TPU's own choice of
# layout for the pool is NOT row-major — the engine has to ask for it
CFG = {"vocab": 512, "units": 768, "heads": 12, "head_dim": 64, "layers": 2,
       "max_length": 128}
HIDDEN = 256
SLOTS, PAGE, NUM_PAGES, BUCKET = 2, 16, 1025, 64


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu here, or another process has it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but never read back without the chip: keep it out
        cached = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


def _engine(num_pages=NUM_PAGES):
    u, v = CFG["units"], CFG["vocab"]
    layer = {"qkv_w": (3 * u, u), "qkv_b": (3 * u,), "proj_w": (u, u),
             "proj_b": (u,), "ln1_g": (u,), "ln1_b": (u,),
             "ffn1_w": (HIDDEN, u), "ffn1_b": (HIDDEN,),
             "ffn2_w": (u, HIDDEN), "ffn2_b": (u,), "ln2_g": (u,),
             "ln2_b": (u,)}
    top = {"embed": (v, u), "pos": (CFG["max_length"], u), "final_g": (u,),
           "final_b": (u,), "dec_w": (v, u), "dec_b": (v,)}
    params = {k: np.zeros(s, np.float32) for k, s in top.items()}
    params["layers"] = [{k: np.zeros(s, np.float32)
                         for k, s in layer.items()}
                        for _ in range(CFG["layers"])]
    return DecodeEngine(CFG, params=params, slots=SLOTS, page_size=PAGE,
                        num_pages=num_pages, prompt_buckets=[BUCKET])


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _traced(engine, one_chip, kind, monkeypatch):
    """The engine's own program, jitted as its constructor does on a TPU
    (pool row-major and donated) and traced for the described chip."""
    import jax
    import jax.numpy as jnp

    # the backend here is cpu, so the library would pick interpret mode
    monkeypatch.setattr(flash_attention, "_use_interpret", lambda: False)

    def spec(shape, dtype=jnp.float32, sharding=one_chip):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = engine._row_major(engine.kv.shape, one_chip)
    held = {name: engine._row_major(a.shape, one_chip)
            for name, a in engine.state.items()}
    prefill, step = engine._jit_programs(pool, held, donate=(1, 2))
    params = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype),
                                    engine._params)
    kv = spec(engine.kv.shape, engine.kv.dtype, sharding=pool)
    state = {name: spec(a.shape, a.dtype, sharding=held[name])
             for name, a in engine.state.items()}
    i32, slots, page = jnp.int32, engine.slots, engine.page_size
    last = spec((slots,), i32)
    # the one array a call uploads (``DecodeEngine``'s docstring)
    if kind == "step":
        packed = spec(engine.blank_step().shape, i32)
        return step.trace(params, kv, state, last, packed)
    bucket = engine.buckets[0]
    if engine.prefill_piece:    # header, the whole page table, a piece
        packed = spec((5 + engine.max_prompt // page + bucket,), i32)
    else:
        packed = spec((4 + bucket // page + bucket,), i32)
    return prefill.trace(params, kv, state, last, packed)


def _tpu_program(engine, one_chip, kind, monkeypatch):
    """:func:`_traced`, lowered and compiled for the described chip."""
    lowered = _traced(engine, one_chip, kind, monkeypatch).lower()
    return lowered, lowered.compile()


def _host_arguments(engine, lowered):
    """The program's arguments less those that rest on the device between
    calls: the weights, the pool, the per-slot state, the last-token vector."""
    import jax

    resident = (len(jax.tree_util.tree_leaves(engine._params)) + 2
                + len(engine.state))
    return jax.tree_util.tree_leaves(lowered.args_info)[resident:]


def _k_slice_bytes(engine):
    """One layer's K of the pool: what ``kv[:, i, 0]`` used to make."""
    return engine.kv.nbytes // (2 * CFG["layers"])


def _pool_lines(compiled, engine):
    """Instructions of the optimised entry computation that yield or take
    a pool-shaped array."""
    shape = ("bf16[" if engine.kv.dtype == "bfloat16" else "f32[") + ",".join(
        str(n) for n in engine.kv.shape) + "]"
    text = compiled.as_text()
    entry = text[text.index("\nENTRY ") + 1:]
    return [line.strip() for line in entry.splitlines()[1:]
            if shape in line]


def test_tpu_step_program_reads_the_pool_where_it_lies(
        engine, one_chip, monkeypatch):
    """Structural, at a size where the pool (201.5 MB, 1025 pages)
    dominates the weights (19 MB) and the pages far outnumber
    slots x max_pages (16).

    The step compiled for a v5e allocates less than one layer's K of the
    pool (50.4 MB): this tree reads ``temp_bytes`` 0 and ``bytes_accessed``
    65 MB; the tree before it — pool ``(pages, layers, 2, page, H, D)``,
    sliced per layer for the kernel — read 808,135,680 and 2.72 GB at the
    same size: the whole pool converted from the page-minor layout it
    rested in and back, and a padded K and V slice per layer (compile-only
    run of commit 35acd89, PR 28). Here the pool parameter rests
    row-major, and the only instructions that touch a pool-shaped array
    are the in-place scatters (one per layer), the Mosaic calls (one per
    layer), the parameter and the result."""
    lowered, compiled = _tpu_program(engine, one_chip, "step", monkeypatch)
    # the layers' calls share ONE traced and lowered kernel (the layer is a
    # prefetched scalar); the compiled program calls it once a layer (below)
    assert lowered.as_text().count("tpu_custom_call") == 1
    # one upload a step: positions, lengths, temperatures, page tables and
    # the seed come up as ONE int32 array; the tokens never leave the device
    (packed,) = _host_arguments(engine, lowered)
    assert (packed.shape, str(packed.dtype)) == (
        (SLOTS + 1, 3 + engine.max_pages), "int32")
    cost = progcache.analyze_compiled(compiled)
    assert cost["bytes_accessed"] > 0
    assert cost["temp_bytes"] < _k_slice_bytes(engine), cost
    lines = _pool_lines(compiled, engine)
    assert "{4,3,2,1,0:T(8,128)}" in lines[0] and "parameter(" in lines[0]
    kinds = sorted(
        "scatter" if " fusion(" in line and "scatter" in line
        else "mosaic" if "tpu_custom_call" in line
        else "root" if line.startswith("ROOT ") and " tuple(" in line
        else line for line in lines[1:])
    assert kinds == (["mosaic"] * CFG["layers"] + ["root"]
                     + ["scatter"] * CFG["layers"]), kinds
    # donated and written in place: the result IS the parameter's buffer
    assert cost["alias_bytes"] >= engine.kv.nbytes


def test_tpu_prefill_program_writes_the_pool_in_place(
        engine, one_chip, monkeypatch):
    """A prefill writes its pages into the donated pool, page by page in a
    loop, and does nothing else to it: ``temp_bytes`` 1,354,752 here
    against 405,304,320 in the tree before (the same two conversions of the
    whole pool). As ONE scatter of all the pages the write had XLA convert
    the whole pool to ``{4,2,3,1,0}`` and back (12 heads pad an 8-row tile;
    the scatter would rather tile the page axis): two pool-shaped ``copy``
    instructions, which is what the lines are searched for."""
    lowered, compiled = _tpu_program(engine, one_chip, "prefill",
                                     monkeypatch)
    assert len(_host_arguments(engine, lowered)) == 1
    cost = progcache.analyze_compiled(compiled)
    assert cost["temp_bytes"] < _k_slice_bytes(engine), cost
    lines = _pool_lines(compiled, engine)
    assert "{4,3,2,1,0:T(8,128)}" in lines[0] and "parameter(" in lines[0]
    assert not [line for line in lines
                if " copy(" in line or " fusion(" in line], lines
    assert any(" while(" in line for line in lines), lines
    assert cost["alias_bytes"] >= engine.kv.nbytes


def test_step_program_stats_on_this_backend(engine):
    """``stats()["step_program"]`` is the compiler's account of the step
    the engine really runs — here the CPU's, XLA gather path. It reads
    ``temp_bytes`` 22,065,792 (XLA:CPU copies each layer's weights out of
    their stack, 19 MB, and gathers the 16 live pages twice) against a K
    slice of 50.4 MB; the tree before read 1,612,096 on the CPU too (see
    the module docstring), so this holds the line and shows the counter,
    not the cure. None until the step is built."""
    assert engine.stats()["step_program"] is None
    engine.warmup()
    program = engine.stats()["step_program"]
    assert set(program) == {"temp_bytes", "bytes_accessed"}
    assert 0 < program["temp_bytes"] < _k_slice_bytes(engine)
    assert program["bytes_accessed"] > 0
    engine.pool.assert_baseline()


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_paged_kernel_stats_say_what_a_step_costs_in_grid_steps(
        impl, monkeypatch):
    """``stats()["paged_kernel"]``: the pages the paged kernel reads a grid
    step and the grid steps of one decode step, set when the step is
    built — here 8 pages (the whole table of a 128-position model: G is at
    most ``max_pages``), so slots x 1 group x layers; None until then, and
    for a step that gathers through XLA. ``step_program`` keeps its keys."""
    monkeypatch.setenv("MXNET_DECODE_ATTN", impl)
    small = _engine(num_pages=17)
    assert small.max_pages == 8
    assert small.stats()["paged_kernel"] is None
    small.warmup()
    stats = small.stats()
    assert set(stats["step_program"]) == {"temp_bytes", "bytes_accessed"}
    assert stats["paged_kernel"] == (
        {"page_group": 8, "grid_steps": SLOTS * 1 * CFG["layers"]}
        if impl == "pallas" else None)


def test_tpu_paged_kernel_reads_a_group_of_pages_at_the_cell_geometry(
        one_chip, monkeypatch):
    """The paged kernel alone at ``gpt2m-serve-closed``'s own geometry —
    16 slots, a table of 64 pages of 16, the 24-layer float32 pool of 1025
    pages (3.2 GB: shapes only here) — goes through Mosaic with 8 pages
    (1 MiB) a grid step: 16 x 8 x 24 = 3,072 grid steps a decode step,
    where one page a step made 24,576; and it makes no temporary."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(flash_attention, "_use_interpret", lambda: False)
    slots, max_pages, layers, heads, dim = 16, 64, 24, 16, 64
    pool_shape = (1025, layers, PAGE, heads, 2 * dim)
    group = flash_attention.decode_page_group(pool_shape, max_pages)
    assert group == 8
    assert slots * -(-max_pages // group) * layers == 3072

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(flash_attention.decode_attention,
                      static_argnums=2).lower(
        spec((slots, heads, dim)), spec(pool_shape), layers - 1,
        spec((slots, max_pages), jnp.int32), spec((slots,), jnp.int32))
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = lowered.compile()
    assert progcache.analyze_compiled(compiled)["temp_bytes"] == 0


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones included."""
    import jax

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("shape,d_v,backward,packed", [
    ((4, 16, 1024, 64), 64, True, False),     # (B, H, S, D): the mesh path
    ((4, 16, 1024, 64), 64, True, True),      # gpt2m-train-s1024, every layer
    ((1, 64, 1536, 192), 128, False, False),  # longcat-omni's longest prefill
])
def test_tpu_flash_kernels_lower_with_the_schedule_they_report(
        shape, d_v, backward, packed, one_chip, monkeypatch):
    """The flash forward (and at the train cell's shape the backward) for a
    v5e: through Mosaic, on the grid and in the blocks that
    ``flash_schedule`` reports from the shapes; the backward's three results
    in the operands' dtype (dQ gathers in a float32 VMEM scratch: none in
    HBM, no convert behind the kernel), the scratch a small part of VMEM;
    no temporary beside the forward's (rows, 8) float32 lse. ``packed``:
    the entry that reads the fused projection (4, 1024, 3072) in 128-lane
    column blocks of two heads — half the grid steps, every block 128 lanes
    wide, the lse as (2, S) rows, δ made inside the backward, which writes
    the projection's whole gradient itself: no temporary beside the rows."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(flash_attention, "_use_interpret", lambda: False)
    b, h, s, d = shape
    sched = flash_attention.flash_schedule(s, d, True, b, h)
    heads = sched["heads_per_step"]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, h, s, d_v), jnp.bfloat16, sharding=one_chip)
    args = (q, q, v)
    if packed:
        args = (jax.ShapeDtypeStruct((b, s, 3 * h * d), jnp.bfloat16,
                                     sharding=one_chip),)
        lanes, heads = sched["packed"]["block_lanes"], \
            sched["packed"]["heads_per_block"]

    def forward(*args):
        if packed:
            return flash_attention.flash_attention_packed(*args, h,
                                                          causal=True)
        return flash_attention.flash_attention(*args, causal=True)

    def loss(*args):
        return forward(*args).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=tuple(range(len(args)))) if backward \
        else forward
    calls = list(_pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr))
    assert len(calls) == (2 if backward else 1)
    grids = [call.params["grid_mapping"].grid for call in calls]
    blocks = [[tuple(getattr(n, "block_size", 1) for n in m.block_shape)
               for m in call.params["grid_mapping"].block_mappings]
              for call in calls]
    assert grids[0] == (b * h // heads, s // sched["block_q"])
    if packed:
        assert grids == [sched["packed"]["grid_fwd"],
                         sched["packed"]["grid_bwd"]]
        assert blocks[0] == [(1, sched["block_q"], lanes), (1, s, lanes),
                             (1, s, lanes), (1, sched["block_q"], lanes),
                             (1, heads, sched["block_q"])]
        assert {(1, s, lanes), (1, heads, s),
                (1, sched["block_k"], lanes)} <= set(blocks[1])
    else:
        assert (heads, sched["block_q"], d) in blocks[0]
    if backward:
        outs = [(v.aval.shape, str(v.aval.dtype)) for v in calls[1].outvars]
        scratch = [(a.shape, str(a.dtype)) for a in
                   calls[1].params["grid_mapping"].scratch_avals
                   if hasattr(a, "dtype") and "sem" not in str(a.dtype)]
        if packed:   # ONE gradient, the projection's, written by the kernel
            assert outs == [((b, s, 3 * h * d), "bfloat16")]
            assert scratch == [((s, lanes), "float32"),
                               ((heads, s), "float32"),
                               ((s, lanes), "bfloat16"),
                               ((2, sched["block_k"], lanes), "bfloat16")]
        else:
            assert grids[1] == (b * h, s // sched["block_k"])  # a head a step
            assert outs == [((b * h, s, d), "bfloat16")] * 3
            assert scratch == [((s, d), "float32")]
        assert s * 128 * 4 <= 2 ** 20    # of the 16 MiB a call may use
    lowered = jax.jit(fn).lower(*args)
    assert lowered.as_text().count("tpu_custom_call") == len(calls)
    compiled = lowered.compile()
    temp = progcache.analyze_compiled(compiled)["temp_bytes"]
    if packed:   # the lse's rows and its (zero) cotangent: δ is the kernel's
        assert temp <= 2 * b * h * s * 4 + 2 ** 20
    else:
        assert temp <= b * h * s * 128 * 4 + 2 ** 20   # the lse, lanes padded


def test_tpu_attention_layer_hands_the_kernels_its_own_layouts(one_chip,
                                                               monkeypatch):
    """One ``MultiHeadAttention`` layer's ``value_and_grad`` at the train
    cell's (4, 1024, 1024), 16 heads, bfloat16, for a v5e: exactly two Mosaic
    calls, their operands and results the step program's own arrays — the
    fused projection (4, 1024, 3072), (4, 1024, 1024) for o, do, dq, dk, dv,
    the lse as rows — and no ``copy`` or ``transpose`` of a head-major
    (4, 16, 1024, 64), of a position-major (4, 1024, 16, 64), of the whole
    projection or of the old (64, 1024, 8) lse anywhere in the optimised
    module: a Mosaic call is opaque to XLA, and each such layout at its edge
    was a copy made and waited for (8.4 ms of gpt2m-train-s1024's 90.4 ms
    step, PERF.md §6, PR 48)."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models.transformer import MultiHeadAttention
    from mxnet_tpu.parallel.functional import functionalize

    monkeypatch.setattr(flash_attention, "_use_interpret", lambda: False)
    b, s, u, h = 4, 1024, 1024, 16
    layer = MultiHeadAttention(u, h, causal=True, prefix="attn_")
    layer.initialize()
    _, apply = functionalize(layer)

    def loss(params, x):
        out, _ = apply({n: w.astype(jnp.bfloat16)
                        for n, w in params.items()}, x)
        return (x + out).astype(jnp.float32).sum()

    params = {p.name: jax.ShapeDtypeStruct(p.shape, jnp.float32,
                                           sharding=one_chip)
              for p in layer._iter_params()}
    x = jax.ShapeDtypeStruct((b, s, u), jnp.bfloat16, sharding=one_chip)
    obs.enable()
    try:
        obs.reset()
        lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            params, x)
        counters = obs.metrics.snapshot()["counters"]
    finally:
        obs.disable()
    assert counters.get("attention.impl.flash_packed") == 1
    assert "attention.impl.flash" not in counters
    text = lowered.compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 2
    # results, then the operands' shapes (operand_layout_constraints)
    big = [set(re.findall(r"(?:bf16|f32)\[[\d,]+\]", call.split(
        "backend_config")[0])) for call in calls]
    assert big[0] == {"bf16[4,1024,3072]", "bf16[4,1024,1024]",
                      "f32[32,2,1024]"}
    assert big[1] == big[0]
    moved = [line.strip()[:140] for line in text.splitlines()
             if re.search(r" (copy|transpose)\(", line) and re.search(
                 r"\[(4,16,1024,64|4,1024,16,64|4,1024,3,16,64|64,1024,64"
                 r"|4,1024,3072|64,1024,8|64,1,1024)\]", line)]
    assert not moved, moved


# -- the latent pool: one bfloat16 row a position, no head axis ----------------
# The row's 576 values (kv_rank 512 + qk_rope 64, the published widths) are
# 4.5 lane tiles: at that width the TPU client picks a layout of its own for
# the pool, page axis minor-most, and on the chip it did not always give the
# row-major one that was asked for (PR 29). So the model stores them in 640
# columns, five whole tiles, where row-major IS the client's choice.

MLA = {"vocab_size": 1024, "hidden_size": 512, "num_layers": 3,
       "first_dense": 1, "num_heads": 16, "qk_nope": 128, "qk_rope": 64,
       "v_head": 128, "kv_rank": 512, "dense_width": 1024,
       "expert_width": 256, "router_experts": 16, "experts_first": 4,
       "experts_held": 4, "experts_per_token": 4, "routed_scale": 2.5,
       "rms_eps": 1e-6, "max_length": 2048,
       "rope": {"theta": 10000, "factor": 40,
                "original_max_position_embeddings": 4096, "beta_fast": 32,
                "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}}


@pytest.fixture(scope="module")
def latent_engine():
    import jax

    from mxnet_tpu.models import mla_moe

    shapes = jax.eval_shape(lambda: mla_moe.init_params(MLA, 0))
    model = mla_moe.MLAMoEDecodeModel(MLA, params=shapes)
    # 8 slots x 4 choices = 32 sorted rows, 8 a row block: under 8 rows XLA
    # multiplies a ragged dot densely instead of with its grouped kernel
    return DecodeEngine(model, slots=8, page_size=64, num_pages=8 * 32 + 1,
                        prompt_buckets=[1024])


def test_tpu_latent_step_program_reads_the_pool_where_it_lies(
        latent_engine, one_chip, monkeypatch):
    """The step of the latent-attention model compiled for a v5e: the pool
    ``(257, 3, 64, 640)`` bfloat16 (63 MB; the weights 9 MB) rests
    row-major (``T(8,128)(2,1)``, no padding), is written by
    one in-place scatter a layer and read by one Mosaic call a layer (the
    paged latent kernel, ``mla_decode``; the grouped expert products are
    Mosaic calls of XLA's own, ``ragged-dot-*``); nothing else is
    pool-shaped and ``temp_bytes`` stays under one layer's latents."""
    engine = latent_engine
    assert engine.kv.shape == (257, 3, 64, 640)
    assert engine.cache_row_bytes == 1280
    lowered, compiled = _tpu_program(engine, one_chip, "step", monkeypatch)
    cost = progcache.analyze_compiled(compiled)
    assert cost["temp_bytes"] < engine.kv.nbytes // MLA["num_layers"], cost
    lines = _pool_lines(compiled, engine)
    assert ("{3,2,1,0:T(8,128)(2,1)}" in lines[0]
            and "parameter(" in lines[0]), lines[0]
    kinds = sorted(
        "scatter" if " fusion(" in line and "scatter" in line
        else "mosaic" if "tpu_custom_call" in line and "mla_decode" in line
        else "root" if line.startswith("ROOT ") and " tuple(" in line
        else line for line in lines[1:])
    assert kinds == (["mosaic"] * 3 + ["root"] + ["scatter"] * 3), kinds
    text = compiled.as_text()
    assert text.count("ragged-dot-none") >= 3 * 2     # 3 products x 2 layers
    assert cost["alias_bytes"] >= engine.kv.nbytes


def test_tpu_latent_prefill_program_writes_the_pool_in_place(
        latent_engine, one_chip, monkeypatch):
    """A 1024-position prefill of the latent model for a v5e (since PR 45
    the engine's one piece program, here with the piece the whole prompt):
    the flash forward with 192-wide keys and 128-wide values goes through
    Mosaic (twice: the dense layer, and the scanned expert layers' one
    body), the pool is written page by page in place, no pool-shaped copy."""
    engine = latent_engine
    lowered, compiled = _tpu_program(engine, one_chip, "prefill", monkeypatch)
    assert lowered.as_text().count("tpu_custom_call") >= 2
    cost = progcache.analyze_compiled(compiled)
    lines = _pool_lines(compiled, engine)
    assert not [line for line in lines
                if " copy(" in line or " fusion(" in line], lines
    assert any(" while(" in line for line in lines), lines
    assert cost["alias_bytes"] >= engine.kv.nbytes


# -- state beside pages: gated delta layers and a flat grouped-KV row ----------
# Published widths of the ``gdn_moe`` kind (heads of 256, 2 cached; value heads
# 32 x 128 x 128 float32 of state), at a depth, expert count and vocabulary
# small enough to compile in seconds.

GDN = {"vocab_size": 1024, "hidden_size": 512, "num_layers": 4,
       "full_interval": 2, "num_heads": 16, "num_kv_heads": 2, "head_dim": 256,
       "rotary_dim": 64, "rope_theta": 10000000, "linear_key_heads": 16,
       "linear_value_heads": 32, "linear_key_dim": 128,
       "linear_value_dim": 128, "conv_width": 4, "expert_width": 256,
       "router_experts": 16, "experts_first": 4, "experts_held": 4,
       "experts_per_token": 4, "rms_eps": 1e-6, "max_length": 2048}


@pytest.fixture(scope="module")
def gdn_engine():
    import jax

    from mxnet_tpu.models import gdn_moe

    shapes = jax.eval_shape(lambda: gdn_moe.init_params(GDN, 0))
    model = gdn_moe.GDNMoEDecodeModel(GDN, params=shapes)
    return DecodeEngine(model, slots=8, page_size=256, num_pages=8 * 8 + 1,
                        prompt_buckets=[1024])


def _as_on_a_tpu(monkeypatch):
    """The model picks its kernels as it does on a TPU (the backend here is
    cpu: interpret mode, and the XLA paths under ``auto``)."""
    from mxnet_tpu.models import gdn_moe, ssm_moe
    from mxnet_tpu.ops import gqa_attention

    for module in (flash_attention, gqa_attention, gdn_moe, ssm_moe):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    monkeypatch.setenv("MXNET_DECODE_ATTN", "pallas")


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_tpu_grouped_kv_kernel_at_the_published_geometry(dtype, one_chip,
                                                         monkeypatch):
    """The grouped-KV paged kernel at ``qwen3next-serve-closed-long``'s own
    geometry — 32 slots, a table of 68 pages of 256, 16 query heads on 2
    cached heads of 256, the flat 1024-wide row — goes through Mosaic in 16
    and in 32 bits (a head axis of 2 would not: R12) and makes no
    temporary; so does the flash forward of a 16,384-position prompt, whose
    keys and values are streamed, not resident."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import gqa_attention

    _as_on_a_tpu(monkeypatch)

    def spec(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    lowered = jax.jit(gqa_attention.gqa_decode_attention,
                      static_argnums=2).lower(
        spec((32, 2, 8, 256)), spec((32 * 68 + 1, 2, 256, 1024)), 1,
        spec((32, 68), jnp.int32), spec((32,), jnp.int32))
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert "gqa_decode" in lowered.as_text()
    assert progcache.analyze_compiled(lowered.compile())["temp_bytes"] == 0
    if dtype == "bfloat16":
        lowered = jax.jit(gqa_attention.gqa_flash_attention).lower(
            spec((2, 8, 16384, 256)), spec((2, 16384, 256)),
            spec((2, 16384, 256)))
        assert lowered.as_text().count("tpu_custom_call") == 1
        assert progcache.analyze_compiled(
            lowered.compile())["temp_bytes"] == 0


def test_tpu_delta_rule_kernel_updates_the_state_in_place(one_chip,
                                                          monkeypatch):
    """The one-token delta-rule kernel at the published geometry — 33 slots
    x 6 layers x 32 x 128 x 128 float32 (415 MB: shapes only here) — goes
    through Mosaic, and the states array is its operand and its result: the
    donated argument is aliased, and nothing state-sized is allocated."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import gated_delta

    def spec(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    b, h, d = 32, 32, 128
    states = spec((b + 1, 6, h, d, d))
    lowered = jax.jit(
        lambda s, q, k, v, g, beta, live: gated_delta.delta_rule_step(
            s, 4, q, k, v, g, beta, live), donate_argnums=0).lower(
        states, spec((b, h, d)), spec((b, h, d)), spec((b, h, d)),
        spec((b, h)), spec((b, h)), spec((b,), jnp.bool_))
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert "gdn_decode" in lowered.as_text()
    cost = progcache.analyze_compiled(lowered.compile())
    nbytes = (b + 1) * 6 * h * d * d * 4
    assert cost["alias_bytes"] >= nbytes
    assert cost["temp_bytes"] < nbytes // ((b + 1) * 6)   # under one slot's


def test_tpu_step_program_with_state_beside_pages(gdn_engine, one_chip,
                                                  monkeypatch):
    """The step of the gated-delta model compiled for a v5e: the pool
    ``(65, 2, 256, 1024)`` bfloat16 counts the two PAGED layers only and
    rests row-major; the per-slot state ``(9, 2, 32, 128, 128)`` float32 is
    donated beside it; one Mosaic call a kind of kernel (``gdn_decode`` and
    ``gqa_decode``, each traced once for both of its layers, and the two of
    ``held_experts``, once for all four); and
    ``temp_bytes`` stays under one layer's share of pool plus state: no
    program copies either."""
    engine = gdn_engine
    assert engine.kv.shape == (65, 2, 256, 1024) and engine.paged_layers == 2
    assert engine.cache_row_bytes == 2048
    assert engine.state["s"].shape == (9, 2, 32, 128, 128)
    assert engine.state["tail"].shape == (9, 2, 192, 128)   # 3 x 8192
    assert engine.state_bytes == 2 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    _as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "step", monkeypatch)
    text = lowered.as_text()
    # the held experts' buffer and their rows' way back (``ops/moe.py``) too
    assert text.count("tpu_custom_call") == 4
    assert all(name in text for name in ("gdn_decode", "gqa_decode",
                                         "moe_rows", "moe_rows_back"))
    cost = progcache.analyze_compiled(compiled)
    held = engine.kv.nbytes + sum(a.nbytes for a in engine.state.values())
    assert cost["temp_bytes"] < held // GDN["num_layers"], cost
    assert cost["alias_bytes"] >= held
    lines = _pool_lines(compiled, engine)
    assert ("{3,2,1,0:T(8,128)(2,1)}" in lines[0]
            and "parameter(" in lines[0]), lines[0]
    assert not [line for line in lines if " copy(" in line], lines
    # the state too: written where it lies, by the kernel and by nothing else
    state_shape = "f32[9,2,32,128,128]"
    entry = compiled.as_text()
    entry = entry[entry.index("\nENTRY ") + 1:]
    assert not [line for line in entry.splitlines()
                if state_shape in line and " copy(" in line]


def test_tpu_prefill_program_hands_its_state_to_the_slot(gdn_engine, one_chip,
                                                         monkeypatch):
    """A 1024-position prefill of the gated-delta model for a v5e: the
    grouped flash forward goes through Mosaic (one body: the periods are
    scanned), pool and state are donated and written in place."""
    engine = gdn_engine
    _as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "prefill", monkeypatch)
    assert "gqa_prefill" in lowered.as_text()
    cost = progcache.analyze_compiled(compiled)
    held = engine.kv.nbytes + sum(a.nbytes for a in engine.state.values())
    assert cost["alias_bytes"] >= held
    lines = _pool_lines(compiled, engine)
    assert not [line for line in lines if " copy(" in line], lines


def test_tpu_piece_program_runs_the_delta_rule_as_one_kernel(
        gdn_engine, one_chip, monkeypatch):
    """The same program with the delta rule in its two forms. As on a TPU the
    two delta layers run ``gdn_prefill`` — ONE Mosaic call, traced once under
    the layers' scan — beside the flash forward and the held experts' two
    (the step keeps its four kernels:
    ``test_tpu_step_program_with_state_beside_pages``); no XLA chunk array
    (``(heads, chunks, 64, ..)``) is left, and the program's temporaries stay
    under a ceiling between the two forms' readings: 27,972,608 B with the
    kernel, 74,715,136 B with XLA's einsums at this size (1,024 positions,
    narrow experts; at the cell's own widths the held experts' rows set
    ``temp_bytes``, ``PERF.md`` section 6)."""
    from mxnet_tpu.ops import gated_delta

    _as_on_a_tpu(monkeypatch)
    assert gdn_engine.stats()["delta_rule"] == "gdn_prefill"
    lowered, compiled = _tpu_program(_anew(gdn_engine), one_chip, "prefill",
                                     monkeypatch)
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 4
    assert text.count("gdn_prefill") == 1
    chunks = "f32[32,16,64,"          # (heads, chunks of a 1,024 piece, 64, ..)
    assert chunks not in compiled.as_text()
    kernel = progcache.analyze_compiled(compiled)["temp_bytes"]
    monkeypatch.setattr(gated_delta, "chunked_form", lambda *a, **k: "xla")
    assert gdn_engine.stats()["delta_rule"] == "xla"
    lowered, compiled = _tpu_program(_anew(gdn_engine), one_chip, "prefill",
                                     monkeypatch)
    assert lowered.as_text().count("tpu_custom_call") == 3
    assert chunks in compiled.as_text()
    einsums = progcache.analyze_compiled(compiled)["temp_bytes"]
    print(f"piece temp_bytes: gdn_prefill {kernel}, XLA {einsums}")
    assert kernel < 48 * 2 ** 20 < einsums, (kernel, einsums)


# -- the held experts' rows over a prompt, at the cells' own geometry -----------

def _cell_engine(config, bucket, slots=2, buckets=None):
    """The engine of a benchmark cell at its published geometry (the cell's
    own ``model`` block), two slots of pool: shapes only, nothing is made."""
    import json
    import os

    import jax

    from mxnet_tpu.models import gdn_moe, mla_moe

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "configs", config + ".json")
    with open(path) as f:
        cfg = json.load(f)["model"]
    kind = {"gdn_moe_lm": gdn_moe, "mla_moe_lm": mla_moe}[cfg.pop("kind")]
    shapes = jax.eval_shape(lambda: kind.init_params(cfg, 0))
    model = (gdn_moe.GDNMoEDecodeModel if kind is gdn_moe
             else mla_moe.MLAMoEDecodeModel)(cfg, params=shapes)
    return cfg, DecodeEngine(model, slots=slots, page_size=256,
                             num_pages=slots * bucket // 256 + 1,
                             prompt_buckets=buckets or [bucket])


@pytest.mark.parametrize("config,bucket", [("qwen3-next-80b-a3b", 16384),
                                           ("sarvam-105b", 7168)])
def test_tpu_prefill_moves_the_held_experts_rows_once(config, bucket, one_chip,
                                                      monkeypatch):
    """The longest prefill of each expert cell compiled for a v5e at the
    published widths (a model fed in pieces given the one bucket: the piece
    is the whole prompt). ``held_experts`` sorts a chunk's ``tokens x k`` pairs
    by expert and brings the products' float32 rows back to their tokens:
    no ``f32[tokens x k, D]`` value is copied or laid out again (the
    ``reshape`` to ``(tokens, k, D)``, k = 10 on the sublane axis, was 7 % of
    a 16 k prefill), and the two kernels of the way back are in the program.

    What ``held_experts`` moves is bounded where every term can be reckoned
    from the shapes, in the function compiled alone at the chunk's geometry
    (the compiler counts a loop's body once and a kernel's operands whole):
    the three products read the experts' array, 3 x G x D x F x 2 bytes,
    and all the rest — the sort, the gather in, one block's products, its
    rows written, the one read of the rows on their way back, ``y`` — stays
    under TWO passes over ``tokens x k x D`` float32. Weighing, permuting,
    laying out and summing that array one after the other was over eight."""
    import re

    import jax
    import jax.numpy as jnp

    cfg, engine = _cell_engine(config, bucket)
    _as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "prefill", monkeypatch)
    chunks = -(-bucket // moe.TOKEN_CHUNK)
    t, k, d = bucket // chunks, cfg["experts_per_token"], cfg["hidden_size"]
    text = compiled.as_text()
    rows = re.escape(f"f32[{t * k},{d}]")
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rows + r"\S* (reshape|copy|transpose)\(", line)
             or f"f32[{t},{k},{d}]" in line or f"f32[{k},{t},{d}]" in line]
    assert not moved, moved
    assert "moe_rows_back" in text and "moe_rows" in lowered.as_text()

    f, held = cfg["expert_width"], cfg["experts_held"]
    groups = 2 * held                       # this layer's behind another's
    scored = cfg["router_experts"]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    alone = jax.jit(
        lambda h, chosen, gates, live, gate_w, up_w, down_w, offset:
        moe.held_experts(h, chosen, gates, live, gate_w, up_w, down_w,
                         cfg["experts_first"], held, offset, scored)).lower(
        spec((t, d), jnp.bfloat16), spec((t, k), jnp.int32),
        spec((t, k), jnp.float32), spec((t,), jnp.bool_),
        spec((groups, d, f), jnp.bfloat16), spec((groups, d, f), jnp.bfloat16),
        spec((groups, f, d), jnp.bfloat16), spec((), jnp.int32)).compile()
    cost = progcache.analyze_compiled(alone)
    bound = 3 * groups * d * f * 2 + 2 * t * k * d * 4
    assert cost["bytes_accessed"] < bound, (cost, bound)
    # and its temporaries are the buffer of rows and little else: every
    # held expert's rows start on a tile of their own, so the buffer is
    # bounded by ceil(tokens x k / tile) + held tiles
    tile = moe.row_tile(t * k, scored, jnp.bfloat16)
    assert tile == {"qwen3-next-80b-a3b": 128, "sarvam-105b": 256}[config]
    most = (-(-t * k // tile) + held) * tile
    assert cost["temp_bytes"] < 1.5 * most * d * 4, cost


def _reads_whole_pages(optimised, engine):
    """A piece reads the prompt so far as ``kv[table, layer]``: a gather of
    WHOLE pages (slices of ``1 x 1 x page x row``) out of the pool, the only
    pool-sized operand of any gather, and none of half pages — what
    ``serve/decode.py`` ``_pages`` does for a page of one layer over
    ``_GATHER_WINDOW_BYTES`` alone."""
    n, (page, row) = engine.max_prompt // engine.page_size, engine.kv.shape[2:]
    assert page * row * engine.kv.dtype.itemsize <= decode._GATHER_WINDOW_BYTES
    pages = [line for line in optimised.splitlines()
             if f"= bf16[{n},{page},{row}]" in line and " gather(" in line]
    assert pages and all(f"slice_sizes={{1,1,{page},{row}}}" in line
                         for line in pages), pages
    assert f"bf16[{n},2,{page // 2},{row}]" not in optimised
    assert "mini-gather" not in optimised


@pytest.mark.parametrize("shape,whole", [
    ((9, 2, 256, 1024), True),      # qwen3next's page of one layer: 512 KiB
    ((9, 6, 256, 640), True),       # sarvam's latent page: 320 KiB
    ((9, 3, 256, 1280), False),     # 640 KiB: in halves
    ((9, 3, 255, 1280), True),      # an odd page is never halved
])
def test_pages_of_a_layer_are_indexed_as_before_up_to_the_gather_window(
        shape, whole):
    """``serve/decode.py`` ``_pages`` traces to the very operations of
    ``kv[table, layer]`` while a page of one layer is within
    ``_GATHER_WINDOW_BYTES`` (every model's that had pieces before the
    window + global one: their piece programs are the ones they had), and
    gives the same rows in half pages above it."""
    import jax
    import jax.numpy as jnp

    kv = jnp.arange(np.prod(shape), dtype=jnp.float32).astype(
        jnp.bfloat16).reshape(shape)
    table = jnp.array([4, 0, 7, 7, 2], jnp.int32)
    got = jax.make_jaxpr(lambda kv, t: decode._pages(kv, t, 1))(kv, table)
    plain = jax.make_jaxpr(lambda kv, t: kv[t, 1])(kv, table)
    assert (str(got) == str(plain)) == whole
    np.testing.assert_array_equal(
        np.asarray(decode._pages(kv, table, 1).reshape(5, *shape[2:]),
                   np.float32),
        np.asarray(kv[table, 1], np.float32))


def test_tpu_piece_program_reads_the_prompt_so_far_through_the_page_table(
        one_chip, monkeypatch):
    """The ONE prefill program of ``qwen3next-serve-closed-long`` compiled
    for a v5e at the cell's geometry (32 slots, pages of 256, the cell's six
    buckets): a piece of 2,048 positions of a prompt of up to 16,384. The
    page table of the whole prompt rides in the packed array beside the
    piece's ``start``; the continued flash forward goes through Mosaic; the
    pool is donated, read through the page table (a gather of whole pages)
    and written in place — no instruction copies or lays out again a
    pool-shaped array — and the program's temporaries stay under one
    layer's rows of the pool."""
    buckets = [2048, 4096, 6144, 8192, 12288, 16384]
    cfg, engine = _cell_engine("qwen3-next-80b-a3b", 17408, slots=32,
                               buckets=buckets)
    assert engine.prefill_piece == 2048 and engine.buckets == [2048]
    assert engine.max_prompt == 16384
    assert engine.kv.shape == (2177, 2, 256, 1024)
    _as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "prefill", monkeypatch)
    (packed,) = _host_arguments(engine, lowered)
    assert packed.shape == (5 + 64 + 2048,)
    text = lowered.as_text()
    assert "gqa_prefill_from" in text and "moe_rows_back" in text
    cost = progcache.analyze_compiled(compiled)
    held = engine.kv.nbytes + sum(a.nbytes for a in engine.state.values())
    assert cost["alias_bytes"] >= held
    assert cost["temp_bytes"] < engine.kv.nbytes // engine.paged_layers, cost
    lines = _pool_lines(compiled, engine)
    assert ("{3,2,1,0:T(8,128)(2,1)}" in lines[0]
            and "parameter(" in lines[0]), lines[0]
    assert not [line for line in lines if " copy(" in line], lines
    whole = compiled.as_text()
    assert not [line for line in whole.splitlines()
                if "bf16[2177,2,256,1024]" in line and " copy(" in line]
    _reads_whole_pages(whole, engine)


def test_tpu_latent_piece_program_expands_the_prompt_so_far(one_chip,
                                                            monkeypatch):
    """The ONE prefill program of ``sarvam105b-serve-closed`` compiled for a
    v5e at the cell's geometry (32 slots, pages of 256, the cell's six
    buckets): a piece of 1,024 positions of a prompt of up to 7,168. The
    prefix's latents come through the page table as ONE gather of whole
    pages a layer and go to the continued flash forward (``mla_prefill_from``,
    a Mosaic call in the dense layer and one in the scanned expert layers'
    body) as they are: keys and values are expanded inside it, so nothing
    shaped like a head's keys rests in HBM, and no batched slice has become
    a loop over positions — the program's four ``while`` are the layers'
    scan, the held experts' row blocks with their ``searchsorted``, and the
    pages written. The pool is donated and written in place, nothing copies
    a pool-shaped array (one bucket: two programs in all), and the temporaries (0.30 GB:
    the held experts' buffers) stay far under the whole 7,168-position
    prefill's 1.51 GB (``PERF.md`` §4)."""
    buckets = [1024, 2048, 3072, 4096, 5120, 7168]
    cfg, engine = _cell_engine("sarvam-105b", 8192, slots=32, buckets=buckets)
    assert engine.prefill_piece == 1024 and engine.buckets == [1024]
    assert engine.max_prompt == 7168 and not engine.state
    assert engine.kv.shape == (1025, 6, 256, 640)
    _as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "prefill", monkeypatch)
    (packed,) = _host_arguments(engine, lowered)
    assert packed.shape == (5 + 28 + 1024,)
    text = lowered.as_text()
    assert "mla_prefill_from" in text and "moe_rows_back" in text
    cost = progcache.analyze_compiled(compiled)
    assert cost["alias_bytes"] >= engine.kv.nbytes
    assert cost["temp_bytes"] < 0.5e9, cost
    lines = _pool_lines(compiled, engine)
    assert ("{3,2,1,0:T(8,128)(2,1)}" in lines[0]
            and "parameter(" in lines[0]), lines[0]
    assert not [line for line in lines if " copy(" in line], lines
    whole = compiled.as_text()
    heads, wide = cfg["num_heads"], cfg["qk_nope"] + cfg["qk_rope"]
    assert f"bf16[{heads},7168,{wide}]" not in whole
    entry = whole[whole.index("\nENTRY ") + 1:]
    calls = [line for line in entry.splitlines()
             if "tpu_custom_call" in line and "mla_prefill_from" in line]
    # the rows (7168, 640) go in whole; the queries are the piece's alone
    assert calls and all("bf16[7168,640]" in line
                         and f"bf16[{heads},1024,{wide}]" in line
                         for line in calls), calls
    loops = [line for line in whole.splitlines() if " while(" in line]
    assert len(loops) == 4, [line.strip()[:160] for line in loops]
    _reads_whole_pages(whole, engine)
    assert sum("searchsorted" in line for line in loops) == 1


# -- state-space layers beside a 2-head pool, relu^2 experts --------------------

# NVIDIA-Nemotron-3-Nano-30B-A3B's published widths, four layers, few experts
SSM = {"vocab_size": 1024, "vocab_first": 0, "hidden_size": 2688,
       "pattern": "MEM*", "num_heads": 32, "num_kv_heads": 2, "head_dim": 128,
       "ssm_heads": 64, "ssm_head_dim": 64, "ssm_groups": 8, "ssm_state": 128,
       "conv_width": 4, "chunk_size": 128, "time_step_min": 0.001,
       "time_step_max": 0.1, "time_step_floor": 0.0001, "expert_width": 1856,
       "shared_width": 3712, "router_experts": 16, "experts_first": 4,
       "experts_held": 4, "experts_per_token": 6, "routed_scale": 2.5,
       "rms_eps": 1e-5, "max_length": 1024}


@pytest.fixture(scope="module")
def ssm_engine():
    import jax

    from mxnet_tpu.models import ssm_moe

    shapes = jax.eval_shape(lambda: ssm_moe.init_params(SSM, 0))
    model = ssm_moe.SSMMoEDecodeModel(SSM, params=shapes)
    return DecodeEngine(model, slots=128, page_size=256, num_pages=128 * 4 + 1,
                        prompt_buckets=[512])


def test_tpu_state_space_kernel_updates_the_state_in_place(one_chip):
    """The one-token state-space kernel at the cell's own geometry — 129 slots
    x 8 layers x (8, 128, 512) float32 (64 heads of 64 x 128 a layer; 2.2 GB:
    shapes only here) — goes through Mosaic under its own name, and the
    states array is its operand and its result: the donated argument is
    aliased, and nothing state-sized is allocated."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import mamba2

    def spec(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    b, layers = 128, 8
    lowered = jax.jit(
        lambda s, x, delta, a, bb, c, live: mamba2.ssm_step(
            s, 5, x, delta, a, bb, c, live), donate_argnums=0).lower(
        spec((b + 1, layers, 8, 128, 512)), spec((b, 64, 64)), spec((b, 64)),
        spec((64,)), spec((b, 8, 128)), spec((b, 8, 128)),
        spec((b,), jnp.bool_))
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert "ssm_decode" in lowered.as_text()
    cost = progcache.analyze_compiled(lowered.compile())
    nbytes = (b + 1) * layers * 64 * 64 * 128 * 4
    assert cost["alias_bytes"] >= nbytes
    assert cost["temp_bytes"] < nbytes // ((b + 1) * layers)  # under one slot's


# cell: (k, hidden, hidden as stored, expert width as stored, experts held,
# expert layers, experts the router scores, whether an expert has a gate
# matrix)
EXPERT_CELLS = {"qwen3next": (10, 2048, 2048, 512, 128, 8, 512, True),
                "sarvam": (8, 4096, 4096, 2048, 32, 5, 128, True),
                "nemotron3nano": (6, 2688, 3072, 2048, 32, 8, 128, False)}


@pytest.mark.parametrize("cell,tokens,tile", [
    ("qwen3next", 2048, 64), ("qwen3next", 32, 16), ("sarvam", 3072, 256),
    ("sarvam", 32, 16), ("nemotron3nano", 2048, 128),
    ("nemotron3nano", 128, 16)])
def test_tpu_grouped_products_take_the_row_tile_held_experts_chose(
        cell, tokens, tile, one_chip, monkeypatch):
    """``held_experts`` at the three expert cells' geometries — a prompt (a
    2,048-position piece of qwen3next's, sarvam's median 3,072, nemotron3nano's
    longest 2,048) and a step of the cell's slots, every expert layer's
    experts in one array (nemotron3nano's two matrices of 2688 x 1856 STORED
    3072 x 2048: 3.2 GB a matrix, shapes only) — compiled for a v5e.
    XLA:TPU takes as the grouped kernel's row tile the largest power of two,
    512 at most, that divides the rows a product is handed; ``row_block``
    hands it ``row_tile`` x an odd number, so the compiled products carry
    the tile ``held_experts`` laid the rows out in (a libtpu that picks
    otherwise would run tiles that straddle experts: this is the guard).
    Each product in tiles of 512 x 512 of its weights, and no copy of an
    array of experts: XLA:TPU tiles each size by the largest of 512, 256,
    128 that divides it — at the published 1856 (14.5 lane tiles) it copies
    the whole up-projection array before the loop, every call, and at 2688 x
    1920 it runs tiles of 128 x 128 (13 % of the memory's rate on the chip,
    PR 40): ``models/ssm_moe.py`` stores whole tiles of 512."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models.ssm_moe import stored_width

    _as_on_a_tpu(monkeypatch)
    k, d, wide, f, held, layers, scored, gated = EXPERT_CELLS[cell]
    assert (stored_width(2688), stored_width(1856)) == (3072, 2048)
    assert moe.row_tile(tokens * k, scored, jnp.bfloat16) == tile
    groups = layers * held

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda h, chosen, gates, live, gate_w, up_w, down_w, offset:
        moe.held_experts(h, chosen, gates, live, gate_w if gated else None,
                         up_w, down_w, 0, held, offset, scored)).lower(
        spec((tokens, d), jnp.bfloat16), spec((tokens, k), jnp.int32),
        spec((tokens, k), jnp.float32), spec((tokens,), jnp.bool_),
        spec((groups, wide, f), jnp.bfloat16),
        spec((groups, wide, f), jnp.bfloat16),
        spec((groups, f, wide), jnp.bfloat16), spec((), jnp.int32)).compile()
    text = compiled.as_text()
    products = 3 if gated else 2
    assert text.count("ragged-dot-none") >= products
    tilings = re.findall(r'ragged_dot_tiling="(\d+),(\d+),(\d+)"', text)
    assert len(tilings) >= products and all(
        t == (str(tile), "512", "512") for t in tilings), tilings
    cost = progcache.analyze_compiled(compiled)
    one_matrix = groups * wide * f * 2
    # the rows' buffer and a block's products, far from a matrix of experts
    assert cost["temp_bytes"] < one_matrix // 4, cost
    assert not [line[:160] for line in text.splitlines()
                if f"bf16[{groups}," in line and " copy(" in line]


def test_tpu_step_program_with_state_space_layers_at_128_slots(
        ssm_engine, one_chip, monkeypatch):
    """The step of the state-space model compiled for a v5e at 128 slots: the
    pool ``(513, 1, 256, 512)`` bfloat16 counts the ONE paged layer and
    rests row-major; the per-slot state ``(129, 2, 8, 128, 512)`` float32 is
    donated beside it; one Mosaic call a kind of kernel (``ssm_decode``
    traced once for both of its layers, ``gqa_decode``, and the two of
    ``held_experts``); ``temp_bytes`` stays under one layer's share of pool
    plus state; and nothing copies the state."""
    engine = ssm_engine
    assert engine.kv.shape == (513, 1, 256, 512) and engine.paged_layers == 1
    assert engine.cache_row_bytes == 1024
    assert engine.state["s"].shape == (129, 2, 8, 128, 512)
    assert engine.state["tail"].shape == (129, 2, 144, 128)   # 3 x 6144
    assert engine.state_bytes == 2 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    _as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "step", monkeypatch)
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 4
    assert all(name in text for name in ("ssm_decode", "gqa_decode",
                                         "moe_rows", "moe_rows_back"))
    cost = progcache.analyze_compiled(compiled)
    held = engine.kv.nbytes + sum(a.nbytes for a in engine.state.values())
    assert cost["temp_bytes"] < held // len(SSM["pattern"]), cost
    assert cost["alias_bytes"] >= held
    entry = compiled.as_text()
    entry = entry[entry.index("\nENTRY ") + 1:]
    assert not [line[:160] for line in entry.splitlines()
                if "f32[129,2,8,128,512]" in line and " copy(" in line]


def test_tpu_prefill_program_with_state_space_layers(ssm_engine, one_chip,
                                                     monkeypatch):
    """A 512-position prefill of the state-space model for a v5e: every layer
    under one scan that switches on its kind (the grouped flash forward goes
    through Mosaic once), pool and state donated and written in place."""
    engine = ssm_engine
    _as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "prefill", monkeypatch)
    assert lowered.as_text().count("gqa_prefill") >= 1
    cost = progcache.analyze_compiled(compiled)
    held = engine.kv.nbytes + sum(a.nbytes for a in engine.state.values())
    assert cost["alias_bytes"] >= held


# -- double layers over the latent pool, at the cell's own sizes ----------------
# ``longcat-omni-serve-closed-128`` as the benchmark runs it: published widths,
# 4 double layers, 16 held of 512 real + 256 identity experts, 128 slots of
# 2,048 positions. Shapes only: 10.35 GB of weights are never made.

@pytest.fixture(scope="module")
def scmoe_engine():
    import json
    import os

    import jax

    from mxnet_tpu.models import mla_scmoe

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "configs", "longcat-flash-omni.json")
    with open(path) as f:
        cfg = json.load(f)["model"]
    assert cfg.pop("kind") == "mla_scmoe_lm"
    shapes = jax.eval_shape(lambda: mla_scmoe.init_params(cfg, 0))
    model = mla_scmoe.MLAScMoEDecodeModel(cfg, params=shapes)
    return DecodeEngine(model, slots=128, page_size=256,
                        num_pages=128 * 8 + 1, prompt_buckets=[256])


def _weight_copies(compiled, engine):
    """Instructions of the optimised entry computation that make a new
    bfloat16 array the size of a sub-layer's query up-projection (38 MB) or
    larger in the device's main memory (the pool's in-place writes apart,
    and what XLA prefetches into its fast memory, ``S(1)``, for the product
    that reads it): a slice, a transpose or a copy of a stack of weights."""
    import re

    text = compiled.as_text()
    entry = text[text.index("\nENTRY ") + 1:]
    found = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = bf16\[([\d,]+)\]", line)
        if not m or " parameter(" in line or "get-tuple-element(" in line:
            continue
        if "S(1)}" in line[:line.index(" = ") + 80]:
            continue
        shape = tuple(int(n) for n in m.group(1).split(","))
        if shape != engine.kv.shape and np.prod(shape) >= 12288 * 1536:
            if " fusion(" in line or " copy(" in line:
                found.append(line.strip()[:160])
    return found


def test_tpu_double_layer_step_program_at_the_cells_sizes(
        scmoe_engine, one_chip, monkeypatch):
    """The step of the double-layer latent model compiled for a v5e at the
    cell's sizes: the pool ``(1025, 8, 256, 640)`` bfloat16 has a layer a
    SUB-layer (8 for 4 double layers) and rests row-major; the step's
    arguments are the 10.35 GB of weights + 2.69 GB of pool and its
    temporaries stay under one pool layer's latents; no weight stack is
    sliced, transposed or copied on its way into a product (``w[j][i]``
    copied 302 MB a leaf, ``q_b_w`` stored (in, out) 38 MB twice a
    sub-layer: PR 44); and the held experts' grouped products carry the row
    tile ``moe.layer_row_tile`` names for 128 tokens x 12 choices over the
    router's whole width of 768."""
    import re

    import jax.numpy as jnp

    engine = scmoe_engine
    assert engine.kv.shape == (1025, 8, 256, 640) and engine.paged_layers == 8
    assert engine.cache_row_bytes == 1280
    tile = moe.layer_row_tile(128, 12, 768, jnp.bfloat16)
    assert tile == 16 and engine.stats()["moe_row_tile"]["step"] == tile
    _as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "step", monkeypatch)
    text = lowered.as_text()
    assert all(name in text for name in ("mla_decode", "moe_rows",
                                         "moe_rows_back"))
    cost = progcache.analyze_compiled(compiled)
    assert 13.0e9 < cost["argument_bytes"] < 13.1e9, cost
    assert cost["temp_bytes"] < engine.kv.nbytes // 8, cost
    assert cost["alias_bytes"] >= engine.kv.nbytes
    assert not _weight_copies(compiled, engine)
    optimised = compiled.as_text()
    assert optimised.count("ragged-dot-none") >= 3 * 4   # 3 products x 4 layers
    tilings = re.findall(r'ragged_dot_tiling="(\d+),(\d+),(\d+)"', optimised)
    assert len(tilings) >= 12 and all(
        t == (str(tile), "512", "512") for t in tilings), tilings


def test_tpu_double_layer_prefill_program_at_the_cells_sizes(
        scmoe_engine, one_chip, monkeypatch):
    """A 256-position prefill of the same model for a v5e: the flash forward
    through Mosaic, the double layers unrolled so that every weight is read
    where it lies (under a ``lax.scan`` each iteration copied its double
    layer's 1.28 GB out of the stacks), the pool donated and written in
    place."""
    engine = scmoe_engine
    _as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "prefill", monkeypatch)
    assert lowered.as_text().count("tpu_custom_call") >= 3
    cost = progcache.analyze_compiled(compiled)
    assert cost["temp_bytes"] < 0.5e9, cost
    assert cost["alias_bytes"] >= engine.kv.nbytes
    assert not _weight_copies(compiled, engine)
    lines = _pool_lines(compiled, engine)
    assert not [line for line in lines if " copy(" in line], lines


# -- window rings in per-slot state beside the pool, at the cell's own sizes ----
# ``mimo-v2-flash-serve-closed-64`` as the benchmark runs it: published widths,
# 9 window + 3 global layers, 8 held of 256 experts, 64 slots, 2,561 pages of
# 256, prompts in pieces of 1,024 up to 24,576. Shapes only: 7.4 GB of weights
# are never made.

@pytest.fixture(scope="module")
def swa_engine():
    import json
    import os

    import jax

    from mxnet_tpu.models import swa_moe

    root = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
    with open(os.path.join(root, "configs", "mimo-v2-flash.json")) as f:
        cfg = json.load(f)["model"]
    with open(os.path.join(root, "workloads",
                           "mimo-v2-flash-serve-closed-64.json")) as f:
        sv = json.load(f)["serve"]
    assert cfg.pop("kind") == "swa_moe_lm"
    shapes = jax.eval_shape(lambda: swa_moe.init_params(cfg, 0))
    model = swa_moe.SWAMoEDecodeModel(cfg, params=shapes)
    return DecodeEngine(model, slots=sv["slots"], page_size=sv["page_size"],
                        num_pages=sv["num_pages"],
                        prompt_buckets=sv["prompt_buckets"])


def _window_kernels_as_on_a_tpu(monkeypatch):
    from mxnet_tpu.ops import swa_attention

    _as_on_a_tpu(monkeypatch)
    monkeypatch.setattr(swa_attention, "_use_interpret", lambda: False)


def _copies_of(compiled, *shapes):
    """Instructions of the optimised entry computation that copy an array
    of one of ``shapes``."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY ") + 1:]
    names = ["bf16[" + ",".join(str(n) for n in s) + "]" for s in shapes]
    return [line.strip()[:160] for line in entry.splitlines()
            if " copy(" in line and any(n in line for n in names)]


def test_tpu_window_step_program_at_the_cells_sizes(swa_engine, one_chip,
                                                    monkeypatch):
    """The step of the window + global model compiled for a v5e at the cell's
    sizes: the pool ``(2561, 3, 256, 1280)`` bfloat16 counts the THREE global
    layers alone, the nine window layers' rings ``(65, 9, 128, 2560)`` are
    per-slot state donated beside it; one Mosaic call a kind of kernel
    (``swa_decode`` and ``gqa_decode_dv`` each traced once for all their
    layers, and the two of ``held_experts``): the 192-wide key slices lower;
    the step's arguments are the 7.40 GB of weights + 5.03 GB of pool + 0.38
    GB of rings, its temporaries under 0.3 GB; a row a slot a layer is
    scattered in place and no instruction copies the rings or the pool; every
    layer's weights are arrays of their own, so none is sliced out of a stack;
    and the grouped products carry the row tile ``moe.layer_row_tile`` names
    for 64 tokens x 8 choices over the router's 256."""
    import re

    import jax.numpy as jnp

    engine = swa_engine
    assert engine.kv.shape == (2561, 3, 256, 1280) and engine.paged_layers == 3
    assert engine.state["window"].shape == (65, 9, 128, 2560)
    assert engine.cache_row_bytes == 2560 and engine.state_bytes == 5898240
    stats = engine.stats()
    assert stats["prefill_piece"] == 1024 and stats["max_prompt"] == 24576
    tile = moe.layer_row_tile(64, 8, 256, jnp.bfloat16)
    assert tile == 16 and stats["moe_row_tile"] == {"step": 16,
                                                    "prefill": {1024: 64}}
    _window_kernels_as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "step", monkeypatch)
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 4
    assert all(name in text for name in ("swa_decode", "gqa_decode_dv",
                                         "moe_rows", "moe_rows_back"))
    cost = progcache.analyze_compiled(compiled)
    held = engine.kv.nbytes + engine.state["window"].nbytes
    assert 12.8e9 < cost["argument_bytes"] < 12.86e9, cost
    assert cost["temp_bytes"] < 0.3e9, cost
    assert cost["alias_bytes"] >= held
    assert not _copies_of(compiled, engine.kv.shape,
                          engine.state["window"].shape)
    # no layer's weights are cut out of a stack: a 100 MB ``q_w`` is an
    # argument, and the only arrays of its shape the program makes are the
    # ones XLA prefetches into its fast memory, ``S(1)``
    optimised = compiled.as_text()
    entry = optimised[optimised.index("\nENTRY ") + 1:]
    assert not [line[:160] for line in entry.splitlines()
                if re.match(r"\s*%?[\w.\-]+ = bf16\[(1,)?12288,4096\]", line)
                and " parameter(" not in line
                and "S(1)}" not in line[:line.index(" = ") + 80]]
    tilings = re.findall(r'ragged_dot_tiling="(\d+),(\d+),(\d+)"', optimised)
    assert len(tilings) >= 3 * 11 and all(      # 3 products x 11 layers
        t == (str(tile), "512", "512") for t in tilings), tilings


def test_tpu_window_piece_program_at_the_cells_sizes(swa_engine, one_chip,
                                                     monkeypatch):
    """The ONE prefill program of the same cell: a piece of 1,024 positions
    of a prompt of up to 24,576. Both continued forwards go through Mosaic;
    the pool is read through the page table by the THREE global layers alone
    (a gather of half pages: a whole page of this row, 640 KB, is more than
    XLA:TPU's gather takes as one window, and it would split the POOL in
    column halves — two copies of half of it a piece, 2.9 GB of temporaries;
    ``serve/decode.py`` ``_GATHER_WINDOW_BYTES``) — a window layer's keys are
    its slot's ring and the piece's own rows —; pool and rings are donated
    and written in place; the temporaries stay under 0.6 GB."""
    import re

    engine = swa_engine
    _window_kernels_as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "prefill", monkeypatch)
    (packed,) = _host_arguments(engine, lowered)
    assert packed.shape == (5 + 96 + 1024,)
    text = lowered.as_text()
    assert all(name in text for name in ("swa_prefill_from",
                                         "gqa_prefill_from_dv",
                                         "moe_rows_back"))
    cost = progcache.analyze_compiled(compiled)
    held = engine.kv.nbytes + engine.state["window"].nbytes
    assert cost["alias_bytes"] >= held
    assert cost["temp_bytes"] < 0.6e9, cost
    assert not _copies_of(compiled, engine.kv.shape,
                          engine.state["window"].shape)
    optimised = compiled.as_text()
    # what is gathered out of the pool: 96 pages' two halves, three times
    pages = re.findall(r"= bf16\[96,2,128,1280\]\S* gather\(", optimised)
    assert len(pages) == 3, len(pages)
    assert "mini-gather" not in optimised
    assert not [line[:160] for line in optimised.splitlines()
                if re.search(r"= bf16\[2561,3,(256|2,128),\d+\]", line)
                and " slice(" in line]


# -- Kimi delta attention's state beside a latent pool, at the cell's sizes ------
# ``ling3-flash-serve-closed-128`` as the benchmark runs it: published widths,
# 11 KDA + 2 latent-attention layers, 32 held of 512 experts, 128 slots, the
# cell's pool of pages of 256, prompts in pieces of 2,048 up to 4,096. Shapes
# only: 6.5 GB of weights, 3.1 GB of state and the pool are never made.

@pytest.fixture(scope="module")
def kda_engine():
    import json
    import os

    import jax

    from mxnet_tpu.models import kda_mla_moe

    root = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
    with open(os.path.join(root, "configs", "ling-3.0-flash-vl.json")) as f:
        cfg = json.load(f)["model"]
    with open(os.path.join(root, "workloads",
                           "ling3-flash-serve-closed-128.json")) as f:
        sv = json.load(f)["serve"]
    assert cfg.pop("kind") == "kda_mla_moe_lm"
    shapes = jax.eval_shape(lambda: kda_mla_moe.init_params(cfg, 0))
    model = kda_mla_moe.KDAMLAMoEDecodeModel(cfg, params=shapes)
    return DecodeEngine(model, slots=sv["slots"], page_size=sv["page_size"],
                        num_pages=sv["num_pages"],
                        prompt_buckets=sv["prompt_buckets"])


def test_tpu_channel_decay_kernel_updates_the_state_in_place(one_chip):
    """``kda_decode`` at the published geometry — 129 slots x 11 layers x 32
    heads x 128 x 128 float32 (2.98 GB: shapes only here) — goes through
    Mosaic under its own name, the decay a column a head beside the key's,
    and the states array is the kernel's operand AND result (aliased)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import kda

    b, h, dk, dv = 128, 32, 128, 128

    def spec(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    lowered = jax.jit(
        lambda s, q, k, v, g, beta, live: kda.kda_step(
            s, 7, q, k, v, g, beta, live),
        donate_argnums=0).lower(
            spec((b + 1, 11, h, dk, dv)), spec((b, h, dk)), spec((b, h, dk)),
            spec((b, h, dv)), spec((b, h, dk)), spec((b, h)),
            spec((b,), jnp.bool_))
    assert lowered.as_text().count("tpu_custom_call") == 1
    assert "kda_decode" in lowered.as_text()
    compiled = lowered.compile()
    cost = progcache.analyze_compiled(compiled)
    state = (b + 1) * 11 * h * dk * dv * 4
    assert cost["alias_bytes"] >= state and cost["temp_bytes"] < state // 100


def test_tpu_kda_step_program_at_the_cells_sizes(kda_engine, one_chip,
                                                 monkeypatch):
    """The step of the KDA + latent model compiled for a v5e at the cell's
    sizes: the pool counts the TWO latent layers alone (rows of 640), the
    eleven KDA layers' states ``(129, 11, 32, 128, 128)`` float32 and tails
    ``(129, 11, 288, 128)`` are per-slot state donated beside it; one Mosaic
    call a kind of kernel (``kda_decode`` and ``mla_decode`` each traced once
    for all their layers, and the two of ``held_experts``); the step's
    arguments are the 6.51 GB of weights + the pool + 3.08 GB of state; its
    temporaries stay under ONE KDA layer's state of the batch (268 MB): no
    instruction copies the states, the tails (the folded convolution step:
    unfolded, XLA:TPU laid the tails out slot-minor and copied all 105 MB
    there and back a layer) or the pool; no layer's weights are cut out of
    a stack; and the grouped products carry the row tile
    ``moe.layer_row_tile`` names for 128 tokens x 8 choices over 512."""
    import re

    import jax.numpy as jnp

    engine = kda_engine
    assert engine.kv.shape[1:] == (2, 256, 640) and engine.paged_layers == 2
    assert engine.state["s"].shape == (129, 11, 32, 128, 128)
    assert engine.state["tail"].shape == (129, 11, 288, 128)   # 3 x 12288
    assert engine.cache_row_bytes == 1280
    assert engine.state_bytes == 11 * (2097152 + 73728)
    stats = engine.stats()
    assert stats["prefill_piece"] == 2048 and stats["max_prompt"] == 4096
    tile = moe.layer_row_tile(128, 8, 512, jnp.bfloat16)
    assert tile == 16 and stats["moe_row_tile"] == {"step": 16,
                                                    "prefill": {2048: 64}}
    _as_on_a_tpu(monkeypatch)
    lowered, compiled = _tpu_program(engine, one_chip, "step", monkeypatch)
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 5
    assert all(name in text for name in ("kda_decode", "mla_decode",
                                         "moe_rows", "moe_rows_back"))
    cost = progcache.analyze_compiled(compiled)
    held = engine.kv.nbytes + sum(a.nbytes for a in engine.state.values())
    weights = 2 * 3256770784 - 4 * 11 * 32 + 4 * 11 * 32 * 2  # A_log float32
    assert abs(cost["argument_bytes"] - (weights + held)) < 1e6, cost
    layer_state = 128 * 32 * 128 * 128 * 4
    assert cost["temp_bytes"] < layer_state, cost
    assert cost["alias_bytes"] >= held
    optimised = compiled.as_text()
    entry = optimised[optimised.index("\nENTRY ") + 1:]
    shapes = ("f32[129,11,32,128,128]", "bf16[129,11,288,128]",
              "bf16[" + ",".join(str(n) for n in engine.kv.shape) + "]")
    assert not [line.strip()[:160] for line in entry.splitlines()
                if " copy(" in line and any(
                    s in line[:line.index(" copy(")] for s in shapes)]
    # a KDA layer's 105 MB ``in_w`` is an argument: the program makes no
    # array of its shape
    assert not [line[:160] for line in entry.splitlines()
                if re.match(r"\s*%?[\w.\-]+ = bf16\[(1,)?2560,20512\]", line)
                and " parameter(" not in line
                and "S(1)}" not in line[:line.index(" = ") + 80]]
    tilings = re.findall(r'ragged_dot_tiling="(\d+),(\d+),(\d+)"', optimised)
    assert len(tilings) >= 3 * 12 and all(      # 3 products x 12 layers
        t[0] == str(tile) for t in tilings), tilings


def test_tpu_kda_piece_program_at_the_cells_sizes(kda_engine, one_chip,
                                                  monkeypatch):
    """The ONE prefill program of the same cell, lowered for a v5e (not
    compiled here: 40 s; compile-only its temporaries are 1.37 GB under
    10.94 of arguments, and every run on the chip peaks at 11.0 GB: PERF.md
    section 4): a piece of 2,048 positions of a prompt of up to 4,096, the
    whole page table of 16 pages in the packed array. The latent layers'
    continued forward goes through Mosaic (``mla_prefill_from``, once a
    latent layer) and reads the pool through the page table; the KDA layers
    read nothing of it — the chunked rule is the ``kda_prefill`` kernel,
    state in, state out: ONE Mosaic body (a jitted function, lowered once)
    called once a KDA layer —; pool and state are donated."""
    import jax

    engine = kda_engine
    _as_on_a_tpu(monkeypatch)
    lowered = _traced(engine, one_chip, "prefill", monkeypatch).lower()
    (packed,) = _host_arguments(engine, lowered)
    assert packed.shape == (5 + 16 + 2048,)
    text = lowered.as_text()
    assert all(name in text for name in ("mla_prefill_from", "moe_rows",
                                         "moe_rows_back"))
    assert "kda_decode" not in text
    assert engine.stats()["delta_rule"] == "kda_prefill"
    assert text.count("call @_kda_prefill(") == len(engine.model.kda_layers)
    assert text.count('kernel_name = "kda_prefill"') == 1
    donated = [info.donated for info in
               jax.tree_util.tree_leaves(lowered.args_info)]
    resident = len(jax.tree_util.tree_leaves(engine._params))
    assert donated[resident:resident + 3] == [True, True, True]
    assert not any(donated[:resident])


def test_tpu_kda_piece_program_runs_the_rule_as_one_kernel(one_chip,
                                                           monkeypatch):
    """The piece program with the channel-decay rule in its two forms,
    COMPILED for a v5e — at the published 32 heads of 128 x 128 but one KDA
    layer (dense MLP) and one latent layer (experts) of narrow widths, 1,024
    positions, because the XLA form of two layers alone compiles for 15 s.
    As on a TPU the KDA layer is one ``kda_prefill`` Mosaic call and no
    chunk array of the XLA form (``(heads, chunks, 64, ..)``) is left; the
    kernel's program needs no more temporaries than the einsums' (at the
    cell's own size 1,119,729,152 B against 1,367,903,232: the engine's
    compile log on the chip, PERF.md section 6 PR 52)."""
    import json
    import os

    import jax

    from mxnet_tpu.models import kda_mla_moe
    from mxnet_tpu.ops import kda

    root = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
    with open(os.path.join(root, "configs", "ling-3.0-flash-vl.json")) as f:
        cfg = json.load(f)["model"]
    cfg.pop("kind")
    cfg.update(layers=[1, 5], num_layers=2, hidden_size=512, dense_width=512,
               expert_width=128, vocab_size=1024, experts_held=8,
               kv_rank=128, max_length=2048)
    shapes = jax.eval_shape(lambda: kda_mla_moe.init_params(cfg, 0))
    engine = DecodeEngine(kda_mla_moe.KDAMLAMoEDecodeModel(cfg, params=shapes),
                          slots=2, page_size=256, num_pages=17,
                          prompt_buckets=[1024])
    _as_on_a_tpu(monkeypatch)

    def compiled_piece():
        _, compiled = _tpu_program(_anew(engine), one_chip, "prefill",
                                   monkeypatch)
        text = compiled.as_text()
        calls = [line for line in text.splitlines()
                 if "custom-call(" in line and "kda_prefill" in line]
        return (len(calls), "f32[32,16,64," in text,
                progcache.analyze_compiled(compiled)["temp_bytes"])

    assert engine.stats()["delta_rule"] == "kda_prefill"
    calls, chunks, kernel = compiled_piece()
    assert calls == len(engine.model.kda_layers) == 1 and not chunks
    monkeypatch.setattr(kda, "chunked_form", lambda *a, **k: "xla")
    assert engine.stats()["delta_rule"] == "xla"
    calls, chunks, einsums = compiled_piece()
    assert calls == 0 and chunks
    print(f"piece temp_bytes: kda_prefill {kernel}, XLA {einsums}")
    assert kernel <= einsums, (kernel, einsums)


def test_gdn_prefill_traces_what_it_traced_before_the_solve_was_shared():
    """``gdn_prefill``'s body calls ``gated_delta._chunk_inverses`` — the
    substitution it shares with ``kda_prefill`` since PR 52 — where it had
    the same lines inline: the traced program (every equation of the kernel
    body, no path, no line) is the one the commit before made, by its hash —
    taken there (b31d503) and here with the lines below, 4 value heads on 2
    key heads of 128 x 128 over 128 tokens. A deliberate change to the
    kernel moves it; so does another jax."""
    import hashlib
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import gated_delta

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    jaxpr = jax.jit(lambda *a: gated_delta._gdn_prefill(*a, False)).trace(
        spec(128, 256), spec(128, 256), spec(128, 512), spec(128, 4),
        spec(128, 4), spec(4, 128, 128)).jaxpr
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))
    assert "gdn_prefill" in text and len(text) > 50000
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "071917ce7b4d917089b536e271ef5527e12ee94ee939d8399aabcd897e1a0c7e")


# What PR 50 gave the code these cells share — a head-wise gate as an optional
# leaf of ``models/mla_moe.py``'s attention halves (``_head_gate``), a grouped
# router as a fourth branch of ``ops/moe.py``'s ``expert_layer`` — is not on
# the path of the models that were there: each one's programs are traced twice
# in this process, as the code stands and with both additions cut off (the gate
# the identity it is without its leaf, the grouped router refusing every call),
# and the two jaxprs are the same text, kernel bodies included. Nothing is
# pasted from another commit, so a later change to these programs moves both
# sides alike.

def _anew(engine):
    """A second engine around the same model: jax hands a jit of a bound
    method it has traced before that trace, whatever a module global has been
    patched to since, so a program is traced anew through an engine of its
    own."""
    return DecodeEngine(engine.model, slots=engine.slots,
                        page_size=engine.page_size,
                        num_pages=engine.num_pages,
                        prompt_buckets=list(engine.buckets))


def _without_pr50(monkeypatch):
    from mxnet_tpu.models import mla_moe

    def refuse(*args, **kwargs):
        raise AssertionError("the grouped router was called")

    monkeypatch.setattr(mla_moe, "_head_gate", lambda cfg, lp, x, o: o)
    monkeypatch.setattr(moe, "route_grouped", refuse)


@pytest.mark.parametrize("kind", ["step", "prefill"])
@pytest.mark.parametrize("family", ["gdn_engine", "latent_engine",
                                    "scmoe_engine"])
def test_programs_of_the_models_that_share_code_take_nothing_new(
        family, kind, one_chip, monkeypatch, request):
    """``qwen3next-serve-closed-long``'s model (``gdn_moe``),
    ``sarvam105b-serve-closed``'s (``mla_moe``) and
    ``longcat-omni-serve-closed-128``'s (``mla_scmoe``)."""
    import re

    import jax

    engine = request.getfixturevalue(family)
    assert not [path for path, _ in jax.tree_util.tree_leaves_with_path(
        engine._params) if "og_w" in jax.tree_util.keystr(path)]
    _as_on_a_tpu(monkeypatch)

    def text(of):
        return re.sub(r" at 0x[0-9a-f]+", "", str(
            _traced(of, one_chip, kind, monkeypatch).jaxpr))

    as_it_stands = text(_anew(engine))
    _without_pr50(monkeypatch)
    assert text(_anew(engine)) == as_it_stands


def test_the_cut_that_comparison_makes_bites(kda_engine, one_chip,
                                              monkeypatch):
    """The same cut refuses the model that does route by groups: the
    comparison above would see a program that took the new branch."""
    _as_on_a_tpu(monkeypatch)
    _without_pr50(monkeypatch)
    with pytest.raises(AssertionError, match="grouped router"):
        _traced(_anew(kda_engine), one_chip, "step", monkeypatch)
