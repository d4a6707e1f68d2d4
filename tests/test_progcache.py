"""Persistent AOT program cache (mxnet_tpu/progcache.py,
docs/PERFORMANCE.md "Program cache and cold start").

- key derivation: one shared ``program_key`` — deterministic across
  processes, distinct across models/statics, canonicalization units;
- structure: hit / miss / reject (truncated entry, CRC corruption,
  foreign-platform fingerprint, stale-code fingerprint) — every bad entry
  degrades to a plain compile with a counted reject, never a crash;
- bitwise parity: a cache-hit engine answers bit-for-bit what the
  fresh-compile engine answered (serve buckets AND the fused update);
- bounds kept: TraceLinter's serve program bound stays green on hits, the
  fused update still dispatches one program per step;
- artifact payloads: ``serve.ship_programs`` → ``serve.load`` warms from
  the shipped ``programs/`` dir;
- elastic-rejoin prewarm: a checkpoint-derived ``prewarm_batch`` derives
  the SAME key a real fit's engine uses (hit, not a wasted compile);
- keep-last-N GC;
- the one build path: ``progcache.build`` with no cache, on a miss, on a
  hit and on a hit whose writer left no cost; every site's ``compile_log``
  entry carries its common keys; and no other module of the package
  compiles a lowering or names the deleted second MFU;
- the chaos leg (slow): a ProcReplica SIGKILLed and respawned against the
  same cache dir becomes ready with zero fresh XLA compiles.
"""
import ast
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, profiler, progcache
from mxnet_tpu import optimizer as opt_mod
from mxnet_tpu import serve
from mxnet_tpu import symbol as sym

pytestmark = pytest.mark.progcache


@pytest.fixture()
def cache_dir(tmp_path):
    """Arm the process-global cache at a per-test dir; disarm after."""
    d = str(tmp_path / "progcache")
    progcache.configure(d)
    yield d
    progcache.configure(None)
    os.environ.pop("MXNET_PROGCACHE_DIR", None)
    os.environ.pop("MXNET_PROGCACHE", None)
    progcache.reset()


def _mlp(hidden=8, in_dim=4):
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=hidden, name="fc1")
    net = sym.softmax(net, name="prob")
    rng = np.random.RandomState(0)
    arg = {"fc1_weight": rng.randn(hidden, in_dim).astype(np.float32) * 0.3,
           "fc1_bias": rng.randn(hidden).astype(np.float32)}
    return net, arg


def _engine(net, arg, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("lint", "off")
    return serve.InferenceEngine(net, arg, {}, **kw)


# ---------------------------------------------------------------------------
# key derivation
# ---------------------------------------------------------------------------

def test_program_key_deterministic_and_distinct():
    statics = ((b"graph", ("data",), 0.0),
               {"b": 1, "a": 2}, float("0.1"), type(int))
    k1 = progcache.program_key("serve", "bucket4", statics)
    k2 = progcache.program_key("serve", "bucket4", statics)
    assert k1 == k2 and k1.site == "serve" and k1.label == "bucket4"
    assert len(k1.digest) == 64
    # any drift in site/label/statics changes the digest
    assert progcache.program_key("update", "bucket4", statics) != k1
    assert progcache.program_key("serve", "bucket8", statics) != k1
    assert progcache.program_key(
        "serve", "bucket4", ((b"graph2", ("data",), 0.0),)) != k1
    # dict ordering canonicalizes away
    assert progcache.program_key("s", "l", {"a": 1, "b": 2}) \
        == progcache.program_key("s", "l", {"b": 2, "a": 1})


def test_env_fingerprint_fields():
    fp = progcache.env_fingerprint()
    for field in ("platform", "device_kind", "num_devices", "jax",
                  "jaxlib", "code"):
        assert field in fp, fp
    assert fp["platform"] == "cpu"
    # cached copy is defensive — mutating it must not poison the source
    fp["platform"] = "mars"
    assert progcache.env_fingerprint()["platform"] == "cpu"


# ---------------------------------------------------------------------------
# hit / miss / reject structure
# ---------------------------------------------------------------------------

def _put_one(cache, tag="x", shape=(3, 2)):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x * 2.0)
    compiled = fn.lower(jnp.zeros(shape)).compile()
    key = progcache.program_key("test", tag, (tag, shape))
    assert cache.put(key, compiled, meta={"bucket": 1})
    return key, compiled


def test_roundtrip_hit_and_miss(tmp_path):
    cache = progcache.ProgramCache(str(tmp_path))
    key, _ = _put_one(cache)
    assert cache.stats["write"] == 1
    miss = progcache.program_key("test", "other", ("other",))
    assert cache.get(miss) is None
    assert cache.stats["miss"] == 1
    entry = cache.get(key)
    assert entry is not None and entry.meta["bucket"] == 1
    assert cache.stats["hit"] == 1 and cache.stats["reject"] == 0
    import jax.numpy as jnp

    out = entry.executable(jnp.ones((3, 2)))
    np.testing.assert_array_equal(np.asarray(out), np.full((3, 2), 2.0))


def test_hit_runs_on_the_devices_it_was_compiled_for(tmp_path):
    """A one-device program read back on this 8-device host is loaded AND
    called: ``deserialize_and_load`` defaults to every device of the
    backend, which loads fine and then fails at the first call ("expected
    8 shards, got 1") — so the entry records the program's devices. Device
    3, not 0, so a loader that merely picked the default device fails."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[3]
    x = jax.device_put(jnp.ones((3, 2)), dev)
    compiled = jax.jit(lambda x: x * 2.0).lower(x).compile()
    cache = progcache.ProgramCache(str(tmp_path))
    key = progcache.program_key("test", "dev3", ("dev3",))
    assert cache.put(key, compiled)
    out = cache.get(key).executable(x)
    np.testing.assert_array_equal(np.asarray(out), np.full((3, 2), 2.0))
    assert out.devices() == {dev}


def test_truncated_entry_rejects(tmp_path):
    cache = progcache.ProgramCache(str(tmp_path))
    key, _ = _put_one(cache)
    path = cache._path(key.digest)
    with open(path, "rb") as f:
        raw = f.read()
    with open(path, "wb") as f:
        f.write(raw[: len(raw) // 2])
    assert cache.get(key) is None
    assert cache.stats["reject"] == 1 and cache.stats["hit"] == 0


def test_corrupt_byte_rejects(tmp_path):
    cache = progcache.ProgramCache(str(tmp_path))
    key, _ = _put_one(cache)
    path = cache._path(key.digest)
    with open(path, "r+b") as f:
        f.seek(len(progcache._MAGIC) + 30)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    assert cache.get(key) is None
    assert cache.stats["reject"] == 1


def test_foreign_fingerprint_rejects(tmp_path):
    cache = progcache.ProgramCache(str(tmp_path))
    real = progcache.env_fingerprint()
    try:
        # entry written by a "TPU process with other code"
        progcache._env_fp_cache[0] = dict(real, platform="tpu",
                                          code="f" * 64)
        key, _ = _put_one(cache)
    finally:
        progcache._env_fp_cache[0] = dict(real)
    assert cache.get(key) is None, \
        "a foreign-platform executable must never load"
    assert cache.stats["reject"] == 1


def test_wrong_digest_filename_rejects(tmp_path):
    cache = progcache.ProgramCache(str(tmp_path))
    key, _ = _put_one(cache)
    other = progcache.program_key("test", "other", ("other",))
    os.rename(cache._path(key.digest), cache._path(other.digest))
    assert cache.get(other) is None  # header digest disagrees with name
    assert cache.stats["reject"] == 1


def test_gc_keep_last_n(tmp_path):
    cache = progcache.ProgramCache(str(tmp_path), keep=2)
    keys = []
    for i in range(4):
        k, _ = _put_one(cache, tag=f"t{i}", shape=(i + 1, 2))
        keys.append(k)
        # strict mtime ordering even on coarse-grained filesystems
        stamp = time.time() - 100 + i
        os.utime(cache._path(k.digest), (stamp, stamp))
    cache.gc()
    assert cache.entries() <= 2
    # the most recently used survives, the oldest is gone
    assert cache.get(keys[-1]) is not None
    assert cache.get(keys[0]) is None


# ---------------------------------------------------------------------------
# build — the one place a compiled program comes to exist
# ---------------------------------------------------------------------------

_COMMON = {"cache_hit", "program_key"} | set(progcache.COST_FIELDS)


_BUILD_CASES = ["no_cache", "miss", "hit", "hit_without_cost"]


@pytest.mark.parametrize("case", _BUILD_CASES)
def test_build(tmp_path, case):
    import jax
    import jax.numpy as jnp

    # a program of its own a case: XLA:CPU dedupes identical kernels
    # process-wide, and a twin's export is refused (ProgramCache.put)
    scale = 2.0 + _BUILD_CASES.index(case)
    fn = jax.jit(lambda x: (x * scale + 1.0).sum())
    x = jnp.arange(6.0).reshape(2, 3)
    key = progcache.program_key("test", case, (case, x.shape))
    cache = None if case == "no_cache" \
        else progcache.ProgramCache(str(tmp_path))
    hit = case.startswith("hit")
    if hit:  # what an earlier process left: with its cost, or without
        compiled = fn.lower(x).compile()
        cost = {} if case == "hit_without_cost" \
            else progcache.analyze_compiled(compiled)
        assert cache.put(key, compiled, meta=dict(cost, kind="t"))
    writes = cache.stats["write"] if cache else 0

    exe, entry = progcache.build(fn, (x,), key=key, cache=cache,
                                 meta={"kind": "t"})
    assert float(exe(x)) == float(fn(x)) == 15.0 * scale + 6.0
    assert entry["cache_hit"] is hit
    assert entry["program_key"] == key.digest
    if case == "hit_without_cost":
        assert set(entry) == {"cache_hit", "program_key"}
    else:
        assert set(entry) == _COMMON
        assert entry["flops"] > 0 and entry["bytes_accessed"] > 0
    if cache is not None:
        # one put on a miss, none on a hit
        assert cache.stats["write"] - writes == (case == "miss")
        assert cache.stats["hit"] == hit
        if case == "miss":
            meta = cache.get(key).meta
            assert meta["kind"] == "t" and meta["flops"] == entry["flops"]
    # an unkeyed build is the same program with nothing to name it by
    _, bare = progcache.build(fn, (x,))
    assert set(bare) == _COMMON - {"program_key"}


def test_build_leaves_the_caller_on_its_jit_wrapper_if_lowering_fails():
    """The one guard: a failure to lower hands ``jitted`` itself back with
    an entry that has no cost; a compile error is the caller's to see."""
    import jax

    class Unlowerable:
        def lower(self, *a, **k):
            raise NotImplementedError("no AOT lowering here")

    jitted = Unlowerable()
    exe, entry = progcache.build(jitted, (1,))
    assert exe is jitted and entry == {"cache_hit": False}

    class Uncompilable:
        def lower(self, *a, **k):
            return self

        def compile(self):
            raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED")

    with pytest.raises(jax.errors.JaxRuntimeError):
        progcache.build(Uncompilable(), (1,))


def _log_decode():
    from mxnet_tpu.models.transformer import transformer_lm

    lm = transformer_lm(vocab_size=31, units=16, hidden_size=32,
                        num_layers=1, num_heads=2, max_length=32,
                        dropout=0.0)
    lm.initialize()
    lm(nd.zeros((1, 8)))
    eng = serve.DecodeEngine(lm, slots=2, page_size=8, num_pages=8,
                             prompt_buckets=[8])
    assert eng.warmup() == 2
    assert eng.stats()["step_program"]["bytes_accessed"] > 0
    return eng.compile_log


def _log_serve():
    eng = _engine(*_mlp())
    eng.warmup((4,))
    return eng.compile_log


def _log_update():
    up = opt_mod.Updater(opt_mod.create("sgd", learning_rate=0.1))
    w = nd.array(np.ones((5, 4), np.float32))
    up.update_batch([0], [w.ones_like()], [w])
    return up._engine.compile_log


def _log_executor():
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Variable("data"), num_hidden=3, name="fc"),
        name="softmax")
    ex = net.simple_bind(mx.cpu(), data=(2, 4), softmax_label=(2,))
    ex.forward(is_train=True, data=np.ones((2, 4), np.float32))
    ex.backward()
    return ex.compile_log


def _log_cachedop():
    from mxnet_tpu import gluon

    net = gluon.nn.Dense(3)
    net.initialize()
    net.hybridize()
    net(nd.ones((2, 4)))
    return net._cached_op.compile_log


@pytest.mark.parametrize("site, observed, programs", [
    (_log_decode, True, 2), (_log_decode, False, 2), (_log_serve, True, 3),
    (_log_update, True, 1), (_log_executor, True, 2),
    (_log_cachedop, True, 1)],
    ids=["decode", "decode-unobserved", "serve", "update", "executor",
         "cachedop"])
def test_every_sites_compile_log_entry_has_builds_keys(site, observed,
                                                       programs):
    """Each site adds its own keys to what ``build`` hands back; the
    decode engine builds ahead of time whether or not anyone watches, the
    others (no cache armed) when ``obs`` is on."""
    from mxnet_tpu import obs

    obs.reset()
    if observed:
        obs.enable()
    try:
        log = site()
        recorded = obs.device.costs()
    finally:
        obs.disable()
        obs.reset()
    assert len(log) == programs
    for entry in log:
        assert _COMMON < set(entry), sorted(entry)
        assert entry["cache_hit"] is False
        assert len(entry["program_key"]) == 64 and entry["flops"] > 0
    # the registry mirrors the records only while someone watches
    digests = {c["program_key"] for c in recorded.values()}
    assert digests <= {e["program_key"] for e in log}
    assert bool(digests) is observed


def test_no_module_but_progcache_compiles_a_lowering():
    """The five hand-written build paths and the second MFU stay gone: in
    ``mxnet_tpu/`` only ``progcache.build`` calls ``.compile()`` on a
    lowering, and nothing names ``get_peak`` / ``annotate_span``."""
    root = os.path.dirname(os.path.abspath(progcache.__file__))
    compiles, named = [], []
    for dirpath, _dirs, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, root)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                # re.compile(pattern) and friends take arguments; a
                # lowering's .compile() takes none
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "compile" \
                        and not node.args and not node.keywords:
                    compiles.append(f"{rel}:{node.lineno}")
                ident = getattr(node, "attr", None) or getattr(node, "id",
                                                               None)
                if ident in ("get_peak", "annotate_span"):
                    named.append(f"{rel}:{node.lineno}")
    assert [c.split(":")[0] for c in compiles] == ["progcache.py"], compiles
    assert named == []


# ---------------------------------------------------------------------------
# serve engine integration
# ---------------------------------------------------------------------------

def test_engine_cache_hit_bitwise_parity(cache_dir):
    net, arg = _mlp()
    e1 = _engine(net, arg)
    assert e1.warmup((4,)) == len(e1.buckets)
    assert all(e.get("cache_hit") is False for e in e1.compile_log)
    x = np.random.RandomState(3).rand(3, 4).astype(np.float32)
    ref = e1.predict(x)

    e2 = _engine(net, arg)
    assert e2.warmup((4,)) == len(e2.buckets)
    assert [e.get("cache_hit") for e in e2.compile_log] \
        == [True] * len(e2.buckets), "warm engine must hit every bucket"
    assert e2.cache_hits == len(e2.buckets)
    out = e2.predict(x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out)), \
        "a deserialized executable is the same machine code — bitwise"
    # the program bound is still proven, hits included
    from mxnet_tpu.analysis.trace import TraceLinter

    assert TraceLinter().check_serve_engine(e1) == []
    assert TraceLinter().check_serve_engine(e2) == []
    # compile_log entries carry the shared program_key digest
    assert all(len(e.get("program_key", "")) == 64
               for e in e1.compile_log + e2.compile_log)
    # concurrent warmup logs buckets in completion order — compare as sets
    assert {e["program_key"] for e in e1.compile_log} \
        == {e["program_key"] for e in e2.compile_log}


def test_engine_key_drift_misses_not_collides(cache_dir):
    net, arg = _mlp(hidden=8)
    e1 = _engine(net, arg)
    e1.warmup((4,))
    # a DIFFERENT graph with identical input avals must not hit
    net2, arg2 = _mlp(hidden=6)
    e2 = _engine(net2, arg2)
    e2.warmup((4,))
    assert all(e.get("cache_hit") is False for e in e2.compile_log)
    # so must a changed engine static (pad value)
    e3 = _engine(net, arg, pad_value=1.0)
    e3.warmup((4,))
    assert all(e.get("cache_hit") is False for e in e3.compile_log)


def test_corrupt_cache_degrades_to_compile(cache_dir):
    net, arg = _mlp()
    e1 = _engine(net, arg)
    e1.warmup((4,))
    for f in os.listdir(cache_dir):
        if f.endswith(".mxprog"):
            path = os.path.join(cache_dir, f)
            with open(path, "r+b") as fh:
                fh.seek(20)
                fh.write(b"\xde\xad\xbe\xef")
    e2 = _engine(net, arg)
    assert e2.warmup((4,)) == len(e2.buckets)  # served anyway
    assert all(e.get("cache_hit") is False for e in e2.compile_log)
    assert e2._progcache.stats["reject"] >= len(e2.buckets)
    x = np.random.RandomState(3).rand(2, 4).astype(np.float32)
    np.testing.assert_allclose(np.asarray(e1.predict(x)),
                               np.asarray(e2.predict(x)), rtol=0, atol=0)


def test_warmup_concurrent_matches_serial(cache_dir):
    net, arg = _mlp()
    e_serial = _engine(net, arg, max_batch_size=8)
    assert e_serial.warmup((4,), concurrency=1) == len(e_serial.buckets)
    e_conc = _engine(net, arg, max_batch_size=8, progcache_dir=None)
    # fresh dir so concurrency exercises the compile path, not hits
    e_conc._progcache = progcache.ProgramCache(cache_dir + "-conc")
    e_conc._key_statics = e_conc._compute_key_statics()
    assert e_conc.warmup((4,), concurrency=4) == len(e_conc.buckets)
    sigs = [e["sig"] for e in e_conc.compile_log]
    assert len(set(map(repr, sigs))) == len(sigs) == len(e_conc.buckets)
    from mxnet_tpu.analysis.trace import TraceLinter

    assert TraceLinter().check_serve_engine(e_conc) == []
    x = np.random.RandomState(5).rand(6, 4).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(e_serial.predict(x)),
                                  np.asarray(e_conc.predict(x)))


def test_warmup_idempotent_second_call_zero(cache_dir):
    net, arg = _mlp()
    e = _engine(net, arg)
    assert e.warmup((4,)) == len(e.buckets)
    assert e.warmup((4,)) == 0  # already-compiled buckets skip entirely


def test_ship_programs_and_load(cache_dir, tmp_path):
    # build + warm WITHOUT the global cache, then ship the payload
    progcache.configure(None)
    net, arg = _mlp()
    prefix = str(tmp_path / "model")
    mx.model.save_checkpoint(prefix, 0, net,
                             {k: nd.array(v) for k, v in arg.items()}, {})
    e1 = _engine(net, arg)
    e1.warmup((4,))
    n = serve.ship_programs(e1, prefix)
    assert n == len(e1.buckets)
    assert os.path.isdir(serve.programs_dir_for(prefix))
    eng = serve.load(prefix, epoch=0, max_batch_size=4, lint="off")
    assert eng._progcache is not None \
        and eng._progcache.root == serve.programs_dir_for(prefix)
    assert eng.warmup((4,)) == len(eng.buckets)
    assert all(e.get("cache_hit") for e in eng.compile_log)
    x = np.random.RandomState(7).rand(3, 4).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(e1.predict(x)),
                                  np.asarray(eng.predict(x)))


# ---------------------------------------------------------------------------
# fused update engine integration
# ---------------------------------------------------------------------------

def _one_step(cache_hit_expected, seed=42):
    rng = np.random.RandomState(seed)
    opt = opt_mod.create("adam", learning_rate=0.05, rescale_grad=0.5)
    up = opt_mod.Updater(opt)
    ws = [nd.array(rng.randn(5, 4).astype(np.float32)),
          nd.array(rng.randn(3).astype(np.float32))]
    gs = [nd.array(rng.randn(5, 4).astype(np.float32)),
          nd.array(rng.randn(3).astype(np.float32))]
    up.update_batch([0, 1], gs, ws)
    eng = up._engine
    assert eng is not None and len(eng.compile_log) == 1
    assert eng.compile_log[0].get("cache_hit") is cache_hit_expected
    assert len(eng.compile_log[0].get("program_key", "")) == 64
    return [w.asnumpy() for w in ws], up


def test_fused_cache_hit_bitwise_and_dispatch_bound(cache_dir):
    w_fresh, _ = _one_step(cache_hit_expected=False)
    w_hit, up = _one_step(cache_hit_expected=True)
    for a, b in zip(w_fresh, w_hit):
        np.testing.assert_array_equal(a, b), \
            "cache-hit update must be bitwise the fresh-compile update"
    # the one-program-per-step bound holds on the deserialized executable
    rng = np.random.RandomState(1)
    ws = [nd.array(rng.randn(5, 4).astype(np.float32)),
          nd.array(rng.randn(3).astype(np.float32))]
    gs = [w.zeros_like() for w in ws]
    with profiler.count_dispatches() as c:
        up.update_batch([0, 1], gs, ws)
    assert c.total_compiled <= 2, c.as_dict()


def test_updater_prewarm_populates_without_mutating(cache_dir):
    rng = np.random.RandomState(0)
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    up = opt_mod.Updater(opt)
    ws = [nd.array(rng.randn(4, 3).astype(np.float32))]
    before = ws[0].asnumpy().copy()
    assert up.prewarm_batch([0], ws)
    np.testing.assert_array_equal(before, ws[0].asnumpy())
    assert opt._index_update_count == {}, "prewarm must not advance counts"
    assert up._engine.compile_log[-1].get("cache_hit") is False
    # a second updater (the restarted worker) hits from disk
    opt2 = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    up2 = opt_mod.Updater(opt2)
    ws2 = [nd.array(rng.randn(4, 3).astype(np.float32))]
    assert up2.prewarm_batch([0], ws2)
    assert up2._engine.compile_log[-1].get("cache_hit") is True


def test_module_fit_then_checkpoint_prewarm_hits(cache_dir, tmp_path):
    """The elastic-rejoin warm path derives the SAME program key from the
    shared checkpoint that the live fit's engine derives from its bound
    executor — so a quarantined rejoiner's prewarm is a cache HIT."""
    from mxnet_tpu.checkpoint import as_manager
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.module import Module

    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=4, name="fc1")
    net = sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    x = rng.randn(8, 5).astype(np.float32)
    y = np.array([0, 1, 2, 3, 0, 1, 2, 3], np.float32)
    it = NDArrayIter(x, y, batch_size=4)
    ckpt = str(tmp_path / "ckpt")
    mod = Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            checkpoint=ckpt, resume="never")
    pc = progcache.cache()
    writes = pc.stats["write"]
    assert writes >= 1

    mod2 = Module(net, context=mx.cpu())
    mgr = as_manager(ckpt)
    try:
        hits_before = pc.stats["hit"]
        assert mod2._prewarm_update_programs(
            mgr, "sgd", {"learning_rate": 0.1, "momentum": 0.9}, it)
        assert pc.stats["hit"] == hits_before + 1, \
            "checkpoint-derived prewarm must hit the fit's cached program"
        assert pc.stats["write"] == writes  # nothing recompiled
    finally:
        mgr.close()


# ---------------------------------------------------------------------------
# the chaos leg: replica SIGKILL → respawn warms from disk
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.chaos
def test_proc_replica_restart_warms_from_cache(tmp_path):
    from mxnet_tpu.model import save_checkpoint
    from mxnet_tpu.serve.fleet import ReplicaPool

    net, arg = _mlp()
    prefix = str(tmp_path / "model")
    save_checkpoint(prefix, 0, net,
                    {k: nd.array(v) for k, v in arg.items()}, {})
    # one CPU device a replica: the suite's eight-device emulation changes
    # XLA:CPU codegen so bucket kernels hash-collide across programs, and
    # progcache (correctly) refuses executables that are not self-contained
    pool = ReplicaPool.spawn(
        prefix, 1,
        args=["--epoch", "0", "--warmup-shape", "4", "--max-batch-size", "4"],
        env={"JAX_PLATFORMS": "cpu", "MXNET_PROGCACHE": "1",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
             "MXNET_PROGCACHE_DIR": str(tmp_path / "progcache")},
        probe_interval=0.2, backoff_base=0.1, backoff_cap=1.0,
        ready_timeout=180).start()

    def compile_counters():
        cli = serve.ServeClient(*pool.members()[0].addr, timeout=5.0)
        try:
            eng = cli.stats()["engine"]
        finally:
            cli.close()
        return int(eng["compiles"]), int(eng["cache_hits"])

    try:
        compiles, hits = compile_counters()
        assert compiles - hits == 3  # cold: buckets(4) = [1, 2, 4]
        pool.kill(0)  # SIGKILL: no graceful cache flush
        m0 = pool.members()[0]
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not (
                m0.restarts >= 1 and m0.state == "ready"):
            time.sleep(0.2)
        assert m0.restarts >= 1 and m0.state == "ready"
        compiles, hits = compile_counters()
        assert hits == compiles == 3  # the respawn compiled nothing fresh
    finally:
        pool.stop()
