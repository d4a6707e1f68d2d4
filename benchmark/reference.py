"""The plain reference: a post-LN transformer written from the equations.

Embedding + learned positions (+ token types and an embedding LayerNorm for
the BERT kind), ``layers`` blocks of [fused-QKV attention, residual,
LayerNorm, GELU feed-forward, residual, LayerNorm], then the head: a final
LayerNorm and a vocabulary matmul (``causal_lm``), or dense + GELU +
LayerNorm + vocabulary matmul (``mlm``). Loss is the mean cross-entropy
over every position; the optimizer is Adam as MXNet documents it
(``lr_t = lr * sqrt(1 - b2**t) / (1 - b1**t)``,
``w -= lr_t * m / (sqrt(v) + eps)``).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernels, no cache, no batching beyond a row at a time. It imports nothing
of ``mxnet_tpu`` and makes its own weights from the seed; the runners hand
those same weights to the program.

``precision`` selects what the *controls* compute in: ``"f32"`` is the
reference proper; ``"high"`` (three bf16 passes), ``"bf16"`` and ``"fp8"``
(matmul operands rounded to e4m3 with a per-tensor scale, straight-through
in the backward pass) are the lower-precision stand-ins that ``correct`` has
to reject.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
STACKED = "layers."
LAYER_SHAPES = (  # name, shape in (u units, h hidden), kind of init
    ("qkv_w", "3u u", "w"), ("qkv_b", "3u", "b"),
    ("proj_w", "u u", "w"), ("proj_b", "u", "b"),
    ("ln1_g", "u", "g"), ("ln1_b", "u", "b"),
    ("ffn1_w", "h u", "w"), ("ffn1_b", "h", "b"),
    ("ffn2_w", "u h", "w"), ("ffn2_b", "u", "b"),
    ("ln2_g", "u", "g"), ("ln2_b", "u", "b"),
)


def param_spec(model: dict) -> dict:
    """name -> (shape, init kind) for a model description with the keys
    ``kind`` (causal_lm | mlm), ``vocab_size``, ``units``, ``hidden_size``,
    ``num_layers``, ``num_heads``, ``max_length`` (and ``num_token_types``
    for mlm). Dense weights are (out, in). The layers' weights are stacked:
    ``layers.qkv_w`` is (num_layers, 3u, u)."""
    u, h, v = model["units"], model["hidden_size"], model["vocab_size"]
    dims = {"u": u, "3u": 3 * u, "h": h}
    spec = {"embed": ((v, u), "w"), "pos": ((model["max_length"], u), "w")}
    if model["kind"] == "mlm":
        spec["type_embed"] = ((model["num_token_types"], u), "w")
        spec["embed_ln_g"], spec["embed_ln_b"] = ((u,), "g"), ((u,), "b")
        spec["mlm_dense_w"], spec["mlm_dense_b"] = ((u, u), "w"), ((u,), "b")
    spec["head_ln_g"], spec["head_ln_b"] = ((u,), "g"), ((u,), "b")
    spec["head_w"], spec["head_b"] = ((v, u), "w"), ((v,), "b")
    for name, shape, kind in LAYER_SHAPES:
        spec[STACKED + name] = ((model["num_layers"],)
                                + tuple(dims[d] for d in shape.split()), kind)
    return spec


def make_weights(model: dict, seed: int) -> dict:
    """Every weight from the seed, on the default device, in one jitted
    call of one draw per name: matrices, embeddings, biases and LayerNorm
    shifts N(0, 0.02), LayerNorm gains 1 + N(0, 0.02), all float32."""
    spec = param_spec(model)
    raw = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(spec.items()):
            x = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
            out[name] = 1.0 + x if kind == "g" else x
        return out

    return make(jnp.asarray(raw, jnp.uint32))


def per_leaf(tree: dict) -> dict:
    """The same values under one name per leaf of the program: a stacked
    ``layers.x`` entry (leading axis = layer) becomes ``layer{i}.x``."""
    out = {}
    for name, value in tree.items():
        if name.startswith(STACKED):
            for i in range(len(value)):
                out[f"layer{i}.{name[len(STACKED):]}"] = value[i]
        else:
            out[name] = value
    return out


# -- the forward pass ---------------------------------------------------------

_XLA_PRECISION = {"f32": "highest", "high": "high"}   # of float32 operands
_BF16_OPERANDS = ("bf16", "fp8")       # matmul operands cast to bfloat16

def _fp8(x):
    """x rounded to e4m3 at a per-tensor scale; gradient passes straight
    through (a cotangent cast to e4m3 unscaled would flush to zero)."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(x.dtype) / scale
    return x + jax.lax.stop_gradient(q - x)


def _matmul(x, w, precision):
    """x (..., in) times w (out, in) transposed, accumulated in float32."""
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    if precision in _BF16_OPERANDS:
        x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    return jnp.einsum("...i,oi->...o", x, w,
                      precision=_XLA_PRECISION.get(precision, "default"),
                      preferred_element_type=jnp.float32)


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu(x):  # the tanh form, which the program's models use
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(model, p, x, precision):
    """One post-LN block over one sequence x (S, U); ``p`` holds the layer's
    own weights under their short names."""
    s, u = x.shape
    heads = model["num_heads"]
    d = u // heads
    qkv = _matmul(x, p["qkv_w"], precision) + p["qkv_b"]
    q, k, v = (qkv[:, j * u:(j + 1) * u].reshape(s, heads, d)
               for j in range(3))
    if precision in _BF16_OPERANDS:
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    prec = _XLA_PRECISION.get(precision, "default")
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=prec,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    if model["kind"] == "causal_lm":
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    ctx = jnp.einsum("hqk,khd->qhd", probs, v, precision=prec,
                     preferred_element_type=jnp.float32).reshape(s, u)
    att = _matmul(ctx, p["proj_w"], precision) + p["proj_b"]
    x = _layer_norm(x + att, p["ln1_g"], p["ln1_b"])
    hid = _gelu(_matmul(x, p["ffn1_w"], precision) + p["ffn1_b"])
    out = _matmul(hid, p["ffn2_w"], precision) + p["ffn2_b"]
    return _layer_norm(x + out, p["ln2_g"], p["ln2_b"])


def logits_one(model, p, tokens, types=None, precision="f32"):
    """Logits (S, V) of one sequence of token ids (S,)."""
    s = tokens.shape[0]
    x = p["embed"][tokens]
    if model["kind"] == "mlm":
        # as the program's BERTModel has it: LayerNorm over word + type, the
        # positions added after it (BERT itself normalises all three)
        if types is not None:
            x = x + p["type_embed"][types]
        x = _layer_norm(x, p["embed_ln_g"], p["embed_ln_b"])
    x = x + p["pos"][:s]
    layers = {k[len(STACKED):]: w for k, w in p.items()
              if k.startswith(STACKED)}
    # one layer's program scanned over the stack, its activations recomputed
    # in the backward pass: compiles once and fits beside its own weights
    x, _ = jax.lax.scan(jax.checkpoint(
        lambda x_, lp: (_block(model, lp, x_, precision), None)), x, layers)
    if model["kind"] == "mlm":
        x = _gelu(_matmul(x, p["mlm_dense_w"], precision) + p["mlm_dense_b"])
    x = _layer_norm(x, p["head_ln_g"], p["head_ln_b"])
    return _matmul(x, p["head_w"], precision) + p["head_b"]


def _row_loss(model, p, row, precision):
    """Summed cross-entropy of one row (dict of ``tokens``, ``labels`` and,
    for mlm, ``types``)."""
    logits = logits_one(model, p, row["tokens"], row.get("types"), precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, row["labels"][:, None], 1))


# -- training: loss, gradients, Adam ------------------------------------------

def leaf_norms(tree: dict) -> dict:
    """The Euclidean norm of each leaf; of a stacked entry, one per layer."""
    def norm(name, x):
        axes = tuple(range(1 if name.startswith(STACKED) else 0, x.ndim))
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))

    return {k: norm(k, v) for k, v in tree.items()}


def train_steps(model, params, batches, lr, precision="f32", log=None):
    """Follow ``len(batches)`` Adam steps from ``params`` (not modified). A
    batch is a dict of (B, S) int arrays. Returns what ``correct`` compares,
    each under one name per leaf: the loss of each step, the norms of the
    first gradient, and the norms of the parameters' change after the last
    step."""

    @jax.jit
    def loss_and_grad(p, batch):
        n_tokens = batch["labels"].size

        def body(acc, row):
            loss, g = jax.value_and_grad(
                lambda p_: _row_loss(model, p_, row, precision))(p)
            return (acc[0] + loss,
                    jax.tree_util.tree_map(jnp.add, acc[1], g)), None

        zero = (jnp.float32(0), jax.tree_util.tree_map(jnp.zeros_like, p))
        (loss, grads), _ = jax.lax.scan(body, zero, batch)
        return loss / n_tokens, jax.tree_util.tree_map(
            lambda g: g / n_tokens, grads)

    def adam(p, g, m, v, t):
        lr_t = lr * jnp.sqrt(1.0 - ADAM_B2 ** t) / (1.0 - ADAM_B1 ** t)
        m = {k: ADAM_B1 * m[k] + (1 - ADAM_B1) * g[k] for k in p}
        v = {k: ADAM_B2 * v[k] + (1 - ADAM_B2) * jnp.square(g[k]) for k in p}
        p = {k: p[k] - lr_t * m[k] / (jnp.sqrt(v[k]) + ADAM_EPS) for k in p}
        return p, m, v

    adam = jax.jit(adam, donate_argnums=(0, 1, 2, 3))
    flat = lambda norms: {k: float(x) for k, x in  # noqa: E731
                          per_leaf(jax.device_get(norms)).items()}
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(jnp.copy, params)
        m = jax.tree_util.tree_map(jnp.zeros_like, p)
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        losses, grad_norms = [], None
        for t, batch in enumerate(batches, 1):
            loss, g = loss_and_grad(p, {k: jnp.asarray(b)
                                        for k, b in batch.items()})
            losses.append(float(loss))
            if log:
                log(f"reference step {t}: loss {losses[-1]:.4f}")
            if grad_norms is None:
                grad_norms = flat(jax.jit(leaf_norms)(g))
            p, m, v = adam(p, g, m, v, jnp.float32(t))
        delta = jax.jit(lambda a, b: leaf_norms(
            {k: a[k] - b[k] for k in a}))(p, params)
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": flat(delta)}


# -- serving: the logit gap of the served tokens --------------------------------

@functools.partial(jax.jit, static_argnames=("kind", "heads", "precision"))
def _position_gaps(p, seq, judge, *, kind, heads, precision):
    """At every position of one padded sequence: how far the logit of
    ``judge[i]`` lies below the largest logit, and which token is largest."""
    model = {"kind": kind, "num_heads": heads}
    logits = logits_one(model, p, seq, None, precision)
    took = jnp.take_along_axis(logits, judge[:, None], 1)[:, 0]
    return jnp.max(logits, axis=-1) - took, jnp.argmax(logits, axis=-1)


def served_gaps(model, params, prompt, served, precision="f32", judge=None):
    """One full forward over ``prompt + served`` (teacher-forced). For each
    served position, how far the logit of the ``judge`` token (the served
    one unless given) lies below the largest logit there: (gaps (n_served,),
    the tokens this precision puts first)."""
    n, m = len(prompt), len(served)
    seq = np.concatenate([prompt, served]).astype(np.int32)
    # pad to a multiple of 128 so that a handful of programs serves every
    # length; causal masking keeps the pad out of the positions read
    size = min(-(-(n + m) // 128) * 128, model["max_length"])
    padded, judged = np.zeros((2, size), np.int32)
    padded[:n + m - 1] = seq[:-1]
    judged[n - 1:n - 1 + m] = served if judge is None else judge
    with jax.default_matmul_precision("highest"):
        gaps, first = _position_gaps(
            params, padded, judged, kind=model["kind"],
            heads=model["num_heads"], precision=precision)
    return (np.asarray(gaps, np.float64)[n - 1:n - 1 + m],
            np.asarray(first)[n - 1:n - 1 + m])
