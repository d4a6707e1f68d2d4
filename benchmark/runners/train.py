"""Runner ``train``: tokens through ``parallel.ShardedTrainer.step``.

Set-up builds ONE trainer, drives it through its first three steps by the
window's own call and feed, and hands that same object to the window. The
plain reference follows the same three steps from the same seeded weights,
before the trainer's state exists, and ``correct`` compares each step's
loss, the norm of the first gradient as the optimizer got it (Adam's first
moment after step 1 is ``(1 - b1) * g``) and the norm of the parameters'
change after the three, the two norms by the worst leaf.
"""
from __future__ import annotations

import statistics
import time

import numpy as np

from benchmark import reduce_trace, reference, traffic

TRACED_STEPS = 12      # un-blocked steps under the profiler (one loss fetch)
BLOCKED_STEPS = 20     # steps timed one by one for train_step_ms


def resolve(obj, path):
    """``encoder.cells[3].ln1.gamma`` from ``obj``."""
    for part in path.split("."):
        name, _, index = part.partition("[")
        obj = getattr(obj, name)
        if index:
            obj = obj[int(index[:-1])]
    return obj


def program_params(net, model) -> dict:
    """reference name -> gluon Parameter, by the paths the configuration
    file gives (``layer{i}.`` entries once per layer)."""
    out = {}
    for ref_name, path in model["params"].items():
        if "{i}" in ref_name:
            for i in range(model["num_layers"]):
                out[ref_name.format(i=i)] = resolve(net, path.format(i=i))
        else:
            out[ref_name] = resolve(net, path)
    return out


def worst_leaf_gap(got: dict, want: dict, log=None) -> float:
    """max over leaves of |got - want| / max(want, median of want): a leaf
    whose norm is all but zero is held to the median leaf's scale."""
    floor = statistics.median(want.values())
    gap = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want}
    worst = max(gap, key=lambda k: (not np.isfinite(gap[k]), gap[k]))
    if log:
        log(f"worst leaf {worst}: program {got[worst]:.6g} reference "
            f"{want[worst]:.6g} (median leaf {floor:.6g})")
    return gap[worst]


def gaps(got: dict, ref: dict, log=None) -> dict:
    """The three numbers compared, of ``got`` against the reference."""
    return {
        "loss_gap": max(abs(a - b) for a, b in
                        zip(got["losses"], ref["losses"])),
        "grad_norm_gap": worst_leaf_gap(got["grad_norms"], ref["grad_norms"],
                                        log),
        "delta_norm_gap": worst_leaf_gap(got["delta_norms"],
                                         ref["delta_norms"], log),
    }


def compare(program: dict, ref: dict, limits: dict, log) -> bool:
    """Each number beside its limit; True when all hold."""
    numbers = gaps(program, ref, log)
    ok = True
    for name, value in numbers.items():
        holds = bool(np.isfinite(value)) and value <= limits[name]
        ok = ok and holds
        log(f"correct: {name} {value:.6g} limit {limits[name]:.6g} "
            f"{'ok' if holds else 'FAIL'}")
    log("correct: losses program " + " ".join(f"{x:.4f}" for x in
                                              program["losses"])
        + " | reference " + " ".join(f"{x:.4f}" for x in ref["losses"]))
    return ok


def build_trainer(run, weights):
    """The gluon net with the seeded weights set (no ``initialize()``: every
    shape is known), under ShardedTrainer on the cell's mesh."""
    import mxnet_tpu as mx
    from mxnet_tpu import models, nd
    from mxnet_tpu import parallel as par

    model, opt = run.model, run.workload["train"]
    args = {k: model[k] for k in model["constructor_args"]}
    net = getattr(models, model["constructor"])(**args)
    params = program_params(net, model)
    for name, value in reference.per_leaf(weights).items():
        params[name].set_data(nd.NDArray(value))
    n_chips = run.workload["chips"]
    trainer = par.ShardedTrainer(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
        par.make_mesh(run.workload["mesh"], devices=run.devices[:n_chips]),
        rules=models.bert_sharding_rules(), optimizer=opt["optimizer"],
        optimizer_params={"learning_rate": opt["learning_rate"]},
        compute_dtype=opt["compute_dtype"])
    return trainer, {ref: p.name for ref, p in params.items()}


def first_steps(run, trainer, names, batches) -> dict:
    """The trainer's first three steps through the window's own call and
    feed, and what ``correct`` reads of them: each loss, the per-leaf norm
    of the first gradient (Adam's first moment after one step is
    ``(1 - b1) * g``) and of the parameters' change after the three."""
    import jax
    import jax.numpy as jnp

    norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))))
    gap = jax.jit(lambda x, w0: jnp.sqrt(jnp.sum(jnp.square(x - w0))))
    program = {"losses": []}
    for i in range(3):
        program["losses"].append(float(step_on(trainer, batches[i]).asnumpy()))
        run.log(f"step {i + 1} done")
        if i == 0:
            first_moment = jax.device_get(
                {ref_name: norm(trainer.opt_state[name][0])
                 for ref_name, name in names.items()})
            program["grad_norms"] = {
                k: float(x) / (1.0 - reference.ADAM_B1)
                for k, x in first_moment.items()}
    seeded = reference.per_leaf(reference.make_weights(run.model, run.seed))
    program["delta_norms"] = {
        k: float(x) for k, x in jax.device_get(
            {ref_name: gap(trainer.param_vals[name], seeded[ref_name])
             for ref_name, name in names.items()}).items()}
    run.log("first-gradient and parameter-change norms read")
    return program


def step_on(trainer, batch):
    """The window's one call: a host batch in, the device loss out."""
    return trainer.step(*(batch[k] for k in ("tokens", "types", "labels")
                          if k in batch))


def drive(trainer, batches, first, *, steps=None, seconds=None,
          loss_every=10):
    """Steps ``first, first+1, ...`` over the cycled host batches, the loss
    fetched every ``loss_every`` steps as a job's logging would, until
    ``steps`` steps are done or ``seconds`` have passed; the last step is
    waited for. Returns (steps done, fetched losses, seconds)."""
    t0 = time.monotonic()
    losses, n = [], 0
    while (n < steps if steps is not None
           else time.monotonic() - t0 < seconds):
        with reduce_trace.mark("train.step"):
            loss = step_on(trainer, batches[(first + n) % len(batches)])
        n += 1
        if n % loss_every == 0:
            with reduce_trace.mark("train.loss_fetch"):
                losses.append(float(loss.asnumpy()))
    with reduce_trace.mark("train.wait_last"):
        trainer.block_until_ready()
    return n, losses, time.monotonic() - t0


def run(run):
    import jax

    model, tr, opt = run.model, run.traffic, run.workload["train"]
    batches = traffic.train_batches(tr, model, run.seed)
    weights = reference.make_weights(model, run.seed)
    jax.block_until_ready(weights)
    run.log(f"weights made on the device: {len(weights)} leaves, "
            f"{sum(w.size for w in weights.values()) / 1e6:.1f}M parameters")

    t = time.monotonic()
    ref = reference.train_steps(model, weights, batches[:3],
                                opt["learning_rate"], log=run.log)
    run.reference_s += time.monotonic() - t
    run.log(f"reference followed 3 steps in {run.reference_s:.2f}s")

    trainer, names = build_trainer(run, weights)
    del weights
    run.log("trainer built (seeded weights set, state placed)")

    program = first_steps(run, trainer, names, batches)
    correct = compare(program, ref, run.workload["limits"], run.log)

    tokens_per_step = tr["batch"] * tr["seq"]
    obs = {"tokens_per_step": tokens_per_step, "seq": tr["seq"],
           "batch": tr["batch"], "traced_steps": TRACED_STEPS,
           "model": model, "chips": run.workload["chips"],
           "device_kind": run.devices[0].device_kind}
    built = run.open_window()
    if run.trace:
        blocked = []
        for i in range(BLOCKED_STEPS):
            t = time.monotonic()
            step_on(trainer, batches[(3 + i) % len(batches)]) \
                ._data.block_until_ready()
            blocked.append((time.monotonic() - t) * 1e3)
        obs["blocked_step_ms"] = blocked
        with reduce_trace.profile(run.scratch) as prof:
            n, losses, secs = drive(trainer, batches, 3 + BLOCKED_STEPS,
                                    steps=TRACED_STEPS,
                                    loss_every=opt["loss_every"])
        obs["trace"] = reduce_trace.reduce(prof.path, obs["chips"],
                                           prof.seconds)
        n += BLOCKED_STEPS
    else:
        n, losses, secs = drive(trainer, batches, 3, seconds=run.seconds,
                                loss_every=opt["loss_every"])
    in_window = run.programs_built - built

    failed = sum(1 for x in losses if not np.isfinite(x))
    # Under Adam from seeded weights the loss first rises and only then
    # falls: after some 30 steps it reads anywhere from 1.7 below the first
    # loss to above it, by the seed. So "the last fetched loss is lower than
    # the first" is judged only in a window of ``falling_after_steps`` steps
    # or more, which a traced run (a few tens of steps) never is.
    judged = n >= opt["falling_after_steps"]
    falling = bool(losses) and losses[-1] < program["losses"][0]
    run.log(f"window: {n} steps, {n * tokens_per_step} tokens in {secs:.3f}s;"
            f" fetched losses " + " ".join(f"{x:.4f}" for x in losses[:1]
                                           + losses[-1:])
            + f" ({failed} not finite; falling: {falling}"
            + ("" if judged else ", not judged in a window under "
               f"{opt['falling_after_steps']} steps") + ")")
    return {"correct": correct and failed == 0 and (falling or not judged),
            "attempted": n, "failed": failed,
            "metrics": {"train_tokens_per_s": n * tokens_per_step / secs},
            "programs_in_window": in_window, "observations": obs}


def control(run):
    """The readings a limit is set from: the program with its own lower
    precision switched on (``compute_dtype`` one step below the cell's), and
    the reference computed in fp8 and put in the program's place, each
    compared with the reference proper."""
    model, opt = run.model, run.workload["train"]
    batches = traffic.train_batches(run.traffic, model, run.seed)
    weights = reference.make_weights(model, run.seed)
    ref = reference.train_steps(model, weights, batches[:3],
                                opt["learning_rate"])
    low = reference.train_steps(model, weights, batches[:3],
                                opt["learning_rate"], "fp8")
    numbers = {"control_reference_fp8": gaps(low, ref)}
    opt["compute_dtype"] = run.workload["control"]["compute_dtype"]
    trainer, names = build_trainer(run, weights)
    del weights
    numbers["control_program_" + opt["compute_dtype"]] = gaps(
        first_steps(run, trainer, names, batches), ref)
    return numbers
