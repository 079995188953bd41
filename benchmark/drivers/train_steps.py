"""Driver ``train_steps``: the fused training step back to back for the window.

Entry: ``Symbol.simple_bind(type_dict=..., compute_dtype=...)`` and
``Executor.make_train_step`` with the package's own SGD rule
(``Optimizer.pure_rule``), the path ``bench.py`` and ``tools/profile_step.py``
train through. It stands in for ``Module.fit_step``, which allocates every
input as float32 and so, under ``compute_dtype="bfloat16"``, rounds token ids
and class labels above 256 (PERF.md, Open questions, first entry); through
``type_dict`` the ids and labels stay int32 and reach the step exact.

Set-up builds one executor and one step from the seed (parameters and
inputs made on the device by the family file), drives them through the
first steps, and hands the same objects to the window. The window feeds
device-resident batches in turn, keeps ``AHEAD`` steps queued behind the one
that runs (it reads each step's loss that many steps late, as a training loop
that logs its loss now and then does), and ends on ``block_until_ready`` of
the last step. The host of a one-chip machine was seen to wake 50-110 ms
late once or twice a run; three queued steps hide a stall of 0.6 s.

``correct`` compares those first steps with the family's plain reference,
run after the window on the freed chip: each step's loss, the norm per leaf
of the first gradient as the optimizer got it (its momentum after one step
over the learning rate), the norm per leaf of the parameters' change, and
for the leaves kept whole the norm of the difference of each.

Traffic file: ``{"driver": "train_steps", "batch", "seq_len", "optimizer":
{"learning_rate", "momentum"} (SGD, no weight decay), "compute_dtype",
"feed_batches", "ref_steps", "trace_seconds", "programs": {"step": [...]},
"kernels": {...}, "limits": {...}}``.
"""
import collections
import gc
import statistics
import time

import numpy as np


AHEAD = 3  # steps dispatched beyond the one whose loss is read


def _leaf_gaps(prog, ref, skip=()):
    """|prog - ref| of each leaf's norm, against the leaf's own reference
    norm or the median leaf's, whichever is larger: (worst, its leaf,
    median over leaves). A NaN is the worst there is."""
    names = [n for n in ref if n not in skip]
    med = statistics.median(ref[n] for n in names)
    gaps = {}
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        gaps[n] = gap if gap == gap else float("inf")
    at = max(gaps, key=gaps.get)
    return gaps[at], at, statistics.median(gaps.values())


def _vector_diffs(prog, ref):
    """For the leaves kept whole on both sides (``refmath.kept``): the norm of
    the difference against the leaf's reference norm or the median leaf's.
    First order in rounding noise, where a gap between norms is second
    order: this is what separates bfloat16 from the fp8 control."""
    ref = {n: v for n, v in ref.items() if n in prog}
    norms = {n: float(np.linalg.norm(v)) for n, v in ref.items()}
    med = statistics.median(norms.values())
    diffs = {}
    for n, v in ref.items():
        d = float(np.linalg.norm(np.asarray(prog[n], np.float64) - v))
        d = d / max(norms[n], med, 1e-30)
        diffs[n] = d if d == d else float("inf")
    at = max(diffs, key=diffs.get)
    return statistics.median(diffs.values()), diffs[at], at


def numbers(prog, ref):
    """Every number the comparison knows, and where the worst leaves are."""
    steps = len(ref["loss"])
    loss = [(abs(p - r) / abs(r) if p == p else float("inf"))
            for p, r in zip(prog["loss"][:steps], ref["loss"])]
    grad_worst, grad_at, grad_med = _leaf_gaps(prog["grad_norm"],
                                               ref["grad_norm"])
    # leaves whose reference gradient is nought to rounding move by
    # round-off alone: out of the change, by a rule on the gradient
    med = statistics.median(ref["grad_norm"].values())
    still = [n for n, g in ref["grad_norm"].items() if g < 1e-3 * med]
    ch_worst, ch_at, ch_med = _leaf_gaps(prog["change_norm"],
                                         ref["change_norm"], skip=still)
    gv_med, gv_worst, gv_at = _vector_diffs(prog["grad_vec"],
                                            ref["grad_vec"])
    cv_med, cv_worst, cv_at = _vector_diffs(
        {n: v for n, v in prog["change_vec"].items() if n not in still},
        {n: v for n, v in ref["change_vec"].items() if n not in still})
    out = {"loss_gap": max(loss), "loss_gap_first": loss[0],
           "grad_norm_gap": grad_worst, "grad_norm_gap_median": grad_med,
           "change_norm_gap": ch_worst, "change_norm_gap_median": ch_med,
           "grad_vector_diff": gv_med, "grad_vector_diff_worst": gv_worst,
           "change_vector_diff": cv_med, "change_vector_diff_worst": cv_worst}
    where = {"grad_norm_gap": grad_at, "change_norm_gap": ch_at,
             "grad_vector_diff_worst": gv_at,
             "change_vector_diff_worst": cv_at,
             "leaves_left_out_of_change": still}
    return out, where


def compare(prog, ref, limits):
    """The numbers the traffic file gives a limit for are compared; all of
    them go into the run's facts."""
    nums, where = numbers(prog, ref)
    checks = [(name, nums[name], limit) for name, limit in limits.items()]
    return checks, {"numbers": nums, "worst_leaves": where}


class Trainer:
    """The executor, its fused step and the state the step threads."""

    def __init__(self, ctx):
        import jax.numpy as jnp
        import mxnet_tpu as mx

        fam, cfg, tr = ctx.family, ctx.cfg, ctx.traffic
        dev = ctx.devices[0]
        dev_ctx = mx.Context(dev.platform, dev.id)
        sym = fam.symbol(cfg, True)
        data_descs, label_descs = fam.input_descs(cfg, tr)
        inputs = {n: s for n, s, _ in data_descs + label_descs}
        types = {n: t for n, _, t in data_descs + label_descs}
        grad_req = {n: ("null" if n in inputs else "write")
                    for n in sym.list_arguments()}
        self.exe = sym.simple_bind(dev_ctx, grad_req=grad_req,
                                   type_dict=types,
                                   compute_dtype=tr["compute_dtype"],
                                   **inputs)
        self.params = fam.init_params(cfg, ctx.seed)
        self.names = list(self.params)
        o = tr["optimizer"]
        opt = mx.optimizer.create(
            "sgd", learning_rate=o["learning_rate"], momentum=o["momentum"],
            wd=0.0, rescale_grad=1.0,
            param_idx2name=dict(enumerate(self.names)))
        rule = opt.pure_rule()
        lw = np.array([opt.effective_lr_wd(i)
                       for i in range(len(self.names))], np.float32)
        self.lr, self.wd = jnp.asarray(lw[:, 0]), jnp.asarray(lw[:, 1])
        names = self.names

        def update(params, grads, states, lr, wd):
            new_p, new_s = {}, {}
            for i, n in enumerate(names):
                new_p[n], new_s[n] = rule(params[n], grads[n], states[n],
                                          lr[i], wd[i])
            return new_p, new_s

        self.step_fn = self.exe.make_train_step(update)
        self.states = {n: jnp.zeros_like(a) for n, a in self.params.items()}

    def step(self, feed):
        """One fused step on ``feed``; returns the step's outputs (not yet
        waited for)."""
        outs, self.params, self.states = self.step_fn(
            self.params, self.states, feed, self.lr, self.wd)
        return outs


def first_steps(ctx, trainer, batches, steps):
    """Drive the window's own call through the first ``steps`` steps and read
    what is compared: losses, |m1|/lr per leaf, |p_steps - p_0| per leaf."""
    from lib import refmath

    fam, cfg = ctx.family, ctx.cfg
    lr = ctx.traffic["optimizer"]["learning_rate"]
    prog = {"loss": []}
    for i in range(steps):
        data, labels = batches[i]
        outs = trainer.step({**data, **labels})
        prog["loss"].append(fam.loss_from_outputs(outs, labels))
        if i == 0:
            prog["grad_norm"] = {n: float(refmath.norm(s)) / lr
                                 for n, s in trainer.states.items()}
            prog["grad_vec"] = refmath.kept_vectors(trainer.states, 1.0 / lr)
    prog["change_norm"], prog["change_vec"] = {}, {}
    for n, a in trainer.params.items():
        start = fam.init_leaf(cfg, ctx.seed, n)
        prog["change_norm"][n] = float(refmath.diff_norm(a, start))
        if refmath.kept(a):
            prog["change_vec"][n] = np.asarray(a - start, np.float64)
    return prog


def _step_facts(seen):
    """How evenly the steps ended, as the host saw them: a run that reads
    low says here whether every step was slow or a few were long."""
    gaps = sorted(1e3 * (b - a) for a, b in zip(seen, seen[1:]))
    if len(gaps) < 4:
        return {}
    med = statistics.median(gaps)
    return {"step_end_gap_ms": {
        "median": med, "min": gaps[0], "max": gaps[-1],
        "over_1.05_median": sum(g > 1.05 * med for g in gaps),
        "lost_ms": sum(g - med for g in gaps if g > 1.05 * med)}}


def run(ctx):
    import jax
    from lib import tracing

    fam, cfg, tr = ctx.family, ctx.cfg, ctx.traffic
    trainer = Trainer(ctx)
    n_feed = max(tr["feed_batches"], tr["ref_steps"])
    batches = fam.make_batches(cfg, tr, ctx.seed, n_feed)
    feeds = [{**d, **l} for d, l in batches]
    prog = first_steps(ctx, trainer, batches, tr["ref_steps"])
    # one more step, read late, so the window's own loop has run
    jax.block_until_ready(trainer.step(feeds[0]))
    setup_s = ctx.elapsed()

    tracer = tracing.WindowTrace(ctx.root) if ctx.trace else None
    trace_at = 0.25 * ctx.seconds
    trace_len = min(tr["trace_seconds"], 0.5 * ctx.seconds)
    n, tracing_on = 0, False
    queued = collections.deque()  # outputs of the steps not yet waited for
    seen = []  # when the host saw each step end: for the facts, not the MFU
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= ctx.seconds:
            break
        if tracer and tracer.t0 is None and now >= trace_at:
            tracer.start()
            tracing_on = True
        with jax.profiler.TraceAnnotation("bench.train_step"):
            queued.append(trainer.step(feeds[n % n_feed])[0])
        n += 1
        if len(queued) > AHEAD:
            with jax.profiler.TraceAnnotation("bench.read_loss"):
                jax.block_until_ready(queued.popleft())
            seen.append(time.perf_counter())
        if tracing_on and time.perf_counter() - tracer.t0 >= trace_len:
            jax.block_until_ready(queued[-1])
            tracer.stop()
            tracing_on = False
    last = queued[-1]
    jax.block_until_ready(last)
    window_s = time.perf_counter() - t0
    if tracing_on:
        tracer.stop()
    finite = bool(np.isfinite(np.asarray(last, np.float32)).all())
    peak = ctx.memory_peak(ctx.devices)

    flops = fam.step_flops(cfg, tr)
    mfu = 100.0 * flops * n / window_s / (
        len(ctx.devices) * ctx.peaks["bf16_flops_per_s"])
    run_ = {
        "end_to_end": {"train_mfu_pct": mfu, "setup_s": setup_s},
        "attempted": n, "failed": 0 if finite else n,
        "memory_peak_bytes": peak, "step_flops": flops, "steps": n,
        "window_s": window_s, "peaks": ctx.peaks, "cfg": cfg, "traffic": tr,
        "facts": {"steps": n, "window_s": window_s,
                  "step_ms": 1e3 * window_s / n, **_step_facts(seen)},
    }
    run_["trace"] = tracer.reduce(tr["programs"], align="step") \
        if tracer else None

    # the reference, once the window has closed and the peak is read
    del trainer, batches, feeds, last, queued
    gc.collect()
    ref = fam.ref_train(cfg, tr, ctx.seed, tr["ref_steps"])
    run_["checks"], where = compare(prog, ref, tr["limits"])
    run_["facts"].update(where)
    run_["facts"]["loss"] = {"program": prog["loss"], "reference": ref["loss"]}
    return run_
