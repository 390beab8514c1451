"""Training cells: the program's jitted train step, driven as ``train_loop``
drives it.

Set-up makes the weights from the seed on the device (the reference's
``init``, one jitted call), builds the program's step exactly as
``repro.train.loop.train_loop`` builds it (``make_train_step`` under
``jax.jit`` with the state donated, called with the anomaly guard) and runs
its first three steps, which compile it. Those three steps are what the
reference follows: each step's loss, the first gradient as the optimizer got
it (AdamW's first moment after one step, over ``1 - b1``) and the change of
the parameters after the third step, each leaf's norm against the
reference's. The window then runs every step that starts before
``--seconds`` ends, and at least one, on the same object.

Spans: ``batch`` (inputs made and put on the device), ``dispatch`` (the
step's call), ``loss_fetch`` (the host waits for the loss, as the loop does
every step).
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

import bench
import generator as TR
from program import check_layout, program_config, seed_key

SETUP_STEPS = 3


def train_config(opt: dict, microbatches: int):
    from repro.optim.adamw import AdamWConfig, cosine_schedule
    from repro.train.step import TrainConfig
    return TrainConfig(
        optimizer=AdamWConfig(
            lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"],
            schedule=cosine_schedule(opt["lr"], opt["warmup"], opt["total"],
                                     opt["floor"])),
        microbatches=microbatches)


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


def norm_gaps(prog: list[float], ref: list[float]) -> np.ndarray:
    """Each leaf's |‖prog‖ − ‖ref‖| over max(‖ref‖, median leaf ‖ref‖)."""
    ref = np.asarray(ref, np.float64)
    return np.abs(np.asarray(prog, np.float64) - ref) / np.maximum(ref, np.median(ref))


def _faulty(step_fn, fault):
    """Breaks the timed path for the harness's own tests."""
    import jax
    import jax.numpy as jnp
    if fault == "state_unchanged":
        def step(state, batch, guard):
            keep = jax.tree_util.tree_map(jnp.copy, state)
            _, metrics = step_fn(state, batch, guard)
            return keep, metrics
        return step
    if fault == "half_batch":
        def step(state, batch, guard):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step_fn(state, half, guard)
        return step
    raise ValueError(f"unknown fault {fault!r}")


class Job:
    """The program's training job as the window drives it: weights from the
    seed, the state, the jitted step, and the first three steps."""

    def __init__(self, ctx: bench.Context):
        import jax
        import jax.numpy as jnp
        from repro.optim.adamw import adamw_init
        from repro.train.step import make_train_step

        cell = ctx.cell
        self.ctx = ctx
        self.wl = {**cell.workload, **ctx.overrides.get("workload", {})}
        self.conf = conf = {**cell.config, **ctx.overrides.get("config", {})}
        self.traffic = tr = {**cell.traffic, **ctx.overrides.get("traffic", {})}
        self.ref = ref = cell.reference
        cfg = program_config(cell, ctx.overrides.get("program", {}))
        tcfg = train_config(self.wl["optimizer"], self.wl["microbatches"])
        self.key = key = seed_key(ctx.seed)
        check_layout(cfg, jax.eval_shape(lambda k: ref.init(conf, k), key))

        def make_state(k):
            p = ref.init(conf, k)
            return {"params": p, "opt": adamw_init(p),
                    "rng": jax.random.fold_in(k, 0x5EED)}

        self.state = jax.jit(make_state)(key)
        self.step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
        if ctx.fault:
            self.step_fn = _faulty(self.step_fn, ctx.fault)
        self.guard = (jnp.float32(math.inf), jnp.float32(math.inf))
        self.data = TR.MarkovBatches(tr, conf["vocab_size"], ctx.seed)
        self.tokens_per_step = tr["batch"] * tr["seq_len"]
        self.losses, self.applied = [], []

    def step(self, i: int) -> None:
        import jax.numpy as jnp
        spans = self.ctx.spans
        with spans.span("batch"):
            batch = {k: jnp.asarray(v) for k, v in self.data.batch(i).items()}
        with spans.span("dispatch"):
            self.state, metrics = self.step_fn(self.state, batch, self.guard)
        with spans.span("loss_fetch"):
            self.losses.append(float(metrics["loss"]))
            self.applied.append(bool(metrics["applied"]))

    def first_steps(self) -> dict:
        """Steps 1-3 (compiling the step); the readings the reference
        follows: losses, the first gradient as AdamW got it (its first
        moment over 1 - b1) and each leaf's change after the third step."""
        import jax
        import jax.numpy as jnp
        p0 = jax.tree_util.tree_map(jnp.copy, self.state["params"])
        self.step(0)
        b1 = self.wl["optimizer"]["b1"]
        g1 = [float(x) / (1 - b1) for x in jax.jit(leaf_norms)(self.state["opt"]["m"])]
        for i in range(1, SETUP_STEPS):
            self.step(i)
        dp = [float(x) for x in jax.jit(lambda a, b: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, a, b)))(self.state["params"], p0)]
        return {"losses": self.losses[:SETUP_STEPS], "g1": g1, "dp": dp}

    def sound(self) -> bool:
        return all(self.applied) and all(math.isfinite(x) for x in self.losses)


def run(ctx: bench.Context) -> bench.Outcome:
    job = Job(ctx)
    prog = job.first_steps()
    compiles_setup = ctx.compiles.count

    # --- window --------------------------------------------------------------
    trace_dir = bench.start_trace(ctx)
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    i = SETUP_STEPS
    step_times = []
    with ctx.spans.span("window"):
        while True:
            ts = time.perf_counter()
            job.step(i)
            step_times.append(time.perf_counter() - ts)
            i += 1
            if time.perf_counter() - t0 >= ctx.seconds:
                break
    t1 = time.perf_counter()
    bench.stop_trace(ctx)
    steps = i - SETUP_STEPS
    compiles_window = ctx.compiles.count - compiles_setup
    mem_peak = bench.memory_peak_bytes(ctx.cell.entry["chips"])
    job.state = job.step_fn = None
    gc.collect()

    # --- the reference follows the first three steps -------------------------
    r = reference_steps(job.ref, job.conf, job.wl, job.data, job.key)
    bench.log(f"reference: {r['seconds']:.1f} s")
    checks = compare(job.wl, prog["losses"], prog["g1"], prog["dp"], r)
    if not job.sound():
        checks.append(bench.Check("steps_rejected", 1.0, 0.0))

    counters = {"steps": steps, "setup_steps": SETUP_STEPS,
                "tokens_per_step": job.tokens_per_step,
                "step_s": step_times, "losses": job.losses,
                "compiles_in_window": compiles_window}
    return bench.Outcome(
        end_to_end={"train_tokens_per_s": steps * job.tokens_per_step / (t1 - t0),
                    "setup_s": setup_s},
        checks=checks, attempted=steps, failed=0 if job.sound() else steps,
        counters=counters, window=(t0, t1), trace_dir=trace_dir,
        memory_peak_bytes=mem_peak,
        extra_lines=[f"steps {steps} in {t1 - t0:.3f} s, step_s median "
                     f"{float(np.median(step_times)):.3f}, first losses "
                     f"{prog['losses']}, compiles in window {compiles_window}"])


def reference_steps(ref, conf, wl, data, key, quant=None, fault=None) -> dict:
    """The reference's first three steps on the same batches and weights:
    losses, each leaf's first-gradient norm (after clipping) and each leaf's
    change after the third step. ``quant`` computes it in float8 (the
    control); ``fault`` plants one of the faults a step can have."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    opt = wl["optimizer"]
    blk = wl["reference_block"]
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: ref.init(conf, k))(key)
        p0 = jax.tree_util.tree_map(jnp.copy, params)

        @jax.jit
        def grads_of(p, toks, labels):
            def one(carry, xs):
                l, g = jax.value_and_grad(
                    lambda p_: ref.loss(conf, p_, xs[0], xs[1], quant))(p)
                return jax.tree_util.tree_map(jnp.add, carry, (l, g)), None
            n = toks.shape[0] // blk
            zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like, p))
            (l, g), _ = jax.lax.scan(one, zero, (toks.reshape(n, blk, -1),
                                                labels.reshape(n, blk, -1)))
            return l / n, jax.tree_util.tree_map(lambda x: x / n, g)

        def clipped_norms(g):
            gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
            s = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-12))
            return [n * s for n in leaf_norms(g)]

        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        v = jax.tree_util.tree_map(jnp.zeros_like, params)
        update = jax.jit(lambda step, g, m, v, p: ref.adamw(opt, step, g, m, v, p),
                         static_argnums=0)
        losses, g1 = [], None
        for i in range(SETUP_STEPS):
            b = data.batch(i)
            toks, labels = jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
            if fault == "half_batch":
                toks, labels = toks[: toks.shape[0] // 2], labels[: labels.shape[0] // 2]
            l, g = grads_of(params, toks, labels)
            losses.append(float(l))
            if i == 0:
                g1 = [float(x) for x in jax.jit(clipped_norms)(g)]
                if fault == "state_unchanged":  # AdamW's moment stays at zero
                    g1 = [0.0] * len(g1)
            if fault != "state_unchanged":
                params, m, v = update(i + 1, g, m, v, params)
        dp = [float(x) for x in jax.jit(lambda a, b: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, a, b)))(params, p0)]
    return {"losses": losses, "g1": g1, "dp": dp,
            "seconds": time.perf_counter() - t0}


def compare(wl, losses, g1, dp, r) -> list[bench.Check]:
    """The numbers compared with the reference: the worst of the three
    losses' gaps; the worst leaf's and the median leaf's gap of the first
    gradient's norm; the worst leaf's gap of the change after three steps.
    Leaves whose reference gradient is under ``leaf_floor_share`` of the
    median leaf's are left out: Adam moves those by round-off alone."""
    lim = wl["limits"]
    keep = np.asarray(r["g1"]) >= wl["leaf_floor_share"] * float(np.median(r["g1"]))
    grad = norm_gaps(g1, r["g1"])[keep]
    upd = norm_gaps(np.asarray(dp)[keep], np.asarray(r["dp"])[keep])
    loss_gap = max(abs(a - b) for a, b in zip(losses, r["losses"]))
    return [bench.Check("loss_gap", loss_gap, lim["loss_gap"]),
            bench.Check("grad_gap", float(grad.max()), lim["grad_gap"]),
            bench.Check("grad_gap_median", float(np.median(grad)), lim["grad_gap_median"]),
            bench.Check("update_gap", float(upd.max()), lim["update_gap"])]


def calibrate(ctx: bench.Context) -> dict:
    """Readings for setting the limits (``calibrate.py``): the program's,
    and with ``overrides["control"]`` the control's and the faults' (half
    of each batch; a state left unchanged), each planted in the reference
    put in the program's place, as the numbers ``compare`` gives."""
    job = Job(ctx)
    prog = job.first_steps()
    sound = job.sound()
    ref, conf, wl, data, key = job.ref, job.conf, job.wl, job.data, job.key
    del job
    gc.collect()
    r = reference_steps(ref, conf, wl, data, key)
    rec = {"sound": sound, "losses": prog["losses"], "ref_losses": r["losses"],
           "program": {c.name: c.value for c in compare(wl, prog["losses"], prog["g1"],
                                                        prog["dp"], r)},
           "g1": prog["g1"], "ref_g1": r["g1"], "dp": prog["dp"], "ref_dp": r["dp"],
           "ref_seconds": r["seconds"]}
    if ctx.overrides.get("control"):
        for name, kw in (("control", {"quant": "fp8"}), ("half_batch", {"fault": "half_batch"}),
                         ("state_unchanged", {"fault": "state_unchanged"})):
            x = reference_steps(ref, conf, wl, data, key, **kw)
            rec[name] = {c.name: c.value for c in compare(wl, x["losses"], x["g1"], x["dp"], r)}
            rec[name + "_leaves"] = {"losses": x["losses"], "g1": x["g1"], "dp": x["dp"]}
    return rec
