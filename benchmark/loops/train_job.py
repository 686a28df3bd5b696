"""kind "train_job": drive the program's train step as a user's loop does.

Set-up builds ONE object — the step `make_train_step` returns, with its
state — makes the weights from the seed in one jitted call, drives the step
through its first steps (the readings the reference follows) and hands the
same object to the window. The window puts a seeded batch on the device
step by step, fetches the loss one step behind, and blocks on the last step
before the clock stops.
"""
import gc
import time

import numpy as np


def make_batches(traffic, draw_vocab, seed):
    """The pool of [batch, seq] token/label batches, on the host. Rows all
    differ; ids are drawn below the published vocabulary."""
    rng = np.random.default_rng([int(seed), 0x7a11])
    shape = (traffic["pool_batches"], 2, traffic["batch"], traffic["seq"])
    pool = rng.integers(0, draw_vocab, shape, dtype=np.int32)
    return [(b[0], b[1]) for b in pool]


def program_grad_norms(state, shapes, layout, heads, b1):
    """Per-leaf norms of the first gradient as the optimizer got it, out of
    its state after one step: m = (1 - b1) * g. The state is flat; `shapes`
    gives each leaf its stacked shape back."""
    from benchmark.harness import reference_gpt
    grads = {k: s["m"].reshape(shapes[k]) / (1.0 - b1)
             for k, s in state.items()}
    return reference_gpt.leaf_norms(grads, layout, heads)


def program_delta_norms(state, p0, layout, heads):
    """Per-leaf norms of the change of the float32 master parameters from
    their start. `p0` comes in as arrays of the trained type, made by a call
    of their own: regenerated inside this program, XLA would keep them in
    float32 (excess precision) and the rounding of the start would be read
    as movement."""
    import jax.numpy as jnp

    from benchmark.harness import reference_gpt
    delta = {k: s["master"].reshape(p0[k].shape) - p0[k].astype(jnp.float32)
             for k, s in state.items()}
    return reference_gpt.leaf_norms(delta, layout, heads)


class Program:
    """The one object set-up builds and the window drives: the compiled
    step with its state, and the feed."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from paddle_tpu.parallel import (GPTSpmdConfig, MeshPlan, gpt_spmd,
                                         make_train_step)

        from benchmark.harness import weights

        config, traffic = ctx["config"], ctx["traffic"]
        opt = config["optimizer"]
        program = config["program"]
        cfg = GPTSpmdConfig(**program["gpt_spmd_config"])
        plan = MeshPlan(**program["mesh_plan"])
        step_fn, _, mesh = make_train_step(
            cfg, plan, learning_rate=opt["lr"],
            weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"])
        specs = gpt_spmd.param_specs(cfg)
        shard = {k: NamedSharding(mesh, s) for k, s in specs.items()}
        dtype = jnp.dtype(config["dtype"]["param"])
        key = weights.root_key(ctx["seed"])

        def make_state(key):
            params = weights.stacked(config, key, dtype)
            return params, {k: gpt_spmd.init_opt_state_leaf(p, plan)
                            for k, p in params.items()}

        if plan.n_devices != 1:
            # a sharded plan lays its optimizer state out per shard; the
            # four-chip cell (PERF.md, Open questions, row 1) brings that
            raise NotImplementedError(
                f"train_job drives a one-chip plan; got {plan.dims}")
        # weights and optimizer state in one jitted call from the seed,
        # placed as the step itself returns them
        flat = NamedSharding(mesh, P("sharding"))
        state_shard = {k: {"m": flat, "v": flat, "master": flat,
                           "t": NamedSharding(mesh, P())} for k in specs}
        self.params, self.state = jax.jit(
            make_state, out_shardings=(shard, state_shard))(key)
        self.batches = make_batches(traffic, config["draw_vocab"],
                                    ctx["seed"])
        shapes = {k: tuple(p.shape) for k, p in self.params.items()}
        layout, heads = config["qkv_layout"], config["n_head"]
        self.grad_norms = jax.jit(lambda st: program_grad_norms(
            st, shapes, layout, heads, opt["b1"]))
        self.delta_norms = jax.jit(lambda st, p0: program_delta_norms(
            st, p0, layout, heads))
        self.start = lambda: weights.make(config, ctx["seed"], dtype)
        self.step_fn = ctx["wrap_step"](step_fn) if ctx.get("wrap_step") \
            else step_fn
        self.n = 0

    def feed(self, i):
        import jax
        toks, labs = self.batches[i % len(self.batches)]
        return jax.device_put(toks), jax.device_put(labs)

    def step(self, toks, labs):
        loss, self.params, self.state = self.step_fn(
            self.params, self.state, toks, labs)
        self.n += 1
        return loss

    def first_steps(self, count):
        """Drive the first steps through the window's own call and feed and
        take the readings the reference follows."""
        import jax
        got = {"losses": []}
        for i in range(count):
            got["losses"].append(float(self.step(*self.feed(self.n))))
            if i == 0:
                got["grad_norms"] = jax.device_get(
                    self.grad_norms(self.state))
        got["delta_norms"] = jax.device_get(
            self.delta_norms(self.state, self.start()))
        return got

    def free(self):
        self.params = self.state = self.step_fn = None
        self.grad_norms = self.delta_norms = None
        gc.collect()


def reference_readings(ctx, batches, mode="float32"):
    """The reference (or, in a lower `mode`, the control) over the same
    first steps, from the seed's weights as trained: their bf16 values,
    held in float32."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import reference_gpt, weights

    config, traffic = ctx["config"], ctx["traffic"]
    first = traffic["first_steps"]

    def make_params():
        return jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32),
            weights.make(config, ctx["seed"], config["dtype"]["param"]))

    return reference_gpt.train_readings(
        make_params,
        [(jnp.asarray(t), jnp.asarray(l)) for t, l in batches[:first]],
        config["optimizer"], row_block=min(2, traffic["batch"]),
        steps=first, heads=config["n_head"], layout=config["qkv_layout"],
        eps=config["layer_norm_epsilon"], mode=mode)


def run(ctx):
    import jax.numpy as jnp

    from paddle_tpu.framework import compile_cache

    from benchmark.harness import correctness
    from benchmark.harness.lastline import memory_peak_bytes

    config, traffic = ctx["config"], ctx["traffic"]
    compile_cache.place()
    counter = ctx["compile_counter"]

    # ---- set-up: the object the window will drive, and its first steps
    prog = Program(ctx)
    ctx["mark"]("program_built")
    first = traffic["first_steps"]
    got = prog.first_steps(first)
    ctx["mark"]("first_steps_done")
    compiles_before = counter.requests
    setup_s = time.perf_counter() - ctx["t0"]

    # ---- the measured window
    tokens_per_step = traffic["batch"] * traffic["seq"]
    spans = {"put_batch": 0.0, "step_call": 0.0, "fetch_loss": 0.0}
    prev = None
    t0 = time.perf_counter()
    while True:
        a = time.perf_counter()
        toks, labs = prog.feed(prog.n)
        b = time.perf_counter()
        loss = prog.step(toks, labs)
        c = time.perf_counter()
        if prev is not None:
            float(prev)                       # the loss, one step behind
        d = time.perf_counter()
        spans["put_batch"] += b - a
        spans["step_call"] += c - b
        spans["fetch_loss"] += d - c
        prev = loss
        if d - t0 >= ctx["seconds"]:
            break
    last_loss = float(prev)                    # blocks on the last step
    window_s = time.perf_counter() - t0
    steps = prog.n - first
    compiles_in_window = counter.requests - compiles_before

    record = {
        "end_to_end": {"setup_s": setup_s,
                       "train_tokens_per_s": steps * tokens_per_step
                       / window_s},
        "attempted": steps, "failed": 0 if np.isfinite(last_loss) else steps,
        "window_s": window_s, "steps": steps, "spans": spans,
        "shapes": {"batch": traffic["batch"], "seq": traffic["seq"],
                   "heads": config["n_head"],
                   "head_dim": config["n_embd"] // config["n_head"],
                   "layers": config["n_layer"],
                   "itemsize": jnp.dtype(config["dtype"]["compute"]).itemsize},
        "counters": {"compiles_in_window": compiles_in_window,
                     "last_loss": last_loss},
    }

    # ---- the traced window: a few more steps of the same loop
    if ctx["trace"]:
        def traced():
            loss = None
            for _ in range(ctx["trace_steps"]):
                with ctx["span"]("put_batch"):
                    toks, labs = prog.feed(prog.n)
                with ctx["span"]("step"):
                    loss = prog.step(toks, labs)
            float(loss)
        record["trace"] = ctx["capture"](traced)
        record["trace_steps"] = ctx["trace_steps"]

    record["memory_peak_bytes"] = memory_peak_bytes()

    # ---- the comparison, once the program's state is freed
    batches = prog.batches
    prog.free()
    del prog, loss, prev
    t_ref = time.perf_counter()
    want = reference_readings(ctx, batches,
                              ctx.get("reference_mode", "float32"))
    readings = correctness.train_readings_gap(got, want)
    readings["compiles_in_window"] = compiles_in_window
    record["readings"] = readings
    record["reference_s"] = time.perf_counter() - t_ref
    record["checks"] = correctness.checks_from(
        readings, correctness.load_limits(ctx["cell"]["name"], ctx["tiny"]))
    return record
