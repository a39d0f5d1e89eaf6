"""A training cell: the stock ``run()`` loop of the program, fed by the
benchmark's batches, with the program's default hooks.

One ``run()`` call holds the whole cell.  Its first ``setup_steps`` steps
are set-up (the first compiles); after the last of them the benchmark
reads the program's state for the check and the measured window opens.
The window closes at the first step end past ``--seconds``; the result
counts whole steps only, from one ``block_until_ready`` to the next.
After the window the program's state is freed and the reference runs the
set-up steps again from the same seed and batches.

A traffic mix with a ``mesh`` (``MeshSpec.shape``, e.g. ``[2, 2]``, data
× model) runs the cell on that many chips through the program's own mesh
path: ``run()`` hands the spec to ``fleet/elastic.run_elastic``, and the
weights are made straight into the parameter shardings that path uses.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import statistics
import time

import numpy as np

from bench import flops, gen, harness, weights as W
from bench.harness import log, span


class WindowClosed(Exception):
    """Raised from the hook to end ``run()`` at the window's end."""


def program_arch(cfg: dict):
    """The program's architecture for this configuration; its sizes must
    be the file's.  A file that lists ``num_hidden_layers`` under
    ``reduced`` (a depth cut: the layers left out stand for further
    pipeline stages) gives the published depth under ``published``; the
    registry's architecture, which must have that depth, is then built at
    the file's."""
    from repro.models.registry import get_arch
    arch = get_arch(cfg["registry_id"], smoke=bool(cfg.get("smoke")))
    if "num_hidden_layers" in cfg.get("reduced", ()):
        published = cfg["published"]["num_hidden_layers"]
        if arch.cfg.n_layers != published:
            raise SystemExit(
                f"bench: the program's {cfg['registry_id']} has "
                f"{arch.cfg.n_layers} layers; the configuration file "
                f"publishes {published}")
        arch = dataclasses.replace(arch, cfg=dataclasses.replace(
            arch.cfg, n_layers=cfg["num_hidden_layers"]))
    c, m = arch.cfg, W.dims(cfg)
    have = {"L": c.n_layers, "d": c.d_model, "H": c.n_heads,
            "K": c.n_kv_heads, "dh": c.head_dim, "f": c.d_ff, "V": c.vocab}
    qk_norm = bool(cfg.get("qk_norm"))
    if have != m or c.window != cfg.get("sliding_window") or \
            c.rope_theta != cfg["rope_theta"] or c.qk_norm != qk_norm:
        raise SystemExit(f"bench: the program's {cfg['registry_id']} is "
                         f"{have}, window {c.window}, rope_theta "
                         f"{c.rope_theta}, qk_norm {c.qk_norm}; the "
                         f"configuration file says {m}, "
                         f"{cfg.get('sliding_window')}, "
                         f"{cfg['rope_theta']}, {qk_norm}")
    return arch


def run_spec(cfg: dict, traffic: dict, seed: int):
    from repro.data.pipeline import DataConfig
    from repro.run import ModelSpec, OptSpec, RunSpec, StepSpec
    from repro.run.spec import FaultSpec, MeshSpec
    opt = traffic["optimizer"]
    mesh = (MeshSpec(kind="multi", shape=tuple(traffic["mesh"]))
            if "mesh" in traffic else MeshSpec())
    return RunSpec(
        model=ModelSpec(cfg["registry_id"], smoke=bool(cfg.get("smoke"))),
        data=DataConfig(vocab=W.dims(cfg)["V"], seq_len=traffic["seq_len"],
                        global_batch=traffic["batch"]),
        opt=OptSpec(name=opt["name"], lr=opt["lr"], schedule="constant",
                    hparams={k: opt[k] for k in
                             ("beta", "clip", "weight_decay")}),
        steps=StepSpec(total=10 ** 9),
        mesh=mesh,
        fault=FaultSpec(retries=0),
        seed=seed & 0x7FFFFFFF)


def param_shardings(spec, arch):
    """The shardings the program's mesh path gives the parameters, or
    None off a mesh."""
    if spec.mesh.shape is None:
        return None
    from repro.fleet.elastic import mesh_from_spec, program_shardings
    from repro.run import build_step_program
    return program_shardings(build_step_program(spec, arch),
                             mesh_from_spec(spec.mesh))[0]


# --------------------------------------------------------------------------
# What the check reads from the program
# --------------------------------------------------------------------------

def _tensors(tree: dict, paths) -> dict:
    out = {}
    for p in paths:
        node = tree
        for k in p.split("."):
            node = node[k]
        out[p] = node
    return out


def first_grad_norms(cfg: dict, opt_state, beta: float) -> dict:
    """Each tensor's first gradient norm, worked out from the AdaLomo state
    after one step: r = (1-β)·rowsum(g²) (or v = (1-β)·g²), so
    ‖g‖² = Σr / (1-β).  Keys are (path, layer or None)."""
    import jax
    import jax.numpy as jnp
    one_minus = float(np.float32(1.0) - np.float32(beta))

    def norms(st):
        s = st.r if st.v is None else st.v
        return jnp.sqrt(jnp.sum(s, axis=-1) / one_minus)

    m = opt_state.moments
    outer = jax.device_get({p: norms(s) for p, s in _tensors(
        m["outer"], [p for p, _ in W.OUTER]).items()})
    paths = [p for p, _ in W.block_leaves(cfg)]
    blocks = jax.device_get({p: norms(s) for p, s in _tensors(
        m["stacks"]["blocks"], paths).items()})
    out = {(p, None): float(v) for p, v in outer.items()}
    for p, v in blocks.items():
        out.update({(p, l): float(x) for l, x in enumerate(v)})
    return out


def change_norms(cfg: dict, seed: int, params) -> dict:
    """‖θ - θ₀‖ of each tensor (each layer's slice apart), θ₀ made again
    from the seed one slice at a time."""
    import jax
    import jax.numpy as jnp

    def gap(a, b):
        d = a.astype(jnp.float32) - b.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(d * d))

    layer_gap = jax.jit(lambda stack, l, b: gap(stack[l], b))
    gap = jax.jit(gap)
    out = {}
    for p, a in _tensors(params["outer"], [p for p, _ in W.OUTER]).items():
        out[(p, None)] = gap(a, W.outer_leaf(cfg, seed, p))
    blocks = _tensors(params["stacks"]["blocks"],
                      [p for p, _ in W.block_leaves(cfg)])
    for p, stack in blocks.items():
        for l in range(stack.shape[0]):
            out[(p, l)] = layer_gap(stack, l, W.layer_leaf(cfg, seed, p, l))
    return {k: float(v) for k, v in jax.device_get(out).items()}


# --------------------------------------------------------------------------
# The comparison
# --------------------------------------------------------------------------

def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """max over tensors of |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖)."""
    keys = [k for k in ref if keep is None or k in keep]
    med = statistics.median(ref[k] for k in keys)
    worst, where = 0.0, ""
    for k in keys:
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not math.isfinite(g):
            return math.inf, f"{k[0]}[{k[1]}]"
        if g > worst:
            worst, where = g, f"{k[0]}" + ("" if k[1] is None
                                           else f"[{k[1]}]")
    return worst, where


def moved_leaves(ref_grad: dict) -> set:
    """Tensors whose reference gradient is not nought to rounding: at
    least a thousandth of the median tensor's."""
    med = statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v >= 1e-3 * med}


def readings(prog: dict, ref: dict) -> dict:
    """The three numbers the check compares."""
    keep = moved_leaves(ref["grad"])
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = math.inf
    grad, grad_at = worst_leaf_gap(prog["grad"], ref["grad"])
    change, change_at = worst_leaf_gap(prog["change"], ref["change"], keep)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad,
            "grad_norm_gap_at": grad_at, "change_norm_gap": change,
            "change_norm_gap_at": change_at,
            "leaves_compared": len(keep), "leaves": len(ref["grad"])}


def checks_from(read: dict, limits: dict) -> tuple[bool, dict]:
    out, ok = {}, True
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap"):
        v, lim = read[name], limits[name]
        out[name] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return ok, out


# --------------------------------------------------------------------------
# The cell
# --------------------------------------------------------------------------

class BenchHook:
    """Last in the hook pipeline: reads the check's numbers during set-up,
    opens the window, and closes it."""

    def __init__(self, cell):
        self.c = cell

    # the Hook protocol of repro.run.hooks
    def on_run_start(self, ctx):
        # the step run() drives: built by run(), or by its mesh path
        self.c.wrap_step(ctx.program)

    def on_recover(self, ctx, step):
        pass

    def on_eval(self, ctx, step, metrics):
        pass

    def on_exit(self, ctx):
        self.c.sync_span.close()

    def on_step_end(self, ctx, ev):
        c = self.c
        c.sync_span.close()
        with span("bench.train.hooks_end"):
            c.step_end(ctx, ev)


class TrainCell:
    def __init__(self, cfg, traffic, seed, seconds, trace_dir, devices,
                 counter, fault=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.seconds = float(seconds)
        if trace_dir:
            self.seconds = min(self.seconds, float(traffic["trace_seconds"]))
        self.trace_dir = trace_dir
        self.devices, self.counter = devices, counter
        self.fault = fault
        self.setup_steps = int(traffic["setup_steps"])
        self.sync_span = harness.OpenSpan()
        self.gen = gen.TrainTraffic(traffic, W.dims(cfg)["V"], seed)
        self.prog = {"losses": []}
        self.tokens, self.step_flops, self.ends = {}, {}, {}
        self.window = None
        self.setup_record = {}
        self.ctx = None
        self.step_lowered = None
        self.programs = {}

    def batches(self):
        step = 0
        while True:
            with span("bench.train.batch"):
                b = self.gen.batch(step)
                self.tokens[step] = gen.real_tokens(b)
                self.step_flops[step] = flops.train_flops(self.cfg, b)
            yield b
            step += 1

    def wrap_step(self, program):
        jitted = program.step
        cell = self

        class Step:
            def __call__(self, *a):
                if cell.step_lowered is None:
                    # the same lowering the call below reuses
                    cell.step_lowered = jitted.lower(*a)
                with span("bench.train.dispatch"):
                    out = jitted(*a)
                cell.sync_span.open("bench.train.sync_hooks")
                return out

            def __getattr__(self, name):
                return getattr(jitted, name)

        program.step = Step()
        if self.fault is not None:
            self.fault(program)

    def step_end(self, ctx, ev):
        import jax
        self.ctx = ctx
        s = ev.step
        now = time.perf_counter()
        if s < self.setup_steps:
            self.prog["losses"].append(float(ev.loss))
            if s == 0:
                self.prog["grad"] = first_grad_norms(
                    self.cfg, ctx.opt_state,
                    self.traffic["optimizer"]["beta"])
            if s == self.setup_steps - 1:
                self.prog["change"] = change_norms(self.cfg, self.seed,
                                                   ctx.params)
                jax.block_until_ready((ctx.params, ctx.opt_state))
                self.open_window()
            return
        self.ends[s] = now
        if now - self.window[0] >= self.seconds:
            jax.block_until_ready((ctx.params, ctx.opt_state))
            self.ends[s] = time.perf_counter()
            self.close_window(s)
            raise WindowClosed

    def open_window(self):
        import jax
        if self.step_lowered is not None:
            self.programs["step"] = self.step_lowered.compile()
        mem = harness.memory(self.devices)
        self.resident = mem["bytes_in_use"]
        self.setup_record = {
            "bytes_in_use_gib": mem["bytes_in_use"] / harness.GIB,
            "peak_bytes_in_use_gib": mem["peak_bytes_in_use"] / harness.GIB,
            "compiles_in_setup": self.counter.count}
        self.compiles0 = self.counter.count
        if self.trace_dir:
            jax.profiler.start_trace(self.trace_dir)
            self.window_span = harness.OpenSpan()
            self.window_span.open("bench.trace_window")
        self.window = [time.perf_counter(), None]
        self.first_window_step = self.setup_steps

    def close_window(self, last_step):
        import jax
        self.window[1] = self.ends[last_step]
        self.last_window_step = last_step
        self.compiles_in_window = self.counter.count - self.compiles0
        if self.trace_dir:
            self.window_span.close()
            jax.profiler.stop_trace()

    def run(self):
        from repro.run import run
        arch = program_arch(self.cfg)
        spec = run_spec(self.cfg, self.traffic, self.seed)
        with span("bench.setup.weights"):
            params = W.init_params(self.cfg, self.seed,
                                   param_shardings(spec, arch))
        try:
            with harness.stdout_to_stderr():
                run(spec, arch=arch, params=params,
                    batch_iter=self.batches(), hooks=(BenchHook(self),),
                    log_fn=log)
        except WindowClosed:
            pass
        del params

    def window_numbers(self) -> dict:
        steps = range(self.first_window_step, self.last_window_step + 1)
        t = self.window[1] - self.window[0]
        return {"steps": len(steps),
                "tokens": sum(self.tokens[s] for s in steps),
                "flops": sum(self.step_flops[s] for s in steps),
                "seconds": t}

    def free_program_state(self):
        self.ctx.params = None
        self.ctx.opt_state = None
        self.ctx = None
        gc.collect()

    def reference(self) -> dict:
        from bench.reference import TrainReference
        ref = TrainReference(self.cfg, self.traffic["optimizer"],
                             devices=self.devices)
        batches = [self.gen.batch(s) for s in range(self.setup_steps)]
        return ref.run(self.seed, batches)


def run_cell(cfg, traffic, seed, seconds, trace_dir, devices, counter,
             limits, fault=None) -> dict:
    """Drive the cell; returns what run.py prints."""
    cell = TrainCell(cfg, traffic, seed, seconds, trace_dir, devices,
                     counter, fault)
    cell.run()
    w = cell.window_numbers()
    mem = harness.memory(devices)
    foot = harness.footprint(mem, cell.resident, cell.programs)
    cell.programs.clear()
    cell.free_program_state()
    t0 = time.perf_counter()
    ref = cell.reference()
    read = readings(cell.prog, ref)
    read["losses"] = cell.prog["losses"]
    read["reference_s"] = time.perf_counter() - t0
    ok, checks = checks_from(read, limits)
    cell.setup_record["window_open"] = cell.window[0]
    record = {"kind": "train", "cfg": cfg, "traffic": traffic,
              "window": w, "steps_in_window": w["steps"],
              "setup_steps": cell.setup_steps}
    return {"correct": ok, "attempted": w["steps"], "failed": 0,
            "tokens_per_s": w["tokens"] / w["seconds"],
            "memory": mem, "footprint": foot, "checks": checks,
            "readings": read, "record": record,
            "setup_record": cell.setup_record,
            "compiles_in_window": cell.compiles_in_window, "ref": ref,
            "batches": [cell.gen.batch(s) for s in range(cell.setup_steps)]}
