"""Train cells: the program's jitted train step, run back to back.

Set-up builds one object, the compiled step with its state: weights from the
seed (``weights.py``), AdamW moments at zero, the step from
``launch.train.build_training`` compiled for the cell's batch.  It drives
that object through the traffic's first ``check_steps`` steps, through the
window's own call and feed, and keeps three readings of them:

* each step's loss;
* the first step's clipped gradient as AdamW got it, ``mu / (1 - b1)``, leaf
  by leaf (stacked layers slice by slice);
* the change of every leaf over those steps, against the weights regenerated
  from the seed.

The window then continues training the same object.  Once it has closed and
the program's state is freed, the plain reference (``reference.py``) runs the
same steps from the same weights, and ``correct`` compares the readings.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import reference
import tracereduce
import weights
from traffic import TokenFeed

from repro.configs import ARCHS, RunConfig, ShapeConfig
from repro.configs.base import SSMConfig
from repro.launch.train import build_training
from repro.models.lm import build_model
from repro.train.optimizer import OptConfig, adamw_init

# Leaves whose reference gradient is under this share of the median leaf's
# move under Adam by round-off alone; they are left out of the gradient and
# change comparisons (a rule on the reference, not on names).
TINY_LEAF = 1e-3


# ---------------------------------------------------------------- program
SIZE_KEYS = ("n_layers", "d_model", "padded_vocab", "tie_embeddings", "ssm")


def _sizes(mc) -> dict:
    """A ``ModelConfig``'s sizes under the configuration file's keys."""
    ms = mc.ssm
    return {"n_layers": mc.n_layers, "d_model": mc.d_model,
            "padded_vocab": mc.padded_vocab,
            "tie_embeddings": mc.tie_embeddings,
            "ssm": {"d_state": ms.d_state, "d_conv": ms.d_conv,
                    "expand": ms.expand, "head_dim": ms.head_dim,
                    "chunk": ms.chunk, "n_groups": ms.n_groups}}


def program_config(cfg: dict):
    """The registry's ``ModelConfig`` with the file's sizes.  Every size the
    file does not list under ``reduced`` has to be the registry's own."""
    base = ARCHS[cfg["registry"]]
    s = cfg["ssm"]
    fields = dict(n_layers=cfg["n_layers"], d_model=cfg["d_model"],
                  vocab_size=cfg["vocab_size"],
                  tie_embeddings=cfg["tie_embeddings"],
                  remat=cfg["run"]["remat"],
                  ssm=SSMConfig(d_state=s["d_state"], d_conv=s["d_conv"],
                                expand=s["expand"], head_dim=s["head_dim"],
                                chunk=s["chunk"], n_groups=s["n_groups"]))
    mc = dataclasses.replace(base, **fields)
    ours, registry = _sizes(mc), _sizes(base)
    for key in SIZE_KEYS:
        if ours[key] != cfg[key]:
            raise ValueError(f"{key}: the program builds {ours[key]}, the "
                             f"configuration file states {cfg[key]}")
        if key not in cfg["reduced"] and ours[key] != registry[key]:
            raise ValueError(f"{key}: the file states {cfg[key]}, the "
                             f"registry's {cfg['registry']} has "
                             f"{registry[key]}, and {key} is not in reduced")
    return mc


def _tree_shapes(tree) -> dict:
    return {jax.tree_util.keystr(p): tuple(x.shape) for p, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


class Program:
    """The system under test for one configuration and traffic: the
    program's train step, its weights and optimizer state."""

    def __init__(self, cfg: dict, traffic: dict, *,
                 compute_dtype: str | None = None, step_wrapper=None):
        run = cfg["run"]
        opt = cfg["optimizer"]
        self.cfg, self.traffic = cfg, traffic
        mc = program_config(cfg)
        self.model = build_model(mc, ssd_impl=run["ssd_impl"],
                                 attn_impl=run["attn_impl"])
        self.run = RunConfig(
            model=mc, shape=ShapeConfig(traffic["name"], traffic["seq"],
                                        traffic["batch"], "train"),
            param_dtype=run["param_dtype"],
            compute_dtype=compute_dtype or run["compute_dtype"],
            learning_rate=opt["lr"], weight_decay=opt["weight_decay"],
            grad_clip=opt["grad_clip"])
        jitted, _, _ = build_training(self.model, self.run)
        self.step = step_wrapper(jitted) if step_wrapper else jitted
        want = _tree_shapes(jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0))))
        ours = _tree_shapes(weights.shapes(cfg))
        if want != ours:
            raise ValueError(f"weight layout differs from the program's: "
                             f"{set(want.items()) ^ set(ours.items())}")
        self.make_weights = weights.make_fn(cfg)
        self.opt_cfg = OptConfig(name="adamw", b1=opt["b1"], b2=opt["b2"],
                                 eps=opt["eps"],
                                 weight_decay=opt["weight_decay"],
                                 grad_clip=opt["grad_clip"])
        self._zeros = jax.jit(lambda p: adamw_init(p, self.opt_cfg))
        self._change = jax.jit(lambda p, lo, hi: leaf_norms(
            jax.tree.map(jnp.subtract, p, self.make_weights(lo, hi))))
        self._grad = jax.jit(lambda mu: leaf_norms(
            jax.tree.map(lambda m: m / (1.0 - opt["b1"]), mu)))
        self.compiled = None

    def state(self, seed: int):
        params = self.make_weights(*weights.seed_args(seed))
        return params, self._zeros(params)

    def compile(self, params, opt, batch):
        if self.compiled is None:
            self.compiled = self.step.lower(params, opt, batch).compile()
        return self.compiled

    def check_steps(self, seed: int, feed):
        """Set-up's first steps: returns the state and the readings."""
        params, opt = self.state(seed)
        step = self.compile(params, opt, feed(0))
        losses, grad = [], None
        for i in range(self.traffic["check_steps"]):
            params, opt, m = step(params, opt, feed(i))
            losses.append(m["loss"])
            if i == 0:
                grad = self._grad(opt.mu)
        change = self._change(params, *weights.seed_args(seed))
        readings = {"losses": [float(x) for x in losses],
                    "grad": _host(grad), "change": _host(change)}
        return params, opt, readings


# -------------------------------------------------------------- readings
def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf; stacked layer leaves slice by slice."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        x = x.astype(jnp.float32)
        if name.startswith("['layers']"):
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x).reshape(x.shape[0], -1),
                                         axis=1))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    return out


def _host(norms: dict) -> dict:
    return {k: np.asarray(v, np.float64) for k, v in norms.items()}


def reference_readings(cfg: dict, traffic: dict, seed: int, feed,
                       mm=reference.highest_mm) -> dict:
    """The plain reference over the same steps from the same weights; with
    ``mm=reference.int8_mm``, the control in the program's place."""
    opt = cfg["optimizer"]
    make = weights.make_fn(cfg)

    def step(params, mu, nu, tokens, t):
        loss, grads = jax.value_and_grad(reference.loss)(params, tokens, cfg,
                                                         mm)
        params, mu, nu, g = reference.adamw(params, grads, mu, nu, t, opt)
        return params, mu, nu, loss, leaf_norms(g)

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    lo, hi = weights.seed_args(seed)
    params = make(lo, hi)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, grad = [], None
    for i in range(traffic["check_steps"]):
        params, mu, nu, loss, g = step(params, mu, nu, jnp.asarray(feed(i)),
                                       jnp.float32(i + 1))
        losses.append(loss)
        if i == 0:
            grad = g
    change = jax.jit(lambda p, lo, hi: leaf_norms(
        jax.tree.map(jnp.subtract, p, make(lo, hi))))(params, lo, hi)
    out = {"losses": [float(x) for x in losses], "grad": _host(grad),
           "change": _host(change)}
    del params, mu, nu
    return out


def worst_leaf(prog: dict, ref: dict, keep: dict) -> tuple:
    """(gap, leaf): the largest |prog norm - ref norm| over max(ref norm,
    median ref norm), among the leaves ``keep`` marks."""
    med = float(np.median(np.concatenate(list(ref.values()))))
    worst, where = 0.0, ""
    for k, r in ref.items():
        gap = np.where(keep[k], np.abs(prog[k] - r) / np.maximum(r, med), 0.0)
        i = int(np.argmax(gap))
        if gap[i] > worst:
            worst, where = float(gap[i]), f"{k}[{i}]"
    return worst, where


def gaps(prog: dict, ref: dict, leaves: bool = False) -> dict:
    """The numbers ``correct`` can compare; the cell's limits file names
    those it does (with ``leaves``, also the leaf that sets each of the two
    norm gaps)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    if not np.all(np.isfinite(lp)):
        loss_gap = math.inf
    med = float(np.median(np.concatenate(list(ref["grad"].values()))))
    keep = {k: r >= TINY_LEAF * med for k, r in ref["grad"].items()}
    grad = worst_leaf(prog["grad"], ref["grad"], keep)
    change = worst_leaf(prog["change"], ref["change"], keep)
    out = {"loss_gap": loss_gap, "grad_gap": grad[0], "change_gap": change[0]}
    if leaves:
        out.update(grad_leaf=grad[1], change_leaf=change[1])
    return out


# -------------------------------------------------------------------- run
def _free(*trees):
    for t in trees:
        for x in jax.tree.leaves(t):
            if isinstance(x, jax.Array):
                x.delete()
    gc.collect()


def run(cfg: dict, traffic: dict, limits: dict, *, seed: int,
        seconds: float, trace: bool, t0: float, out_dir: Path,
        step_wrapper=None) -> dict:
    """One run of a train cell.  ``step_wrapper`` puts a fault in the
    program's place (tests)."""
    prog = Program(cfg, traffic, step_wrapper=step_wrapper)
    tokens = TokenFeed.from_traffic(traffic, cfg["vocab_size"], seed)

    def feed(i):
        return {"tokens": jnp.asarray(tokens.batch(i))}

    rows = np.concatenate([tokens.batch(i)
                           for i in range(traffic["check_steps"])])
    if len({r.tobytes() for r in rows}) != len(rows):
        raise ValueError("the check steps' rows are not all distinct")
    params, opt, readings = prog.check_steps(seed, feed)
    step = prog.compiled
    B, S = traffic["batch"], traffic["seq"]
    i = traffic["check_steps"]
    losses = []
    out: dict = {"ctx": {"cfg": cfg, "traffic": traffic}}

    if not trace:
        setup_s = time.perf_counter() - t0
        t_w0 = time.perf_counter()
        prev = None
        while True:
            params, opt, m = step(params, opt, feed(i))
            losses.append(m["loss"])
            i += 1
            if prev is not None:
                prev.block_until_ready()
            prev = m["loss"]
            if time.perf_counter() - t_w0 >= seconds:
                break
        jax.block_until_ready((params, opt, m))
        elapsed = time.perf_counter() - t_w0
        n = len(losses)
        out["e2e"] = {"train_tokens_per_s": n * B * S / elapsed,
                      "setup_s": setup_s}
    else:
        n = traffic["trace_steps"]
        trace_dir = out_dir / "trace"
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # host spans and TraceMes only
        with jax.profiler.trace(str(trace_dir), profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(n):
                    with jax.profiler.TraceAnnotation("bench.feed"):
                        batch = feed(i)
                    with jax.profiler.StepTraceAnnotation("bench.step",
                                                          step_num=i):
                        params, opt, m = step(params, opt, batch)
                    losses.append(m["loss"])
                    i += 1
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready((params, opt, m))
        out["ctx"].update(trace_context(trace_dir, n))
        out["ctx"]["hlo_text"] = step.as_text()
        try:
            out["ctx"]["sim_t_est"] = simulated_step(step)
        except Exception:       # sim_est_err then reads nothing, and says so
            traceback.print_exc()

    losses = [float(x) for x in losses]
    out["attempted"] = traffic["check_steps"] + len(losses)
    out["failed"] = sum(not math.isfinite(x)
                        for x in readings["losses"] + losses)
    stats = jax.devices()[0].memory_stats() or {}
    out["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    _free(params, opt)
    del params, opt, step
    prog.compiled = None

    ref = reference_readings(cfg, traffic, seed, tokens.batch)
    got = gaps(readings, ref)
    for k in sorted(set(got) - set(limits)):
        print(f"reading {k} {got[k]!r} (not compared)", file=sys.stderr)
    out["checks"] = {k: {"value": got[k], "limit": limits[k]}
                     for k in limits}
    out["correct"] = out["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in out["checks"].values())
    return out


def trace_context(trace_dir: Path, steps: int) -> dict:
    """The traced window's device ops, its bounds on the trace's clock (the
    ``bench.window`` host span) and its summary."""
    devices, host = tracereduce.read_xplane(
        tracereduce.find_xplane(trace_dir))
    win = [h for h in host if h.name == "bench.window"]
    if not devices or not win:
        raise RuntimeError("the trace holds no device ops or no window")
    lo, hi = win[0].start, win[0].end
    inside = [sum(o.end > lo and o.start < hi for o in ops) for ops in devices]
    print(f"bench: trace: {len(devices)} device(s), {inside} ops in the "
          f"window of {hi - lo:.4f} s", file=sys.stderr, flush=True)
    if not any(inside):
        first = min(o.start for ops in devices for o in ops)
        last = max(o.end for ops in devices for o in ops)
        raise RuntimeError(f"no device op in the window [{lo}, {hi}]; the "
                           f"device ops span [{first}, {last}]")
    summary = tracereduce.summarize(devices, host, lo, hi)
    return {"devices": devices, "lo": lo, "hi": hi, "steps": steps,
            "summary": summary}


def simulated_step(compiled) -> float:
    """The simulator's estimate of the chip's own compiled step."""
    from repro.core.hwspec import TPU_V5E
    from repro.core.simulate import simulate

    return float(simulate(compiled, hw=TPU_V5E, n_chips=1,
                          compute_dtype="f32").engine.t_est)
