"""Calibration & accuracy evaluation — the paper's §5 methodology.

The paper tunes gem5 with Fujitsu's parameters, then validates the simulator
against the A64FX *test chip* on 28 kernels.  Our test chip is the CPU host
(the only silicon in this container): we

  1. FIT the ``CPU_HOST`` HardwareSpec from a handful of microbenchmarks
     (add -> vector throughput, exp -> transcendental factor, triad ->
     memory bandwidth, empty-jit -> op startup), then
  2. EVALUATE the simulator on all 28 Table-1 kernels: measured wall time vs
     simulated estimate of the same compiled HLO, reporting the % difference
     exactly like Fig. 3 (mean / stddev / mean|.| / fraction within 10%).

Adaptation note (recorded): the paper scales the outer iteration count by
1/1000 because the simulator is slow; we scale the array size by 1024x
because the host's per-call dispatch would otherwise dominate the
measurement of L1-resident arrays.  Same trick, same reason.
"""
from __future__ import annotations

import contextlib
import dataclasses
import statistics
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..configs.a64fx_kernelsuite import KERNELS, Kernel
from ..kernels import ref as kref
from ..kernels.stream import EXPRS, _DTYPES
from .hlo import Program
from .hwspec import CPU_HOST, HardwareSpec
from .simulate import simulate

SIZE_SCALE = 1024     # paper: iter/1000; here: n x1024 (see module docstring)


@contextlib.contextmanager
def host_x64():
    """x64 on the host CPU device: the test chip here is the CPU, so its
    arrays and jits are placed there explicitly, never on whatever
    accelerator is JAX's default device."""
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        yield


def _median_time(fn: Callable, args, repeats: int = 15) -> float:
    out = fn(*args)
    jax.block_until_ready(out)          # compile + warm
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def _kernel_inputs(k: Kernel, n: int, key=None):
    fn, n_in, din, dout = EXPRS[k.name]
    key = key or jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    if din == "i4":
        x1 = jax.random.randint(k1, (n,), -1000, 1000, jnp.int32)
    else:
        x1 = (jnp.abs(jax.random.normal(k1, (n,), _DTYPES[din])) + 0.5)
    x2 = (jnp.abs(jax.random.normal(k2, (n,), _DTYPES["f8" if din == "i4"
                                                      else din])) + 0.5)
    if din != "i4":
        x2 = x2.astype(_DTYPES[din])
    y0 = jnp.zeros((n,), _DTYPES[dout])
    return x1, x2, y0


def _jit_kernel(name: str):
    @jax.jit
    def f(x1, x2, y0):
        return kref.elementwise_ref(name, x1, x2, y0)
    return f


def measure_dispatch_overhead() -> float:
    f = jax.jit(lambda x: x)
    x = jnp.zeros((8,), jnp.float32)
    return _median_time(f, (x,), repeats=50)


# kernels used to fit per-opcode factors and the HLO opcodes they exercise
# (the paper's per-OpClass latency table, fitted instead of NDA-supplied).
# Only *transcendental-class* opcodes are fitted; the arithmetic /
# conversion / numeric kernels are predicted purely by the bandwidth +
# vector-throughput model, so they genuinely test it (paper §5.1).
_FACTOR_FIT = {
    "exp": "exponential", "log": "log", "sin": "sine", "cos": "cosine",
    "atan": "atan2", "sqrt": "sqrt", "div": "divide", "pwr": "power",
}


def _poly16(x):
    """Horner chain, 16 fma = 32 f64 flops per element — ALU-bound."""
    y = x
    for _ in range(16):
        y = y * x + 1.25
    return y


def fit_cpu_host(n_mem: int = 1 << 21, n_fac: int = 1 << 15) -> HardwareSpec:
    """Fit the host's HardwareSpec from microbenchmarks (under x64).

    The paper's method, at our scale: separate the memory hierarchy from
    the functional units, fit each level with a benchmark that isolates it,
    then validate on all 28 kernels (§5.1).

    Each memory-hierarchy level is fitted separately (the paper's L1/L2/
    HBM2 function expansion, tuned against the test chip):

    * ``hbm_write_bw`` — a pure-store fill (``zeros_like``) on a DRAM-
      resident array isolates the store path,
    * ``hbm_read_bw``  — DRAM-resident ``add`` (2 loads + 1 store) at the
      SAME array scale the suite evaluates, with the fitted store time
      subtracted — the load path de-blended from the mixed stream,
    * ``vmem_bw``      — LLC-resident ``add`` (the inner level's stream
      rate; load/store symmetric, there is no port asymmetry to see
      through the LLC at this scale),
    * ``vpu_flops``    — a 16-deep Horner polynomial on an LLC-resident
      array: ALU-bound, so it measures the functional unit, not a cache,
    * per-opcode factors — runs with the *estimated per-level stream
      time subtracted*, so the factor is pure instruction cost (the
      paper's per-OpClass latency table, de-masked from bandwidth).
    """
    by_name = {k.name: k for k in KERNELS}
    with host_x64():
        startup = measure_dispatch_overhead()

        def t_kernel(name: str, n: int, repeats: int = 15) -> float:
            k = by_name[name]
            x1, x2, y0 = _kernel_inputs(k, n)
            return _median_time(_jit_kernel(name), (x1, x2, y0), repeats)

        # --- ALU rate: Horner poly16, L2-resident
        xp = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (n_fac,),
                                       jnp.float64)) * 0.1 + 0.5
        t_poly = _median_time(jax.jit(_poly16), (xp,), 25)
        alu = 32.0 * n_fac / max(t_poly - startup, 1e-9)

        # --- stream rates: LLC-resident and DRAM-resident add (3 streams)
        t_add_l2 = t_kernel("add", n_fac, 25)
        l2_bw = 3 * 8 * n_fac / max(t_add_l2 - startup, 1e-9)
        t_add_mem = t_kernel("add", n_mem)
        blend_bw = 3 * 8 * n_mem / max(t_add_mem - startup, 1e-9)

        # --- DRAM store path: a pure fill isolates writes; the add stream
        # then yields the load path with the store time subtracted
        xm = jnp.zeros((n_mem,), jnp.float64)
        t_fill = _median_time(jax.jit(jnp.zeros_like), (xm,), 15)
        wr_bw = 8 * n_mem / max(t_fill - startup, 1e-9)
        t_loads = t_add_mem - startup - 8 * n_mem / wr_bw
        rd_bw = (2 * 8 * n_mem / t_loads) if t_loads > 0 else blend_bw
        # hierarchy sanity (the §12 monotonicity contract): a noisy-VM LLC
        # measurement can come out slower than DRAM because the small-array
        # run is dispatch-dominated; an inner level is never slower than
        # the level it front-ends
        l2_bw = max(l2_bw, rd_bw, wr_bw)

        # --- per-opcode factors at the EVALUATION scale, with the stream
        # time subtracted (paper: instruction latencies from Fujitsu specs;
        # here: fitted — the 9 factor kernels are fit INPUTS, the other 19
        # suite kernels are out-of-fit predictions, marked in the table).
        factors = {}
        for kname, opcode in _FACTOR_FIT.items():
            k = by_name[kname]
            _, n_in, _, _ = EXPRS[kname]
            n_eval = k.n * SIZE_SCALE
            t = t_kernel(kname, n_eval, 9)
            # per-level asymmetric stream estimate: loads + store
            t_mem = n_in * 8 * n_eval / rd_bw + 8 * n_eval / wr_bw
            factors[opcode] = max(1.0,
                                  (t - startup - t_mem) * alu / n_eval)
        # mod = divide + round-trip; remainder rides the divide entry
        factors.setdefault("remainder", factors.get("divide", 4.0))

    # the fitted two-level hierarchy (LLC -> DRAM) is derived from these
    # boundary scalars by HardwareSpec.memory_hierarchy()
    return CPU_HOST.with_(
        vpu_flops={"f64": alu, "f32": 2 * alu, "default": alu},
        peak_flops={"f64": alu, "f32": 2 * alu, "default": alu},
        transcendental_factor=max(2.0, factors.get("exponential", 4.0)),
        # fitted transcendental entries override the fallback table; the
        # non-fitted per-opcode VPU latencies (minimum/round/...) survive
        opcode_factor={**CPU_HOST.opcode_factor, **factors},
        hbm_read_bw=rd_bw,
        hbm_write_bw=wr_bw,
        vmem_bytes=24 * 2**20,      # LLC stand-in
        vmem_bw=l2_bw,
        # a CPU core stalls on the miss THEN computes: additive composition
        # (the A64FX/TPU overlap model does not transfer to the host)
        dma_overlap=0.0,
        op_startup_ns=startup * 1e9,
    )


@dataclass
class KernelRow:
    """One Table-1 kernel: measured wall time vs simulated estimates."""
    name: str
    ktype: str
    n: int
    measured_us: float
    simulated_us: float          # flat occupancy engine
    fit_input: bool = False      # this kernel informed the parameter fit
    simulated_sched_us: float = 0.0   # dependency-aware schedule engine
    bound_by: str = ""           # binding port of the occupancy engine

    @property
    def diff_pct(self) -> float:
        """Positive = simulator slower than test chip (paper convention)."""
        return 100.0 * (self.simulated_us - self.measured_us) / self.measured_us

    @property
    def sched_diff_pct(self) -> float:
        return 100.0 * (self.simulated_sched_us - self.measured_us) \
            / self.measured_us


@dataclass
class AccuracyTable:
    """Fig. 3-style accuracy summary over the kernel suite (paper §5)."""
    rows: List[KernelRow]
    # parsed per-kernel programs, aligned with rows (kept when
    # keep_programs=True so sweep_o3 can re-schedule without re-measuring)
    programs: List[Program] = dataclasses.field(default_factory=list)

    @property
    def mean_diff(self) -> float:
        return statistics.mean(r.diff_pct for r in self.rows)

    @property
    def std_diff(self) -> float:
        return statistics.pstdev(r.diff_pct for r in self.rows)

    @property
    def mean_abs_diff(self) -> float:
        return statistics.mean(abs(r.diff_pct) for r in self.rows)

    @property
    def within_10pct(self) -> float:
        return sum(abs(r.diff_pct) <= 10.0 for r in self.rows) / len(self.rows)

    @property
    def sched_mean_abs_diff(self) -> float:
        return statistics.mean(abs(r.sched_diff_pct) for r in self.rows)

    @property
    def sched_within_10pct(self) -> float:
        return sum(abs(r.sched_diff_pct) <= 10.0
                   for r in self.rows) / len(self.rows)

    def report(self) -> str:
        lines = [f"{'kernel':<8s}{'type':<10s}{'n':>9s}{'measured_us':>13s}"
                 f"{'occup_us':>10s}{'diff%':>8s}{'sched_us':>10s}"
                 f"{'diff%':>8s}  fit?"]
        for r in self.rows:
            lines.append(f"{r.name:<8s}{r.ktype:<10s}{r.n:>9d}"
                         f"{r.measured_us:>13.2f}{r.simulated_us:>10.2f}"
                         f"{r.diff_pct:>8.1f}{r.simulated_sched_us:>10.2f}"
                         f"{r.sched_diff_pct:>8.1f}"
                         f"  {'*' if r.fit_input else ''}")
        lines.append(
            f"-- all {len(self.rows)} (occupancy):  mean {self.mean_diff:+.1f}%"
            f"  std {self.std_diff:.1f}%  mean|.| {self.mean_abs_diff:.1f}%  "
            f"within+-10%: {100 * self.within_10pct:.0f}%  "
            f"(paper: +1.3%, 7.8%, 6.6%, 82%)")
        lines.append(
            f"-- all {len(self.rows)} (schedule):   "
            f"mean|.| {self.sched_mean_abs_diff:.1f}%  "
            f"within+-10%: {100 * self.sched_within_10pct:.0f}%")
        held = [r for r in self.rows if not r.fit_input]
        if held and len(held) < len(self.rows):
            ho = AccuracyTable(held)
            lines.append(
                f"-- held-out ({len(held)}): mean {ho.mean_diff:+.1f}%  "
                f"std {ho.std_diff:.1f}%  mean|.| {ho.mean_abs_diff:.1f}%  "
                f"within+-10%: {100 * ho.within_10pct:.0f}%   "
                f"(* = parameter-fit inputs, as the paper's Fujitsu-"
                f"supplied latencies were)")
        return "\n".join(lines)


def kernel_accuracy_table(hw: Optional[HardwareSpec] = None,
                          size_scale: int = SIZE_SCALE,
                          kernels: Optional[List[Kernel]] = None,
                          keep_programs: bool = False) -> AccuracyTable:
    hw = hw or fit_cpu_host()
    rows: List[KernelRow] = []
    programs: List[Program] = []
    with host_x64():
        for k in (kernels or KERNELS):
            n = k.n * size_scale
            x1, x2, y0 = _kernel_inputs(k, n)
            f = _jit_kernel(k.name)
            t = _median_time(f, (x1, x2, y0))
            compiled = f.lower(x1, x2, y0).compile()
            rep = simulate(compiled, hw=hw, n_chips=1, compute_dtype="f64",
                           engine="both")
            rows.append(KernelRow(k.name, k.ktype, n, t * 1e6,
                                  rep.engine.t_est * 1e6,
                                  fit_input=k.name in _FACTOR_FIT,
                                  simulated_sched_us=rep.schedule.t_est * 1e6,
                                  bound_by=rep.engine.bound_by))
            if keep_programs:
                programs.append(rep.program)
    return AccuracyTable(rows, programs=programs)


# ------------------------------------------------------- O3 parameter sweep
# Sweep grid for the schedule engine's resource knobs — the paper's
# "detailed parameter tuning of out-of-order resources" (§4), fitted
# against the test chip instead of taken from Fujitsu's NDA tables.
# The batched array kernel made scheduling ~free, so the default grid is
# 2.5x the old 4x3x3 one (ROB windows up to 1024, per-port VPU widths)
# at a fraction of its wall cost.
O3_WINDOWS = (4, 16, 64, 256, 1024)
O3_MEM_WIDTHS = (1, 2, 4)
O3_VPU_WIDTHS = (1, 2)
O3_QUEUE_DEPTHS = (4, 16, 64)


def default_o3_knobs(hw: HardwareSpec, windows=O3_WINDOWS,
                     mem_widths=O3_MEM_WIDTHS, vpu_widths=O3_VPU_WIDTHS,
                     queue_depths=O3_QUEUE_DEPTHS):
    """The default batched O3 knob grid as a packed :class:`~.compiled.O3Knobs`.

    One place builds the (window x mem-width x vpu-width x queue-depth)
    product for every consumer of ``schedule_batch`` — ``sweep_o3``, the
    kernel-suite throughput benchmark, and the model-zoo pipeline
    (``core.zoo``, DESIGN.md §15), which passes compact subsets to stay
    inside its wall-clock budget.
    """
    from .compiled import O3Knobs
    return O3Knobs.from_grid(hw, [(w, mw, vw, qd)
                                  for w in windows
                                  for mw in mem_widths
                                  for vw in vpu_widths
                                  for qd in queue_depths])


def _knob_spec(hw: HardwareSpec, w: int, mw: int, vw: int,
               qd: int) -> HardwareSpec:
    return hw.with_(
        inflight_window=w,
        issue_width={**hw.issue_width, "mem": mw, "vpu": vw},
        queue_depth={p: qd for p in ("mxu", "vpu", "mem", "ici")})


def sweep_o3(table: AccuracyTable, hw: HardwareSpec,
             windows=O3_WINDOWS, mem_widths=O3_MEM_WIDTHS,
             queue_depths=O3_QUEUE_DEPTHS, vpu_widths=O3_VPU_WIDTHS,
             compute_dtype: str = "f64", core_counts=(1,),
             topology=None) -> "O3Sweep":
    """Re-schedule already-measured programs under each knob combination
    (no re-measurement, no recompilation) and rank combos by mean |diff|
    of the schedule engine vs the measured wall times.

    The whole grid runs BATCHED (``core.compiled.schedule_batch``): each
    program is compiled once to array form, shared across every combo, and
    one sequential pass per program advances all combos in lockstep — the
    knob grid is a vector axis, not a python loop.

    ``core_counts`` adds the node engine's core count as a sweep axis:
    each count > 1 runs the batched node engine
    (``core.node.schedule_node_batch``, shard partition), which carries
    every knob combo through its own contention fixpoint.  Rows against
    single-core measurements are only comparable at ``n_cores=1``; the
    extra counts chart the knob grid's scaling behaviour (and ``best`` is
    picked among the smallest swept core count).

    Requires a table built with ``keep_programs=True``.  Returns an
    :class:`O3Sweep` (ranked results + the tuned ``HardwareSpec``).
    See DESIGN.md §13 (the batched array kernel), §17 (the batched node
    engine behind ``core_counts``) and §11 (what the knobs mean);
    ``core.zoo.estimate_program`` is the same machinery pointed at
    whole-application programs (DESIGN.md §15)."""
    from .compiled import O3Knobs, compile_program, schedule_batch
    from .node import compile_node, schedule_node_sweep
    if not table.programs:
        raise ValueError("sweep_o3 needs kernel_accuracy_table("
                         "keep_programs=True)")
    import numpy as np
    combos = [(w, mw, vw, qd) for w in windows for mw in mem_widths
              for vw in vpu_widths for qd in queue_depths]
    knobs = O3Knobs.from_grid(hw, combos)
    core_counts = tuple(core_counts) or (1,)
    # per-op costs are independent of the O3 knobs: compile each program
    # ONCE per core count and run the shared array form across the grid
    diffs = np.empty((len(table.programs), len(core_counts), knobs.batch))
    node_counts = sorted({k for k in core_counts if k > 1})
    for r, (prog, row) in enumerate(zip(table.programs, table.rows)):
        # all node counts ride ONE fused [C*B] batch (schedule_node_sweep
        # shares the compiled batch form and the contention fixpoint
        # across the count axis); the 1-core rows keep the array engine
        t_by_count = {}
        if 1 in core_counts:
            cp = compile_program(prog, hw, compute_dtype=compute_dtype)
            t_by_count[1] = schedule_batch(cp, knobs)
        if node_counts:
            sw = schedule_node_sweep(
                compile_node(prog, hw, compute_dtype=compute_dtype),
                hw, knobs, node_counts, topology, partition="shard")
            t_by_count.update(zip(node_counts, sw))
        for ci, n_cores in enumerate(core_counts):
            t_us = t_by_count[n_cores] * 1e6
            diffs[r, ci] = np.abs(t_us - row.measured_us) \
                / row.measured_us * 100.0
    mean_abs = diffs.mean(axis=0)
    within = (diffs <= 10.0).mean(axis=0)
    results: List[Dict] = []
    for ci, n_cores in enumerate(core_counts):
        for k, (w, mw, vw, qd) in enumerate(combos):
            results.append({"inflight_window": w, "mem_issue_width": mw,
                            "vpu_issue_width": vw, "queue_depth": qd,
                            "n_cores": n_cores,
                            "mean_abs_diff_pct": float(mean_abs[ci, k]),
                            "within_10pct": float(within[ci, k])})
    results.sort(key=lambda r: r["mean_abs_diff_pct"])
    min_cores = min(core_counts)
    best = next(r for r in results if r["n_cores"] == min_cores)
    tuned = _knob_spec(hw, best["inflight_window"], best["mem_issue_width"],
                       best["vpu_issue_width"], best["queue_depth"])
    return O3Sweep(results=results, best=tuned)


@dataclass
class O3Sweep:
    """Ranked results of one batched O3 knob sweep (paper §4 tuning)."""
    results: List[Dict]          # ranked best-first
    best: HardwareSpec           # hw with the winning O3 knobs applied

    def report(self, top: int = 8) -> str:
        lines = [f"{'window':>7s}{'mem_w':>7s}{'vpu_w':>7s}{'qdepth':>7s}"
                 f"{'cores':>7s}{'mean|.|%':>10s}{'<=10%':>7s}"]
        for r in self.results[:top]:
            lines.append(f"{r['inflight_window']:>7d}"
                         f"{r['mem_issue_width']:>7d}"
                         f"{r.get('vpu_issue_width', 1):>7d}"
                         f"{r['queue_depth']:>7d}"
                         f"{r.get('n_cores', 1):>7d}"
                         f"{r['mean_abs_diff_pct']:>10.1f}"
                         f"{100 * r['within_10pct']:>6.0f}%")
        return "\n".join(lines)
