"""The paper's contribution: HLO parser, engine, roofline, PA, stats.

Includes hypothesis property tests on the simulator's invariants (the
assignment's property-test requirement)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.engine import collective_factor, simulate_program
from repro.core.hlo import OpStat, Program, parse_program
from repro.core.hwspec import SPECS, TPU_V5E
from repro.core.roofline import model_flops, roofline_from_program
from repro.core.simulate import simulate
from repro.core.stats import Stats


# ------------------------------------------------------------ parser, real HLO
def _compiled(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.slow
def test_parser_dot_flops_exact():
    M, K, N = 64, 128, 32
    a = jnp.ones((M, K), jnp.float32)
    b = jnp.ones((K, N), jnp.float32)
    prog = parse_program(_compiled(lambda a, b: a @ b, a, b).as_text())
    dots = [o for o in prog.ops if o.opclass == "matmul"]
    assert len(dots) >= 1
    assert sum(o.flops * o.count for o in dots) == 2 * M * K * N
    mnk = dots[0].dot_dims
    assert sorted(mnk) == sorted((M, N, K))


@pytest.mark.slow
def test_parser_while_trip_multiplication():
    """A scan of T steps must multiply body op costs by T."""
    T, M = 9, 32
    a = jnp.ones((M, M), jnp.float32)

    def f(a):
        def body(c, _):
            return c @ a, None
        out, _ = jax.lax.scan(body, a, None, length=T)
        return out

    prog = parse_program(_compiled(f, a).as_text())
    dot_flops = sum(o.flops * o.count for o in prog.ops
                    if o.opclass == "matmul")
    assert dot_flops == T * 2 * M * M * M


@pytest.mark.slow
def test_parser_transcendental_classification():
    x = jnp.ones((1024,), jnp.float32)
    prog = parse_program(_compiled(lambda x: jnp.exp(x) + jnp.sin(x), x)
                         .as_text())
    tb = {}
    for o in prog.ops:
        for k, v in o.trans_by_opcode.items():
            tb[k] = tb.get(k, 0) + v * o.count
    assert tb.get("exponential", 0) == 1024
    assert tb.get("sine", 0) == 1024


@pytest.mark.slow
def test_parser_dus_inplace_and_slice_reads():
    """Scan emitting per-step rows must NOT count full-buffer traffic per
    step (in-place DUS + sliced reads)."""
    T, M = 16, 256
    xs = jnp.ones((T, M, M), jnp.float32)

    def f(xs):
        def body(c, x):
            return c + x, c[0]          # ys: one row per step
        return jax.lax.scan(body, jnp.zeros((M, M)), xs)

    prog = parse_program(_compiled(f, xs).as_text())
    total_bytes = prog.bytes_accessed
    # full buffer is T*M*M*4 = 4 MiB; per-step slice traffic is ~M*M*4 (x
    # slice + carry read/write + copies).  Without slice/in-place modeling
    # the scan costs ~T * full-buffer = 67 MB; with it, well under half.
    assert total_bytes < 8 * T * M * M * 4


def test_collective_parsing_synthetic():
    hlo = """
HloModule m, num_partitions=16

ENTRY %main (p0: f32[1024,256]) -> f32[1024,256] {
  %p0 = f32[1024,256] parameter(0)
  %ag = f32[1024,256] all-reduce(%p0), replica_groups=[16,16]<=[256]
  ROOT %out = f32[1024,256] add(%ag, %ag)
}
"""
    prog = parse_program(hlo)
    colls = [o for o in prog.ops if o.opclass == "collective"]
    assert len(colls) == 1
    assert colls[0].opcode == "all-reduce"
    assert colls[0].group_size == 16
    assert colls[0].comm_bytes == 1024 * 256 * 4
    assert prog.n_partitions == 16


_CONV_FUSION = """
HloModule m

%fused_mm (p0: bf16[{lhs}], p1: bf16[{rhs}]) -> f32[{out}] {{
  %p0 = bf16[{lhs}]{{2,1,0}} parameter(0)
  %p1 = bf16[{rhs}]{{2,1,0}} parameter(1)
  ROOT %conv = f32[{out}]{{2,1,0}} convolution(%p0, %p1), {attrs}
}}

ENTRY %main (a: bf16[{lhs}], w: bf16[{rhs}]) -> f32[{out}] {{
  %a = bf16[{lhs}]{{2,1,0}} parameter(0)
  %w = bf16[{rhs}]{{2,1,0}} parameter(1)
  ROOT %f = f32[{out}]{{2,1,0}} fusion(%a, %w), kind=kOutput, calls=%fused_mm
}}
"""


@pytest.mark.parametrize("lhs,rhs,out,attrs,mnk", [
    # a dot the TPU compiler wrote as a 1-wide convolution
    ("2,256,512", "512,1024,1", "2,256,1024",
     "window={size=1}, dim_labels=0bf_io0->0bf", (512, 1024, 512)),
    # a batched dot: the batch dim rides as a fully base-dilated window
    ("4,256,128", "4,256,128", "4,128,128",
     "window={size=4 stride=3 lhs_dilate=4}, dim_labels=0fb_0io->0bf",
     (512, 128, 256)),
])
def test_parser_convolution_fusion_is_matmul(lhs, rhs, out, attrs, mnk):
    """A fusion whose matmul is a ``convolution`` (as TPU-compiled HLO
    writes dots) is classed matmul, counts the dot's FLOPs and is priced
    on the MXU, not the VPU."""
    prog = parse_program(_CONV_FUSION.format(lhs=lhs, rhs=rhs, out=out,
                                             attrs=attrs))
    (op,) = prog.ops
    m, n, k = mnk
    assert op.opcode == "fusion" and op.opclass == "matmul"
    assert op.flops == 2 * m * n * k
    assert op.dot_dims == mnk
    r = simulate_program(prog, TPU_V5E)
    assert r.port_busy["mxu"] > 0.0 and r.port_busy.get("vpu", 0.0) == 0.0


# ------------------------------------------------------------------- engine
def _mk_op(**kw):
    base = dict(name="x", opcode="dot", opclass="matmul", dtype="bf16")
    base.update(kw)
    return OpStat(**base)


def test_engine_matmul_time():
    o = _mk_op(flops=2 * 1024**3, bytes_accessed=1e6,
               dot_dims=(1024, 1024, 512))
    prog = Program(ops=[o], entry="e", n_partitions=1)
    r = simulate_program(prog, TPU_V5E)
    expect = 2 * 1024**3 / TPU_V5E.matmul_flops("bf16")
    assert r.port_busy["mxu"] == pytest.approx(expect, rel=1e-6)
    assert r.mxu_utilization == 1.0


def test_engine_small_dot_goes_vpu_without_tile_padding():
    o = _mk_op(flops=2 * 64 * 2 * 1000, dot_dims=(64, 1000, 2),
               bytes_accessed=1e5)
    prog = Program(ops=[o], entry="e", n_partitions=1)
    r = simulate_program(prog, TPU_V5E)
    assert r.port_busy.get("mxu", 0.0) == 0.0
    assert r.port_busy["vpu"] < 1e-4     # no 128^3 quantization blowup


def test_engine_collective_ring_factors():
    assert collective_factor("all-reduce", 1) == 0.0
    assert collective_factor("all-reduce", 4) == pytest.approx(1.5)
    assert collective_factor("all-gather", 8) == 7.0
    assert collective_factor("reduce-scatter", 8) == pytest.approx(7 / 8)


# ------------------------------------------------- hypothesis property tests
bytes_st = st.floats(min_value=0, max_value=1e13, allow_nan=False)
flops_st = st.floats(min_value=0, max_value=1e16, allow_nan=False)


@settings(max_examples=60, deadline=None)
@given(f=flops_st, b1=bytes_st, b2=bytes_st)
def test_property_memory_monotonic(f, b1, b2):
    """More bytes on the same program never reduces estimated time."""
    lo, hi = sorted((b1, b2))
    def t(b):
        o = _mk_op(opclass="elementwise", opcode="add", flops=f,
                   bytes_accessed=b, dot_dims=None)
        return simulate_program(Program([o], "e", 1), TPU_V5E).t_est
    assert t(hi) >= t(lo) - 1e-12


@settings(max_examples=60, deadline=None)
@given(g=st.integers(min_value=1, max_value=4096),
       payload=st.floats(min_value=1, max_value=1e12, allow_nan=False))
def test_property_collective_nonnegative_and_bounded(g, payload):
    for kind in ("all-reduce", "all-gather", "reduce-scatter",
                 "all-to-all", "collective-permute"):
        fac = collective_factor(kind, g)
        assert fac >= 0.0
        if kind in ("all-reduce", "reduce-scatter", "all-to-all"):
            assert fac <= 2.0            # wire bytes never exceed 2x payload


@settings(max_examples=40, deadline=None)
@given(c=st.floats(1, 1e6), m=st.floats(1, 1e6), i=st.floats(1, 1e6))
def test_property_roofline_dominant_is_max(c, m, i):
    prog = Program([], "e", 1)
    rf = roofline_from_program(prog, TPU_V5E, 1, 0.0)
    import dataclasses
    rf = dataclasses.replace(rf, compute_s=c, memory_s=m, collective_s=i)
    assert rf.t_bound == max(c, m, i)
    assert {"compute": c, "memory": m, "collective": i}[rf.dominant] \
        == max(c, m, i)


@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 100), d=st.integers(1, 10_000),
       kind=st.sampled_from(["train", "prefill"]))
def test_property_model_flops(n, d, kind):
    mf = model_flops(n, d, kind)
    assert mf == (6.0 if kind == "train" else 2.0) * n * d


@settings(max_examples=60, deadline=None)
@given(g1=st.integers(min_value=1, max_value=4096),
       g2=st.integers(min_value=1, max_value=4096))
def test_property_collective_factor_monotone_in_group_size(g1, g2):
    """Growing the group never cheapens a collective (ring algorithm)."""
    lo, hi = sorted((g1, g2))
    for kind in ("all-reduce", "all-gather", "reduce-scatter",
                 "all-to-all", "collective-permute"):
        assert collective_factor(kind, lo) <= collective_factor(kind, hi) \
            + 1e-12, (kind, lo, hi)


@settings(max_examples=40, deadline=None)
@given(b=st.floats(min_value=1e3, max_value=1e12, allow_nan=False),
       payload=st.floats(min_value=1e3, max_value=1e12, allow_nan=False),
       g=st.integers(min_value=2, max_value=256))
def test_property_bf16_denormalization_halves_f32_traffic(b, payload, g):
    """compute_dtype='bf16' must cost f32 bytes AND collective payloads at
    half width (the inverted XLA:CPU float-normalization, DESIGN.md §7).

    Exact 2x halving holds because TPU_V5E is a scratch-memory spec
    (warm_caches=False): cold traffic always routes to HBM, so full- and
    half-width bytes see the same bandwidth at every size (hierarchy
    routing itself is pinned by tests/test_memory_hierarchy.py)."""
    ew = _mk_op(opclass="elementwise", opcode="add", dtype="f32",
                flops=0.0, bytes_accessed=b, dot_dims=None)
    coll = _mk_op(name="ar", opclass="collective", opcode="all-reduce",
                  dtype="f32", comm_bytes=payload, group_size=g,
                  dot_dims=None)
    prog = Program([ew, coll], "e", 1)
    full = simulate_program(prog, TPU_V5E, compute_dtype=None)
    half = simulate_program(prog, TPU_V5E, compute_dtype="bf16")
    assert half.port_busy["mem"] == pytest.approx(
        0.5 * full.port_busy["mem"], rel=1e-9)
    startup = TPU_V5E.collective_startup_us * 1e-6
    assert half.port_busy["ici"] - startup == pytest.approx(
        0.5 * (full.port_busy["ici"] - startup), rel=1e-9)
    # bf16-native ops are untouched
    bf = _mk_op(opclass="elementwise", opcode="add", dtype="bf16",
                flops=0.0, bytes_accessed=b, dot_dims=None)
    prog_bf = Program([bf], "e", 1)
    assert simulate_program(prog_bf, TPU_V5E, compute_dtype="bf16").t_est \
        == pytest.approx(simulate_program(prog_bf, TPU_V5E).t_est, rel=1e-9)


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 15))
def test_property_port_busy_totals_equal_sum_over_ops(seed, n):
    """port_busy is an exact per-op sum: totals must match the per-op
    OpTime decomposition (<=20 ops so top_ops holds all of them)."""
    rng = np.random.default_rng(seed)
    classes = [("matmul", "dot"), ("elementwise", "add"), ("data", "copy"),
               ("collective", "all-reduce"), ("transcendental",
                                              "exponential")]
    ops = []
    for i in range(n):
        cls, opc = classes[rng.integers(len(classes))]
        ops.append(_mk_op(
            name=f"o{i}", opclass=cls, opcode=opc, dtype="f32",
            flops=float(rng.integers(1, 10**9)),
            bytes_accessed=float(rng.integers(1, 10**9)),
            comm_bytes=float(rng.integers(1, 10**9)),
            group_size=int(rng.integers(1, 64)),
            count=float(rng.integers(1, 10)), dot_dims=None))
    r = simulate_program(Program(ops, "e", 1), TPU_V5E)
    for port in ("mxu", "vpu"):
        want = sum(t.t_compute * t.op.count for t in r.top_ops
                   if t.port == port)
        assert r.port_busy.get(port, 0.0) == pytest.approx(want, rel=1e-9, abs=1e-18)
    assert r.port_busy["mem"] == pytest.approx(
        sum(t.t_mem * t.op.count for t in r.top_ops), rel=1e-9, abs=1e-18)
    assert r.port_busy["ici"] == pytest.approx(
        sum(t.t_ici * t.op.count for t in r.top_ops), rel=1e-9, abs=1e-18)
    assert sum(r.by_class_time.values()) == pytest.approx(
        r.t_serial - r.startup, rel=1e-9)


# ------------------------------------------------------------------ simulate
@pytest.mark.slow
def test_simulate_end_to_end_small_matmul():
    a = jnp.ones((256, 256), jnp.bfloat16)
    compiled = _compiled(lambda a: a @ a, a)
    rep = simulate(compiled, hw=TPU_V5E, n_chips=1,
                   model_flops_global=2 * 256**3)
    assert rep.roofline.compute_s > 0
    assert rep.roofline.useful_flops_ratio == pytest.approx(1.0, rel=0.2)
    assert "PA report" in rep.pa
    assert rep.t_est > 0


def test_hwspec_registry():
    assert {"tpu_v5e", "tpu_v4", "a64fx_cmg", "a64fx_core",
            "cpu_host"} <= set(SPECS)
    assert SPECS["tpu_v5e"].peak_flops["bf16"] == 197e12
    assert SPECS["tpu_v5e"].hbm_read_bw == 819e9
    assert SPECS["tpu_v5e"].ici_bw_per_link == 50e9


# -------------------------------------------------------------------- stats
def test_stats_sections_and_delta():
    s = Stats()
    with s.section("warmup"):
        s.add("steps", 3)
    with s.section("steady"):
        s.add("steps", 10)
        s.add("tokens", 100)
    assert s.get("steps") == 13                  # global accumulates
    assert s.get("steps", "steady") == 10
    d = s.delta("steady", "warmup")
    assert d["steps"] == 7
    assert d["tokens"] == 100
    assert "warmup" in s.report() and "steady" in s.report()


def test_stats_nested_sections_credit_enclosing():
    """add() credits the FULL active stack: an enclosing section sees its
    nested sections' counters (regression — only the innermost section
    and __global__ used to be credited)."""
    s = Stats()
    with s.section("steady"):
        s.add("steps", 2)
        with s.section("batch"):
            s.add("steps", 5)
            s.add("tokens", 50)
    assert s.get("steps", "steady") == 7         # encloser sees nested
    assert s.get("tokens", "steady") == 50
    assert s.get("steps", "batch") == 5
    assert s.get("steps") == 7                   # global credited once
    # recursive re-entry is credited once, not twice
    with s.section("steady"):
        with s.section("steady"):
            s.add("steps", 1)
    assert s.get("steps", "steady") == 8
    assert s.get("steps") == 8


def test_stats_wall_time_matches_counter_semantics():
    """section() wall-time attribution is consistent with add():
    enclosing sections see nested wall time, recursive re-entry is
    credited once (at the outermost exit), and __global__ accumulates
    top-level wall time (regression — wall_s used to credit only the
    exited name, double-counting recursion and never reaching
    __global__)."""
    s = Stats()
    with s.section("outer"):
        time.sleep(0.01)
        with s.section("inner"):
            time.sleep(0.01)
    outer = s.get("wall_s", "outer")
    inner = s.get("wall_s", "inner")
    assert inner >= 0.01
    assert outer >= inner + 0.01          # encloser spans nested wall
    # __global__ sees exactly the top-level section's wall
    assert s.get("wall_s") == outer
    assert s.get("entries", "outer") == 1
    assert s.get("entries", "inner") == 1

    # recursive re-entry: credited once, at the outermost exit
    r = Stats()
    with r.section("loop"):
        time.sleep(0.01)
        with r.section("loop"):
            time.sleep(0.01)
    wall = r.get("wall_s", "loop")
    assert wall >= 0.02                   # the outermost dt, once
    assert wall < 0.2                     # not inner+outer double-counted
    assert r.get("entries", "loop") == 1
    assert r.get("wall_s") == wall
