"""The train step's regions: named scopes in the program, read back from the
compiled step's metadata by ``stats.region_of``, priced region by region by
the simulator (``SimReport.sections``)."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, RunConfig, ShapeConfig, reduced_config
from repro.core.hlo import op_names, parse_program
from repro.core.hwspec import TPU_V5E
from repro.core.simulate import simulate
from repro.core.stats import REGIONS, SHARED_REGIONS, Stats, region_of
from repro.launch.train import build_training
from repro.models.lm import build_model

STEP = "jit(train_step)/"
BODY = "transpose(jvp(layers))/while/body/closed_call/checkpoint/"


@pytest.mark.parametrize("op_name, want", [
    (STEP + "jvp(head)/...d,vd->...v/dot_general", ("head", "forward")),
    (STEP + "transpose(jvp(head))/jit(take_along_axis)/scatter-add",
     ("head", "backward")),
    (STEP + BODY + "mixer.gate/jit(silu)/mul", ("mixer.gate", "backward")),
    (STEP + BODY + "rematted_computation/mixer.in_proj/bsd,de->bse/"
     "dot_general", ("mixer.in_proj", "recompute")),
    (STEP + "jvp(layers)/while/body/closed_call/block_norm/rsqrt",
     ("block_norm", "forward")),
    # nested scopes: the innermost region wins
    (STEP + BODY + "mixer.ssd_chunk/jit(ssd_scan)/mixer.ssd_state/while/"
     "body/add", ("mixer.ssd_state", "backward")),
    (STEP + BODY + "mixer.ssd_chunk/jit(ssd_scan)/mixer.ssd_state/"
     "transpose;mixer.ssd_state/bcihn,bchnp->bcihp/transpose",
     ("mixer.ssd_state", "backward")),
    (STEP + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "mixer.ssd_chunk/pallas_call", ("mixer.ssd_chunk", "backward")),
    (STEP + "optimizer/sqrt", ("optimizer", "optimizer")),
    # no region
    (STEP + "transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/jit(ssd_scan)/exp", (None, "recompute")),
    ("jit(loss)/transpose(jvp())/pallas_call", (None, "backward")),
    ("params['layers']['mamba']['wx']", (None, "forward")),
    ("", (None, "forward")),
])
def test_region_of_path_forms(op_name, want):
    assert region_of(op_name) == want


def test_stats_add_to_a_named_section_alone():
    s = Stats()
    with s.section("steady"):
        s.add("t_est_s", 2.0, section="head")
    assert s.get("t_est_s", "head") == 2.0
    assert s.get("t_est_s", "steady") == 0.0
    assert s.get("t_est_s") == 0.0


# ------------------------------------------------ a compiled train step
def _step_text(scopes: bool = True) -> str:
    """The compiled text of a 1-layer Mamba-2 train step at a reduced
    width, full remat and the Pallas SSD path (interpreted on the CPU)."""
    mc = dataclasses.replace(reduced_config(ARCHS["mamba2-1.3b"]),
                             n_layers=1, remat="full")
    model = build_model(mc, ssd_impl="pallas")
    run = RunConfig(model=mc, shape=ShapeConfig("t", 64, 2, "train"),
                    param_dtype="float32", compute_dtype="float32")
    jitted, init, _ = build_training(model, run)
    params, opt = init(0)
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32)}
    with contextlib.ExitStack() as stack:
        if not scopes:
            stack.enter_context(pytest.MonkeyPatch.context()).setattr(
                jax, "named_scope", lambda name: contextlib.nullcontext())
        return jitted.lower(params, opt, batch).compile().as_text()


@pytest.fixture(scope="module")
def step_text():
    return _step_text()


def test_every_dot_and_the_ssd_kernel_fall_in_a_region(step_text):
    names = op_names(step_text)
    dots = [n for n, line in _instructions(step_text) if " dot(" in line]
    assert dots and all(region_of(names.get(n, ""))[0] for n in dots)
    # the interpreted Pallas kernel is the SSD call's loop in ops.ssd_scan
    kernel = {o for o in names.values()
              if "jit(ssd_scan)/while" in o and "ssd_state" not in o}
    assert kernel
    assert {region_of(o)[0] for o in kernel} == {"mixer.ssd_chunk"}
    seen = {region_of(o) for o in names.values()}
    assert {p for r, p in seen if r} == {"forward", "recompute", "backward",
                                         "optimizer"}
    assert {r for r, _ in seen if r} == set(REGIONS) - set(SHARED_REGIONS)


def test_every_instruction_of_the_hybrid_step_falls_in_a_region():
    """zamba2 at a reduced width (five layers, the shared block invoked at
    layers 2 and 4), its train step compiled for the CPU: every instruction
    the device runs as a unit (outside fusion bodies and reducers) maps to
    a region, and the shared block's regions appear in the forward, the
    recompute and the backward."""
    mc = dataclasses.replace(reduced_config(ARCHS["zamba2-1.2b"]),
                             remat="full")
    model = build_model(mc, ssd_impl="pallas", kv_block=32)
    run = RunConfig(model=mc, shape=ShapeConfig("t", 64, 2, "train"),
                    param_dtype="float32", compute_dtype="float32")
    jitted, init, _ = build_training(model, run)
    params, opt = init(0)
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32)}
    text = jitted.lower(params, opt, batch).compile().as_text()
    names = op_names(text)
    called = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    comp, units = None, []
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{$", line)
        if m:
            comp = m.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = \S+ (\S+?)\(", line)
        if m and comp not in called and m.group(2) != "parameter":
            units.append(m.group(1))
    assert len(units) > 1000
    assert [n for n in units if not region_of(names.get(n, ""))[0]] == []
    seen = {region_of(o) for o in names.values()}
    assert {r for r, _ in seen if r} == set(REGIONS)
    for r in SHARED_REGIONS:
        assert {p for q, p in seen if q == r} == {"forward", "recompute",
                                                 "backward"}, r


def _instructions(text):
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = ", line)
        if m:
            yield m.group(1), line


def _canonical(text: str) -> str:
    """The module's text without what scopes may change: no metadata, no
    source tables, the parameter names of computation headers left out,
    and every instruction and computation renamed by its first use."""
    names: dict = {}
    out = []
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames") or re.match(r"\d+ ", line):
            continue
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        if line.endswith("{") and " = " not in line:
            line = re.sub(r"[\w.\-]+: ", ": ", line)
        out.append(re.sub(r"%([\w.\-]+)", lambda m: "%" + names.setdefault(
            m.group(1), f"i{len(names)}"), line))
    return "\n".join(out)


def test_scopes_leave_the_compiled_step_unchanged(step_text):
    bare = _canonical(_step_text(scopes=False))
    assert "metadata" not in bare and bare == _canonical(step_text)
    assert bare != _canonical(step_text.replace(" multiply(", " add(", 1))


def test_parser_keeps_each_instructions_op_name(step_text):
    prog = parse_program(step_text)
    names = op_names(step_text)
    named = [o for o in prog.ops if o.op_name]
    assert len(named) > len(prog.ops) // 2
    assert all(names[o.name] == o.op_name for o in named)


def test_sections_split_the_whole_step(step_text):
    rep = simulate(step_text, hw=TPU_V5E, compute_dtype="f32")
    s = rep.sections
    parts = s.sections()
    assert set(parts) <= set(REGIONS) | {"other"} and "mixer.in_proj" in parts
    assert sum(s.get("t_serial_s", p) for p in parts) == pytest.approx(
        rep.engine.t_serial, rel=1e-12)
    for port, busy in rep.engine.port_busy.items():
        assert sum(s.get(f"busy_{port}_s", p) for p in parts) == \
            pytest.approx(busy, rel=1e-12, abs=1e-18)
    assert s.get("t_est_s") == rep.engine.t_est
    assert "sections (program regions" in rep.pa and "[mixer.gate]" in rep.pa


def test_region_metadata_leaves_every_statistic_bit_identical(step_text):
    bare = re.sub(r', metadata=\{[^}]*\}', "", step_text)
    assert "op_name" not in bare
    a = simulate(step_text, hw=TPU_V5E, compute_dtype="f32")
    b = simulate(bare, hw=TPU_V5E, compute_dtype="f32")
    assert a.engine.t_est == b.engine.t_est
    assert a.engine.t_serial == b.engine.t_serial
    assert a.engine.port_busy == b.engine.port_busy
    assert a.engine.by_class_time == b.engine.by_class_time
    assert a.program_summary == b.program_summary
    assert b.sections.sections() == ["other"]
    assert "sections (program regions" not in b.pa
