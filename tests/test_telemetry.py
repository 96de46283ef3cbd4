"""Stage names in the round program and spans in the sweep executor
(``repro.telemetry``), and the benchmark's readers of them."""
import contextlib
import importlib.util
import re
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.experiments import SweepSpec, run_sweep
from repro.experiments.grid import sweep_hlo

ROOT = Path(__file__).resolve().parents[1]
FAMILY = ("fedpbc", "fedavg", "fedavg_all", "fedavg_known_p")
PHASES = ["sweep.batch", "sweep.dispatch", "sweep.wait", "sweep.train_eval",
          "sweep.rows"]
DEVICE_READERS = {"sample_us.mlp": ["fed.sample"],
                  "local_train_us.mlp": ["fed.local_train"],
                  "aggregate_us.mlp": ["fed.aggregate"],
                  "other_device_us.mlp": ["fed.link", "fed.broadcast",
                                          "fed.eval", telemetry.OTHER]}


@pytest.fixture(scope="module")
def spec():
    """The mlp-family grid (4-algorithm family x 2 lrs x 2 seeds) at a tiny
    size."""
    return SweepSpec(algorithms=FAMILY, schemes=("bernoulli_tv",),
                     seeds=(3, 4), lrs=(0.05, 0.1), rounds=4, eval_every=2,
                     num_clients=6, local_steps=2, batch_size=4,
                     n_per_class=20, n_train=150, per_client=8)


@pytest.fixture(scope="module")
def hlo(spec):
    run_sweep(spec)
    return sweep_hlo(spec)


def _instructions(text):
    """``(line, opcode, stage)`` of every instruction of an HLO module."""
    out = []
    for line in text.splitlines():
        parsed = telemetry._parse(line)
        if parsed is None:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        stage = telemetry.stage_of_op_name(m.group(1)) if m else "other"
        out.append((line, parsed[1], stage))
    return out


def _profile_name(line):
    """An instruction as a device profile names it: no metadata."""
    return line.strip().split(", metadata=")[0]


# -- the span ring ------------------------------------------------------------


def test_spans_link_parents_and_record_on_exit():
    with telemetry.span("t.outer"):
        with telemetry.span("t.inner"):
            time.sleep(0.002)
        with telemetry.span("t.inner"):
            pass
    spans = telemetry.records()
    outer = telemetry.last("t.outer", 1, spans)[0]
    inner = telemetry.last("t.inner", 2, spans)
    assert [s.parent for s in inner] == [outer.id, outer.id]
    assert telemetry.children(outer, spans) == inner
    assert spans.index(inner[1]) < spans.index(outer)   # children end first
    assert outer.start <= inner[0].start and inner[1].end <= outer.end
    assert inner[0].seconds >= 0.002
    assert telemetry.self_seconds(outer, spans) == pytest.approx(
        outer.seconds - inner[0].seconds - inner[1].seconds)


def test_span_records_when_the_block_raises():
    with pytest.raises(ValueError):
        with telemetry.span("t.raises"):
            raise ValueError
    assert telemetry.records()[-1].name == "t.raises"
    with telemetry.span("t.after"):
        pass
    assert telemetry.records()[-1].parent is None


def test_self_seconds_subtracts_direct_children_only():
    S = telemetry.Span
    spans = [S(2, "c", 1, 1.0, 2.0), S(3, "c", 1, 3.0, 3.5),
             S(4, "g", 1, 3.6, 3.7), S(5, "gg", 4, 3.6, 3.65),
             S(1, "p", None, 0.0, 5.0)]
    parent = spans[-1]
    assert telemetry.self_seconds(parent, spans) == pytest.approx(
        5.0 - 1.0 - 0.5 - 0.1)


def test_ring_is_bounded():
    for _ in range(telemetry.RING_SIZE + 10):
        with telemetry.span("t.fill"):
            pass
    spans = telemetry.records()
    assert len(spans) == telemetry.RING_SIZE
    assert all(s.name == "t.fill" for s in spans)


@pytest.mark.parametrize("n, want", [(0, []), (2, [3, 5]), (3, [1, 3, 5]),
                                     (9, [1, 3, 5])])
def test_last_selects_the_newest_calls(n, want):
    S = telemetry.Span
    spans = [S(1, "run", None, 0, 1), S(2, "other", None, 1, 2),
             S(3, "run", None, 2, 3), S(4, "other", None, 3, 4),
             S(5, "run", None, 4, 5)]
    assert [s.id for s in telemetry.last("run", n, spans)] == want


# -- stage attribution ----------------------------------------------------------


def _scoped(x, idx):
    with jax.named_scope("fed.sample"):
        g = x[idx]

    def body(c, _):
        with jax.named_scope("fed.local_train"):
            c = jnp.tanh(c @ g.T @ g)
        return c, None

    c, _ = jax.lax.scan(body, x[:4] * 2.0, None, length=3)
    with jax.named_scope("fed.aggregate"):
        return c.mean(0)


def test_stage_seconds_on_a_compiled_program():
    text = jax.jit(_scoped).lower(jnp.ones((16, 8)),
                                  jnp.arange(4)).compile().as_text()
    instrs = _instructions(text)
    assert any(op == "while" for _, op, _ in instrs)
    ops = [(_profile_name(line), 1.0) for line, _, _ in instrs]
    ops.append(("%mystery.7 = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)",
                5.0))
    got = telemetry.stage_seconds(ops, [text])
    want = {}
    for _, op, stage in instrs:
        if op not in telemetry.CONTAINERS:
            want[stage] = want.get(stage, 0.0) + 1.0
    want["other"] = want.get("other", 0.0) + 5.0
    assert got == want
    assert {"fed.sample", "fed.local_train", "fed.aggregate"} <= set(got)


def test_stage_seconds_matches_name_shape_and_opcode():
    """A name repeats across modules: the op's result shape and opcode pick
    the instruction, and an op of another program whose name collides
    goes to "other"."""
    a = ('  %fusion.1 = s32[8]{0:T(128)} fusion(%p), kind=kLoop, calls=%c, '
         'metadata={op_name="jit(f)/fed.sample/gather"}\n')
    b = ('  ROOT %fusion.1 = f32[8,4]{1,0:T(8,128)} fusion(%q), kind=kLoop, '
         'calls=%d, metadata={op_name="jit(g)/while/body/fed.local_train/dot"}'
         '\n  %while.3 = (s32[], f32[8]{0}) while(%t), condition=%e, '
         'body=%f, metadata={op_name="jit(g)/while"}\n')
    ops = [("%fusion.1 = s32[8]{0:T(128)} fusion(s32[8]{0} %p), kind=kLoop",
            2.0),
           ("%fusion.1 = f32[8,4]{1,0:T(8,128)} fusion(f32[8,4]{1,0} %q)",
            3.0),
           ("%fusion.1 = u32[16,2]{0,1:T(2,128)} fusion(u32[4,2]{0,1} %r)",
            0.25),
           ("%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)",
            9.0),
           ("%copy.2 = f32[8]{0} copy(f32[8]{0} %r)", 0.5)]
    assert telemetry.stage_seconds(ops, [a, b]) == {
        "fed.sample": 2.0, "fed.local_train": 3.0, "other": 0.75}
    assert [st for _, st, _ in telemetry.attribute(ops, [a, b])] == [
        "fed.sample", "fed.local_train", None, None]


@pytest.mark.parametrize("op_name, stage", [
    ("jit(scan_point)/vmap(while)/body/fed.sample/gather", "fed.sample"),
    ("jit(f)/vmap(fed.local_train)/while/body/dot_general",
     "fed.local_train"),
    ("jit(f)/fed.aggregate/fed.eval/dot_general", "fed.eval"),
    ("jit(f)/fed.unknown/add", "other"),
    ("jit(f)/while/body/add", "other"),
])
def test_stage_of_op_name_takes_the_innermost_stage(op_name, stage):
    assert telemetry.stage_of_op_name(op_name) == stage


@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/fed.local_train/fed.cast/convert_element_type", "fed.cast"),
    ("jit(f)/transpose(jvp(fed.local_train))/while/body/checkpoint/lm.attn/"
     "dot_general", "lm.attn"),
    ("jit(f)/fed.local_train/lm.head/while/body/dot_general", "lm.head"),
    ("jit(f)/fed.eval/while/body/lm.mlp/mul", "lm.mlp"),
    ("jit(f)/fed.local_train/while/body/add", "other"),
])
def test_scopes_below_the_stages(op_name, scope):
    """The client model's scopes and the master cast are read below the
    stage that holds them, which keeps its time for ``stage_seconds``."""
    assert telemetry.stage_of_op_name(op_name, telemetry.SUBSCOPES) == scope
    assert telemetry.stage_of_op_name(op_name) in telemetry.STAGES + (
        "other",)


def test_scope_seconds_on_a_compiled_model_loss():
    """A bfloat16-compute transformer's loss from float32 masters, compiled
    with its gradient: every scope below the stages shows up, and the
    split adds up to every non-container op."""
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.models import model as lm

    cfg = dataclasses.replace(reduced(get_config("smollm-135m"), d_model=32,
                                      layers=1), dtype="bfloat16")
    params = lm.init_params(jax.random.PRNGKey(0), lm.master_config(cfg))
    toks = jnp.zeros((1, 16), jnp.int32)

    def step(p):
        with jax.named_scope("fed.local_train"):
            return jax.grad(lambda q: lm.loss_fn(
                q, cfg, {"tokens": toks, "labels": toks}, ce_chunk=8))(p)

    text = jax.jit(step).lower(params).compile().as_text()
    ops = [(_profile_name(line), 1.0) for line, op, _ in _instructions(text)
           if op not in telemetry.CONTAINERS]
    got = telemetry.scope_seconds(ops, [text])
    assert set(telemetry.SUBSCOPES) <= set(got)
    assert sum(got.values()) == len(ops)


def test_round_scan_carries_every_stage(hlo):
    """The mlp-family programs: the scan carries all six stage names, and
    every gather and dot in it falls in one."""
    init, scan = hlo
    instrs = _instructions(scan)
    assert set(telemetry.STAGES) <= {stage for _, _, stage in instrs}
    heavy = [(line, stage) for line, op, stage in instrs
             if op in ("gather", "dot")]
    assert heavy
    assert [line for line, stage in heavy if stage == "other"] == []


# -- host spans in run_sweep ----------------------------------------------------


def test_run_sweep_records_its_phases_in_order(spec, hlo):
    cells = run_sweep(spec)
    assert len(cells) == len(FAMILY) * len(spec.lrs)
    spans = telemetry.records()
    call = telemetry.last("sweep.run", 1, spans)[0]
    assert call.parent is None
    kids = sorted(telemetry.children(call, spans), key=lambda s: s.start)
    assert [k.name for k in kids] == PHASES
    assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))
    assert telemetry.self_seconds(call, spans) >= 0


def test_sweep_hlo_is_memoized_per_precision(spec, hlo):
    assert sweep_hlo(spec) is hlo
    with jax.default_matmul_precision("highest"):
        other = sweep_hlo(spec)
    assert other is not hlo and len(other) == len(hlo)


# -- the benchmark's readers ------------------------------------------------------


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def _run(spec, device_ops, calls):
    work = 16 * spec.rounds
    workload = SimpleNamespace(spec=spec, work_per_step=work,
                               _ctx=contextlib.nullcontext)
    return SimpleNamespace(trace=SimpleNamespace(device_ops=device_ops),
                           units=calls * work, workload=workload)


def test_device_readers_split_the_device_time(spec, hlo):
    ops = [(_profile_name(line), 1e-6 * (i + 1))
           for i, (line, _, _) in enumerate(_instructions(hlo[1]))]
    ops.append(("%pad_add_fusion = u32[16,2]{0,1} fusion(...)", 2e-4))
    run = _run(spec, ops, calls=3)
    by_stage = telemetry.stage_seconds(ops, hlo)
    total = 0.0
    for name, stages in DEVICE_READERS.items():
        value = _reader(name)(run)
        assert value == pytest.approx(
            1e6 * sum(by_stage.get(s, 0.0) for s in stages) / run.units)
        assert value > 0
        total += value
    assert total == pytest.approx(1e6 * sum(by_stage.values()) / run.units)


def test_host_reader_averages_the_last_calls_less_the_waits(spec, hlo):
    for _ in range(2):
        run_sweep(spec)
    spans = telemetry.records()
    calls = telemetry.last("sweep.run", 2, spans)
    want = [c.seconds - sum(k.seconds for k in telemetry.children(c, spans)
                            if k.name == "sweep.wait") for c in calls]
    value = _reader("host_ms_per_call.mlp")(_run(spec, [], calls=2))
    assert value == pytest.approx(1e3 * sum(want) / 2)
    assert 0 < value < 1e3 * max(c.seconds for c in calls)
    assert _reader("host_ms_per_call.mlp")(_run(spec, [], calls=0)) is None


@pytest.mark.parametrize("name", sorted(DEVICE_READERS)
                         + ["host_ms_per_call.mlp"])
def test_readers_give_nothing_without_the_programs_names(monkeypatch, spec,
                                                         name):
    """Laid over a program without ``repro.telemetry``, a reader returns
    None and does not raise."""
    import repro

    monkeypatch.delattr(repro, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert _reader(name)(_run(spec, [("%x = f32[] add()", 1.0)], 1)) is None
