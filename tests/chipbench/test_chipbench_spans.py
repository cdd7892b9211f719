"""The program's spans beside the device trace (``chipbench/spans.py``):
known answers on a synthetic trace, the spans read from a CPU profiler
trace, and a small trace recorded on a TPU v5e chip
(``data/trace_fixture_spans.json``: a few steps of the
``qwen05b.chat.r80`` cell around one admission, cut from a traced
window)."""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import measure, spans  # noqa: E402
from chipbench.trace import Span, Trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _synthetic():
    """Two steps with an admission between them, then a wait; a
    generation-2 collection inside the second step's retire."""
    host = [Span("bench.window", 0.0, 10.0),
            Span("bench.step", 0.0, 3.0), Span("repro.step", 0.0, 3.0),
            Span("repro.step.prepare", 0.0, 0.2),
            Span("repro.step.launch", 0.2, 0.5),
            Span("repro.step.sync", 0.5, 2.5),
            Span("repro.step.retire", 2.5, 3.0),
            Span("bench.admit", 3.0, 5.0), Span("repro.admit", 3.0, 4.95),
            Span("repro.admit.request", 3.1, 4.9),
            Span("repro.admit.sync", 4.0, 4.9),
            Span("bench.step", 5.0, 8.0), Span("repro.step", 5.0, 8.0),
            Span("repro.step.prepare", 5.0, 5.5),
            Span("repro.step.launch", 5.5, 6.0),
            Span("repro.step.sync", 6.0, 7.5),
            Span("repro.step.retire", 7.5, 8.0),
            Span("repro.gc", 7.6, 7.9),
            Span("bench.wait", 8.0, 10.0),
            # outside the window: not read
            Span("repro.step", 10.5, 11.0),
            Span("repro.admit.request", 10.6, 10.9)]
    modules = [Span("jit_sharded_serve_step(1)", 0.3, 2.4),
               Span("jit__pick(2)", 2.4, 2.5),
               Span("jit_prefill_step(3)", 3.2, 3.8),
               Span("jit_write_prefill_step(4)", 3.8, 4.6),
               Span("jit_sharded_serve_step(1)", 5.6, 7.3),
               Span("jit__pick(2)", 7.3, 7.5)]
    ops = [Span(f"fusion.{i}", m.start, m.end)
           for i, m in enumerate(modules)]
    return Trace(window=(0.0, 10.0),
                 chips=[{"modules": modules, "ops": ops}], host=host)


def test_step_host_time_leaves_out_the_syncs():
    # (3.0 - 2.0) and (3.0 - 1.5) seconds
    assert spans.step_host_ms(_synthetic()) == pytest.approx(1250.0)


def test_admission_and_pool_write_percentiles():
    t = _synthetic()
    assert spans.admit_ms_p95(t) == pytest.approx(1800.0)
    assert spans.prefill_write_ms_p95(t) == pytest.approx(800.0)


def test_readers_find_nothing_without_spans():
    t = _synthetic()
    t.host = [s for s in t.host if s.name.startswith("bench.")]
    t.chips[0]["modules"] = [m for m in t.chips[0]["modules"]
                             if "write" not in m.name]
    assert spans.step_host_ms(t) is None
    assert spans.admit_ms_p95(t) is None
    assert spans.prefill_write_ms_p95(t) is None
    assert spans.step_host_ms(None) is None
    assert spans.prefill_write_ms_p95(None) is None


def test_idle_time_split_by_innermost_span():
    t = _synthetic()
    idle = spans.idle_by_span(t)
    want = {"repro.step.prepare": 0.7, "repro.step.launch": 0.2,
            "repro.step.retire": 0.7, "repro.admit": 0.15,
            "repro.admit.request": 0.1, "repro.admit.sync": 0.3,
            "bench.admit": 0.05, "repro.gc": 0.3, "bench.wait": 2.0}
    assert set(idle) == set(want)
    for k, v in want.items():
        assert idle[k] == pytest.approx(v), k
    assert sum(idle.values()) == pytest.approx(
        sum(b - a for a, b in t.idle_gaps(0)))
    r = spans.readings(t)
    assert r["idle_outside_wait_under_program"] == pytest.approx(2.45 / 2.5)
    assert r["steps"] == 2 and r["admissions"] == 1
    assert r["gc_s"] == pytest.approx(0.3)


def test_breakdown_names_gaps_by_program_phase():
    gaps = dict((n, d) for n, d in _synthetic().breakdown()["idle_gaps"])
    assert gaps["repro.step.retire"] == pytest.approx(0.7)
    assert gaps["bench.wait"] == pytest.approx(2.5)


def test_device_time_by_scope(tmp_path):
    (tmp_path / "module_0007.jit_sharded_serve_step.after_optimizations.txt"
     ).write_text(
        'HloModule jit_sharded_serve_step, is_scheduled=true\n'
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(sharded_serve_step)/while/body/attn.qkv/'
        'dot_general" stack_frame_id=3}\n'
        '  ROOT %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%g, '
        'metadata={op_name="jit(sharded_serve_step)/while/body/attn.paged/'
        'pallas_call"}\n'
        '  %copy.3 = f32[8]{0} copy(%p)\n')
    names = spans.op_names_from_dump(str(tmp_path))
    assert names == {"jit_sharded_serve_step": {
        "fusion.1": "jit(sharded_serve_step)/while/body/attn.qkv/"
                    "dot_general",
        "fusion.2": "jit(sharded_serve_step)/while/body/attn.paged/"
                    "pallas_call"}}
    t = _synthetic()
    t.chips[0]["ops"] = [Span("while.1", 0.3, 2.4),
                         Span("fusion.1", 0.3, 1.0),
                         Span("fusion.2", 1.0, 2.0),
                         Span("copy.3", 2.0, 2.4),
                         Span("copy.4", 3.9, 4.5)]
    d = spans.time_by_scope(t, names["jit_sharded_serve_step"],
                            measure.DECODE_PROGRAM)
    assert d["executions"] == 2
    assert d["ms_per_execution"] == pytest.approx(
        {"attn.paged": 500.0, "attn.qkv": 350.0, spans.UNSCOPED: 200.0})
    assert d["copy_ms_per_execution"] == pytest.approx(
        {spans.UNSCOPED: 200.0})
    w = spans.time_by_scope(t, {}, spans.WRITE_PROGRAM)
    assert w["copy_ms_per_execution"] == pytest.approx(
        {spans.UNSCOPED: 600.0})


def test_clock_offset_bounds():
    # launches start 0.1 s before their programs; syncs end with picks
    lo, hi = spans.clock_offset_ms(_synthetic())
    assert lo == pytest.approx(-100.0) and hi == pytest.approx(0.0)
    late = spans.shifted(_synthetic(), -0.05)
    assert spans.clock_offset_ms(late) == pytest.approx((-50.0, 50.0))


def test_cut_keeps_an_admission_and_the_steps_around_it():
    c = spans.cut(_synthetic(), n_steps=1)
    assert c.window == (0.0, 8.0)
    assert [m.name for m in c.modules(spans.WRITE_PROGRAM)] == \
        ["jit_write_prefill_step(4)"]
    assert spans.step_host_ms(c) == pytest.approx(1250.0)
    assert Trace.from_json(json.loads(json.dumps(c.to_json()))) == c


def test_program_spans_read_from_a_cpu_trace(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("repro.step"):
            with jax.profiler.TraceAnnotation("repro.step.sync"):
                f(jnp.ones(4)).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.step"):
            pass
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    from jax.profiler import ProfileData
    got = spans.host_spans(ProfileData.from_file(path))
    assert sorted(s.name for s in got) == ["repro.step", "repro.step.sync"]
    step = next(s for s in got if s.name == "repro.step")
    sync = next(s for s in got if s.name == "repro.step.sync")
    assert step.start <= sync.start and sync.end <= step.end


@pytest.fixture(scope="module")
def recorded():
    return Trace.from_json(json.loads(
        (DATA / "trace_fixture_spans.json").read_text()))


def _inside(inner, outer):
    return any(o.start <= inner.start and inner.end <= o.end for o in outer)


def test_recorded_programs_lie_inside_their_spans(recorded):
    t = recorded
    steps = [s for s in t.host if s.name == spans.STEP]
    decode = t.modules(measure.DECODE_PROGRAM)
    assert len(steps) >= 2 and len(decode) == len(steps)
    assert all(_inside(p, steps) for p in decode)
    picks = sorted(t.modules(spans.PICK_PROGRAM), key=lambda s: s.start)
    syncs = sorted((s for s in t.host if s.name == "repro.step.sync"),
                   key=lambda s: s.start)
    assert len(picks) == len(syncs)
    for pick, sync in zip(picks, syncs):
        assert sync.end > pick.end
    writes = t.modules(spans.WRITE_PROGRAM)
    requests = [s for s in t.host if s.name == spans.ADMIT_REQUEST]
    assert writes and all(_inside(w, requests) for w in writes)


def test_recorded_trace_reads_every_span_metric(recorded):
    from types import SimpleNamespace

    from chipbench import spec
    r = spans.readings(recorded)
    for name in ("sched.step_host_ms", "sched.admit_ms_p95",
                 "engine.prefill_write_ms_p95"):
        assert r[name] is not None and r[name] > 0, name
    read = spec.load_reader(REPO, "engine.prefill_write_ms_p95.r80")
    assert read(SimpleNamespace(trace=recorded)) == \
        r["engine.prefill_write_ms_p95"]
    # the step's host part is a small share of the step
    assert r["sched.step_host_ms"] < 0.1 * r["span_ms"]["repro.step"][
        "mean_ms"]
    assert r["idle_outside_wait_under_program"] > 0.8
    lo, hi = r["clock_offset_ms"]
    assert 0 < lo <= hi < 5
