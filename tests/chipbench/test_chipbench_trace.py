"""Trace -> metrics: the reductions on a synthetic trace with known
answers, and on a small trace recorded on a TPU v5e chip
(``data/trace_fixture.json``: a few decode steps of the
``qwen05b.longctx.sat`` cell, cut from a traced window)."""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import measure  # noqa: E402
from chipbench.trace import Span, Trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _synthetic():
    ops = [Span("while.1", 0.0, 2.0),
           Span("fusion.1", 0.0, 1.0),
           Span("_vwr_paged_flash_decode_jit.2", 0.5, 2.0),
           Span("fusion.1", 3.0, 4.0)]
    modules = [Span("jit_sharded_serve_step(7)", 0.0, 2.0),
               Span("jit_prefill_step(3)", 3.0, 4.0)]
    host = [Span("bench.window", 0.0, 5.0), Span("bench.step", 0.0, 2.1),
            Span("bench.admit", 2.1, 4.2), Span("bench.wait", 4.2, 5.0)]
    return Trace(window=(0.0, 5.0),
                 chips=[{"modules": modules, "ops": ops}], host=host)


def test_busy_is_the_union_of_operations():
    t = _synthetic()
    assert t.busy(0) == [(0.0, 2.0), (3.0, 4.0)]
    assert t.busy_s() == pytest.approx(3.0)
    assert t.window_s == 5.0
    assert t.idle_gaps(0) == [(2.0, 3.0), (4.0, 5.0)]


def test_breakdown_names_gaps_by_host_activity():
    b = _synthetic().breakdown()
    assert b["device_ops"] == [["fusion", 2.0],
                               ["_vwr_paged_flash_decode_jit", 1.5]]
    assert sorted(b["idle_gaps"]) == [["bench.admit", 1.0],
                                      ["bench.wait", 1.0]]


def test_programs_and_kernels_by_name():
    t = _synthetic()
    dec = t.modules(measure.DECODE_PROGRAM)
    assert [m.name for m in dec] == ["jit_sharded_serve_step(7)"]
    assert len(t.modules(measure.PREFILL_PROGRAM)) == 1
    k = t.ops_in(measure.PAGED_DECODE_KERNEL, dec)
    assert [s.name for s in k] == ["_vwr_paged_flash_decode_jit.2"]
    assert t.ops_in(measure.PAGED_DECODE_KERNEL,
                    t.modules(measure.PREFILL_PROGRAM)) == []


def test_json_round_trip():
    t = _synthetic()
    u = Trace.from_json(json.loads(json.dumps(t.to_json())))
    assert u == t


@pytest.fixture(scope="module")
def recorded():
    return Trace.from_json(json.loads(
        (DATA / "trace_fixture.json").read_text()))


def test_recorded_trace_reduces(recorded):
    t = recorded
    busy = t.busy_s()
    assert 0 < busy <= t.window_s
    gaps = sum(b - a for a, b in t.idle_gaps(0))
    assert busy + gaps == pytest.approx(t.window_s, rel=1e-9)
    dec = t.modules(measure.DECODE_PROGRAM)
    assert len(dec) >= 2
    paged = t.ops_in(measure.PAGED_DECODE_KERNEL, dec)
    assert len(paged) == 24 * len(dec)          # one call per layer
    kernel_s = sum(s.dur for s in paged)
    assert 0.5 * sum(m.dur for m in dec) < kernel_s < sum(m.dur for m in dec)
    b = t.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert len(b["idle_gaps"]) <= 10
