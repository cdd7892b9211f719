"""The chip benchmark's harness on the CPU at a tiny size.

Each test builds a checkout of its own under ``tmp_path``: the
benchmark's directory as committed, plus a configuration, mixes and a
metric reader added as files only, and a ``BENCHMARK.json`` that names
them.  ``run.run(..., require_tpu=False)`` then drives the whole run
(set-up, window, reference check) without looking for a chip.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(REPO))

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 12345                # larger than 32 signed bits hold

NEW_READER = '''"""Decode steps the window took (a metric added as a file only)."""


def read(rec):
    return len(rec.steps)
'''


@pytest.fixture
def root(tmp_path, monkeypatch):
    """A checkout with the tiny cells added as files only."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "jax-cache"))
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    cb = tmp_path / "chipbench"
    shutil.copy(DATA / "tiny-gqa.json", cb / "configs" / "tiny-gqa.json")
    for mix in ("tiny-sat", "tiny-open"):
        shutil.copy(DATA / f"{mix}.json", cb / "traffic" / f"{mix}.json")
    (cb / "metrics" / "tiny.decode_steps.py").write_text(NEW_READER)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-gqa", "source": "test",
                             "file": "chipbench/configs/tiny-gqa.json",
                             "reduced": [], "why": "test"})
    bench["workloads"] += [
        {"name": "tiny.sat", "config": "tiny-gqa", "traffic": "tiny-sat",
         "chips": 1, "why": "test"},
        {"name": "tiny.open", "config": "tiny-gqa",
         "traffic": "tiny-open", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny.sat" if m["name"] == "tokens_per_s"
                                  else "tiny.open")
    bench["per_layer"].append(
        {"name": "tiny.decode_steps", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "tokens_per_s", "workloads": ["tiny.sat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


# appended to a copy of ``dense.py``: every count the harness calls
# scaled by SCALE, and each call of the reference recorded
SCALED_ARCH = '''

SCALE = 1000.0
CALLS = []
_served_gaps = served_gaps


def served_gaps(weights, model, prompt, served, **kw):
    CALLS.append(len(served))
    return _served_gaps(weights, model, prompt, served, **kw)


def _scaled(count):
    return lambda *a: SCALE * count(*a)


for _name in ("decode_flops", "decode_bytes", "prefill_flops",
              "paged_decode_flops", "paged_decode_bytes"):
    globals()[_name] = _scaled(globals()[_name])
'''

DENSE_MFU_READER = '''"""The decode MFU of the window with the dense file's counts."""
import dataclasses
from pathlib import Path

from chipbench import measure, spec

DENSE = spec.load_architecture(Path(__file__).resolve().parents[2], "dense")


def read(rec):
    return measure.decode_mfu(dataclasses.replace(rec, arch=DENSE))
'''


def _run(root, cell, trace=0, **kw):
    from chipbench import run
    return run.run(root, cell, SEED, 1.0, trace, require_tpu=False,
                   peaks=PEAKS, **kw)


def test_cell_added_as_files_is_picked_up(root):
    from chipbench import spec
    cell = spec.load_cell(root, "tiny.sat")
    assert cell.config["name"] == "tiny-gqa"
    assert cell.mix["arrival"]["kind"] == "saturated"
    assert "tiny.decode_steps" in [m.name for m in cell.metrics]
    res = _run(root, "tiny.sat")
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert res["window"]["compiles_in_window"] == 0


def test_open_loop_cell_reports_tails(root):
    res = _run(root, "tiny.open")
    assert res["correct"], res["check"]
    assert {"setup_s", "ttft_p90_ms", "itl_p50_ms",
            "itl_p95_ms"} <= set(res["metrics"])
    assert res["metrics"]["ttft_p90_ms"]["value"] > 0


def test_new_metric_reader_read_in_traced_run(root, monkeypatch):
    """A per-layer metric added as a file is read in a traced run; the
    CPU has no device plane, so the recorded chip trace stands in."""
    from chipbench import trace as tr
    fixture = json.loads((DATA / "trace_fixture.json").read_text())
    monkeypatch.setattr(tr, "load_xplane",
                        lambda d: tr.Trace.from_json(fixture))
    res = _run(root, "tiny.sat", trace=1)
    assert res["metrics"]["tiny.decode_steps"]["value"] > 0
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert "device_ops" in res["breakdown"]


def test_architecture_added_as_a_file_is_picked_up(root, monkeypatch):
    """A configuration names an architecture file that exists only in
    this checkout: a copy of the dense file with its counts scaled.  The
    run checks its served tokens with that file's reference, and a
    traced run's decode MFU reads that file's counts."""
    from chipbench import spec
    from chipbench import trace as tr
    cb = root / "chipbench"
    (cb / "architectures" / "scaled.py").write_text(
        (cb / "architectures" / "dense.py").read_text() + SCALED_ARCH)
    cfg = json.loads((DATA / "tiny-gqa.json").read_text())
    cfg["architecture"] = "scaled"
    (cb / "configs" / "tiny-scaled.json").write_text(json.dumps(cfg))
    (cb / "metrics" / "tiny.dense_decode_mfu.py").write_text(
        DENSE_MFU_READER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-scaled", "source": "test",
                             "file": "chipbench/configs/tiny-scaled.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.scaled",
                               "config": "tiny-scaled",
                               "traffic": "tiny-sat", "chips": 1,
                               "why": "test"})
    for name in ("model.decode_mfu.tiny", "tiny.dense_decode_mfu"):
        bench["per_layer"].append(
            {"name": name, "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "model",
             "moves": "tokens_per_s", "workloads": ["tiny.scaled"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    loaded = {}
    load = spec.load_architecture

    def load_kept(r, name):
        loaded[name] = load(r, name)
        return loaded[name]
    monkeypatch.setattr(spec, "load_architecture", load_kept)
    fixture = json.loads((DATA / "trace_fixture.json").read_text())
    monkeypatch.setattr(tr, "load_xplane",
                        lambda d: tr.Trace.from_json(fixture))
    res = _run(root, "tiny.scaled", trace=1)
    assert res["correct"], res["check"]
    arch = loaded["scaled"]
    assert Path(arch.__file__) == cb / "architectures" / "scaled.py"
    read = res["window"]["check_readings"]
    assert len(arch.CALLS) == read["requests"] > 0
    assert sum(arch.CALLS) == read["tokens"]
    m = res["metrics"]
    assert m["model.decode_mfu.tiny"]["value"] == pytest.approx(
        arch.SCALE * m["tiny.dense_decode_mfu"]["value"], rel=1e-9)


# -- faults in the timed path: each has to make ``correct`` false ------

def _alter_token(monkeypatch):
    """A token altered where it is produced."""
    from repro.engine import scheduler as S
    orig = S.Scheduler._pick

    def pick(logits, seeds, steps, temps):
        out = orig(logits, seeds, steps, temps)
        return out.at[0].set((out[0] + 1) % 500)
    monkeypatch.setattr(S.Scheduler, "_pick", staticmethod(pick))


def _state_unchanged(monkeypatch):
    """The decode step returns its KV pool unchanged."""
    from repro.launch import steps
    orig = steps.build_decode

    def build(cfg, mesh=None):
        fn = orig(cfg, mesh)

        def step(params, batch):
            logits, _ = fn(params, batch)
            return logits, batch["cache"]
        return step
    monkeypatch.setattr(steps, "build_decode", build)


def _prefill_not_written(monkeypatch):
    """Admission writes none of the prompt's KV into its pages."""
    from repro.engine import scheduler as S
    monkeypatch.setattr(S, "write_prefill",
                        lambda cfg, cache, caches, table, **kw: cache)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _prefill_not_written])
def test_fault_in_timed_path_fails_check(root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(root, "tiny.sat")
    assert not res["correct"], res["check"]
    assert res["check"]["gap_max"]["value"] > \
        res["check"]["gap_max"]["limit"]


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen05b.chat.r80", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# -- traffic ----------------------------------------------------------

def _take(mix, seed, n, block=24):
    from chipbench import traffic
    gen = traffic.generate(mix, seed, block, 1000)
    return [next(gen) for _ in range(n)]


@pytest.mark.parametrize("mix", ["longctx-decode", "short-interactive"])
def test_traffic_same_work_for_every_seed(mix):
    m = json.loads((REPO / "chipbench" / "traffic" / f"{mix}.json")
                   .read_text())
    a, b, c = (_take(m, s, 48) for s in (SEED, SEED, 7))
    assert [x.prompt.tolist() for x in a] == [x.prompt.tolist() for x in b]
    for block in (slice(0, 24), slice(24, 48)):
        for key in (lambda x: len(x.prompt), lambda x: x.gen):
            assert sorted(map(key, a[block])) == sorted(map(key, c[block]))
    gaps = lambda xs: sorted(np.round(np.diff([0.0] + [x.due for x in xs]),
                                      9))
    assert gaps(a[:24]) == gaps(c[:24])
    assert all(m["output"]["min"] <= x.gen <= m["output"]["max"] for x in a)
    assert {len(x.prompt) for x in a} <= set(m["prompt"]["values"])


def test_open_loop_window_holds_same_requests_for_every_seed():
    """With blocks that span the window, every seed's window holds
    round(rate * seconds) requests of the same sizes, and the next
    block starts after the window closes."""
    from chipbench import traffic
    m = json.loads((REPO / "chipbench" / "traffic"
                    / "short-interactive.json").read_text())
    seconds = 51.0
    n = round(m["arrival"]["rate_per_s"] * seconds)
    runs = []
    for seed in (SEED, 7, 8):
        gen = traffic.generate(m, seed, 64, 1000, period=seconds)
        items = [next(gen) for _ in range(n + 1)]
        assert items[n - 1].due < seconds < items[n].due
        runs.append((sorted(len(x.prompt) for x in items[:n]),
                     sorted(x.gen for x in items[:n])))
    assert runs[0] == runs[1] == runs[2]


# -- BENCHMARK.json ---------------------------------------------------

def test_every_benchmark_entry_has_its_files():
    from chipbench import spec
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()
        cfg = json.loads((REPO / c["file"]).read_text())
        arch = spec.load_architecture(REPO, cfg["architecture"])
        for name in spec.INTERFACE:
            assert callable(getattr(arch, name)), (c["name"], name)
    for w in bench["workloads"]:
        assert (REPO / "chipbench" / "traffic"
                / f"{w['traffic']}.json").is_file()
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.load_reader(REPO, m["name"])), m["name"]
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e


# -- the control: the reference in float8, the precision below bf16 -----

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_the_limit(seed):
    """Twelve requests served to completion (no window, so the tokens do
    not depend on the CPU's speed), then the check's readings.  Over
    seeds 1-8 sound serving reads a widest gap of 0.099 at most, and
    the fp8 control 0.35 or more on seeds 1-7; the tiny configuration's
    limit (0.15) lies between.  The program passes, its control fails."""
    import itertools
    import jax
    from chipbench import check, serve, spec
    from repro.engine import Request
    cfg = json.loads((DATA / "tiny-gqa.json").read_text())
    mix = json.loads((DATA / "tiny-sat.json").read_text())
    arch = spec.load_architecture(REPO, cfg["architecture"])
    srv = serve.Server(arch, cfg, mix, seed, jax.devices())
    items = list(itertools.islice(srv.stream, 12))
    for it in items:
        srv.sched.submit(Request(rid=it.idx, tokens=it.prompt, gen=it.gen))
    done = srv.sched.run()
    reqs = [serve.Req(idx=it.idx, prompt=it.prompt, gen=it.gen, due=0.0,
                      tokens=done[it.idx].tokens) for it in items]
    read = check.readings(arch, srv.weights, cfg["model"], reqs,
                          control=True)
    limit = cfg["check"]["gap_max"]
    assert read["tokens"] == sum(it.gen for it in items)
    assert read["gap_max"] <= limit < read["control_gap_max"]


def test_reader_shared_by_cell_suffix(tmp_path):
    """A metric with no reader of its own is read by the file named
    without its last part; a file of its own comes first."""
    from chipbench import spec
    folder = tmp_path / "chipbench" / "metrics"
    folder.mkdir(parents=True)
    (folder / "layer.step_ms.py").write_text("def read(rec):\n    return 1\n")
    (folder / "layer.step_ms.own.py").write_text(
        "def read(rec):\n    return 2\n")
    assert spec.load_reader(tmp_path, "layer.step_ms.sat")(None) == 1
    assert spec.load_reader(tmp_path, "layer.step_ms.own")(None) == 2
    with pytest.raises(FileNotFoundError):
        spec.load_reader(tmp_path, "layer.other_ms.sat")
