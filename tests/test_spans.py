"""The scheduler's profiler spans (``repro.*``): their order and nesting
through admissions and steps, the ``repro.gc`` hook, and the spans and
the named pool-write program reaching a real profiler trace on the
CPU."""
import gc
import glob

import jax
import numpy as np
import pytest

from repro.common.config import ModelConfig
from repro.engine import DecodeEngine, EngineConfig, Request, Scheduler
from repro.engine import scheduler as S


def _engine(**kw):
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_head=16, d_ff=128,
                      vocab=256, dtype="float32", remat="none",
                      attn_block_q=32, attn_block_kv=32)
    return DecodeEngine(cfg, EngineConfig(batch=2, max_len=32, paged=True,
                                          page_size=4, n_pages=24, **kw))


def _requests(n=3):
    rng = np.random.default_rng(0)
    return [Request(rid=i, tokens=rng.integers(2, 256, (8 + 4 * i,),
                                               dtype=np.int32),
                    gen=4 + i) for i in range(n)]


def _serve(sched, reqs):
    """The serving loop: admit, then step while anything is active."""
    for r in reqs:
        sched.submit(r)
    admitted = []
    while sched.pending or sched.n_active:
        admitted.append(sched.admit())
        if sched.n_active:
            sched.step()
    return admitted


class _Recorder:
    """Stands in for ``TraceAnnotation``: logs each enter and exit."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def _tree(log):
    """Nested ``[name, children]`` from an enter/exit log."""
    root = ["", []]
    stack = [root]
    for kind, name in log:
        if kind == "enter":
            node = [name, []]
            stack[-1][1].append(node)
            stack.append(node)
        else:
            assert stack.pop()[0] == name, "spans must nest"
    assert len(stack) == 1, "every span closes"
    return root[1]


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(S, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    return _Recorder


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["whole-prompt", "chunked"])
def test_step_and_admit_spans_nest_in_order(recorded, chunked):
    kw = dict(chunked_prefill=True, chunk_tokens=8) if chunked else {}
    sched = Scheduler(_engine(**kw))
    admitted = _serve(sched, _requests())
    log = [e for e in recorded.log if e[1] != "repro.gc"]
    top = _tree(log)
    assert {n for n, _ in top} == {"repro.admit", "repro.step"}
    steps = [kids for n, kids in top if n == "repro.step"]
    assert len(steps) == sched.stats["steps"] > 0
    promoted = 0
    for kids in steps:
        assert [n for n, _ in kids] == ["repro.step.prepare",
                                        "repro.step.launch",
                                        "repro.step.sync",
                                        "repro.step.retire"]
        launch = kids[1][1]
        assert {n for n, _ in launch} <= {"repro.promote.sync"}
        promoted += len(launch)
    admits = [kids for n, kids in top if n == "repro.admit"]
    assert len(admits) == len(admitted)
    for kids, n_admitted in zip(admits, admitted):
        assert [n for n, _ in kids] == ["repro.admit.request"] * n_admitted
        for _, inner in kids:
            want = [] if chunked else [["repro.admit.sync", []]]
            assert inner == want
    assert sum(admitted) == 3
    # chunked: each final chunk's guard and first-token read
    assert promoted == (2 * 3 if chunked else 0)


def test_gc_span_opens_and_closes_once_per_collection(recorded):
    Scheduler(_engine())
    Scheduler(_engine())
    assert gc.callbacks.count(S._gc_callback) == 1
    recorded.log = []
    gc.collect(0)
    gc.collect(1)
    assert recorded.log == []
    gc.collect()
    assert recorded.log == [("enter", "repro.gc"), ("exit", "repro.gc")]


def test_spans_and_pool_write_reach_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    sched = Scheduler(_engine())
    reqs = _requests()
    _serve(sched, reqs[:1])                 # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        _serve(sched, reqs[1:])
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    spans, modules = [], set()
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
                modules.update(v for k, v in ev.stats if k == "hlo_module")
    names = [n for n, _, _ in spans]
    assert names.count("repro.admit.request") == 2
    assert names.count("repro.admit.sync") == 2
    n_steps = names.count("repro.step")
    assert n_steps > 0
    for phase in ("prepare", "launch", "sync", "retire"):
        assert names.count(f"repro.step.{phase}") == n_steps
    steps = [(a, b) for n, a, b in spans if n == "repro.step"]
    for n, a, b in spans:
        if n == "repro.step.sync":
            assert sum(s <= a and b <= e for s, e in steps) == 1
    assert "jit_write_prefill_step" in modules
    assert "jit_sharded_serve_step" in modules


def test_serve_profile_dir_captures_the_spans(tmp_path, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.profiler import ProfileData

    from repro.launch import serve
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    before = jax.config.jax_compilation_cache_dir
    try:
        serve.main(["--arch", "qwen1.5-0.5b", "--reduce", "smoke",
                    "--batch", "2", "--prompt-len", "8", "--gen", "3",
                    "--stream", "2", "--page-size", "4",
                    "--profile-dir", str(tmp_path / "profile")])
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    (path,) = glob.glob(f"{tmp_path}/profile/**/*.xplane.pb",
                        recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert {"repro.step", "repro.step.sync",
            "repro.admit.request"} <= names
