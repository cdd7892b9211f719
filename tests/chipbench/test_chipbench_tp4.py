"""The four-chip cell's files: the granite architecture file served by
the harness on four virtual CPU devices at a tiny size, and the two
readers it adds (collective time per decode program, the paged
kernel's skew over chips) on synthetic four-chip traces with known
answers."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(REPO))

from chipbench import spec  # noqa: E402
from chipbench.trace import Span, Trace  # noqa: E402

CELL = "granite8b.tp4.longdoc.sat"
TINY = {
    "name": "tiny-granite-tp4", "architecture": "granite",
    "source": "test configuration: granite's block at CPU size",
    "reduced": [],
    "model": {
        "name": "tiny-granite", "family": "dense",
        "n_layers": 2, "d_model": 256, "n_heads": 8, "n_kv_heads": 2,
        "d_head": 128, "d_ff": 256, "vocab": 500, "qkv_bias": False,
        "tie_embeddings": True, "act": "swiglu", "rope_theta": 1e7,
        "norm_eps": 1e-5, "embedding_multiplier": 12.0,
        "attention_multiplier": 0.0078125, "residual_multiplier": 0.22,
        "logits_scaling": 16.0, "dtype": "bfloat16"},
    "deployment": {
        "chips": 4, "mesh": [1, 4], "decode_shard": "seq",
        "kernel_impl": "pallas", "slots": 4, "max_len": 64,
        "page_size": 16, "n_pages": 24, "kv_dtype": "bf16",
        "prefix_cache": False, "chunked_prefill": False,
        "bucket_tables": True},
    # a small embedding, so that 12 x the embedding does not outweigh
    # the layers and every best token is not the input token
    "weights": {"embed_std": 0.001, "bias_std": 0.1},
    # over seeds 2**31 + 7 and 11-15 the served tokens' widest gap read
    # 0 to 1.6e-5 and the fp8 control's 2.0e-4 to 4.5e-4 (logits are
    # divided by 16): L^(1/3) U^(2/3) = 8.6e-5
    "check": {"gap_max": 8e-5},
}


def test_cell_names_its_files():
    cell = spec.load_cell(REPO, CELL)
    assert cell.chips == 4
    assert cell.arch.__name__.endswith("granite")
    dep, m = cell.config["deployment"], cell.config["model"]
    assert dep["mesh"] == [1, 4] and dep["decode_shard"] == "seq"
    assert dep["n_pages"] % 4 == 0
    assert dep["max_len"] == (max(cell.mix["prompt"]["values"])
                              + cell.mix["output"]["max"])
    names = {x.name for x in cell.metrics}
    # the inter-token gaps, not the window's throughput: which long
    # prompts a seed admits in a window moves tokens_per_s by about 1%
    assert "tokens_per_s" not in names
    assert {"setup_s", "itl_p50_ms", "itl_p95_ms", "dist.collective_ms.tp4",
            "kernel.paged_decode_shard_skew.tp4",
            "model.decode_step_roofline.tp4",
            "model.decode_mfu.tp4"} <= names
    # no width is cut: the published numbers are the served ones
    pub = cell.config["published"]
    assert (m["d_model"], m["d_ff"], m["n_heads"], m["n_kv_heads"],
            m["vocab"], m["n_layers"]) == (
        pub["hidden_size"], pub["intermediate_size"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["vocab_size"], pub["num_hidden_layers"])
    for k in ("embedding_multiplier", "attention_multiplier",
              "residual_multiplier", "logits_scaling"):
        assert m[k] == pub[k], k


def test_tiny_cell_served_on_four_devices(tmp_path):
    """The whole run (set-up, window, reference check) of a tiny granite
    cell on a (1, 4) mesh of virtual CPU devices reads ``correct``, and
    the fp8 control on the same sample fails the limit."""
    root = tmp_path
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (root / "chipbench" / "configs" / "tiny-granite-tp4.json").write_text(
        json.dumps(TINY))
    shutil.copy(DATA / "tiny-sat.json",
                root / "chipbench" / "traffic" / "tiny-sat.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-granite-tp4", "source": "test",
                             "file": "chipbench/configs/"
                                     "tiny-granite-tp4.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.tp4",
                               "config": "tiny-granite-tp4",
                               "traffic": "tiny-sat", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("tokens_per_s", "itl_p50_ms", "itl_p95_ms"):
            m["workloads"].append("tiny.tp4")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import os, json\n"
        "os.environ['XLA_FLAGS']="
        "'--xla_force_host_platform_device_count=4'\n"
        "from pathlib import Path\n"
        "from chipbench import run\n"
        f"res = run.run(Path({str(root)!r}), 'tiny.tp4', 2**31 + 7, 1.0,"
        " 0, require_tpu=False, control=True,"
        " peaks={'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9})\n"
        "print('RESULT', json.dumps(res))\n")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, cwd=REPO,
        env={**os.environ, "PYTHONPATH": f"src:{REPO}",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax-cache")})
    assert r.returncode == 0, r.stderr[-4000:]
    res = json.loads(r.stdout.split("RESULT", 1)[1])
    assert res["correct"], res["check"]
    read = res["window"]["check_readings"]
    assert read["control_gap_max"] > TINY["check"]["gap_max"]
    assert res["device"]["count"] == 4
    assert res["failed"] == 0 and res["metrics"]["tokens_per_s"]["value"] > 0
    gaps = res["metrics"]
    assert 0 < gaps["itl_p50_ms"]["value"] <= gaps["itl_p95_ms"]["value"]


def _chip(kernel_s, collective_s, steps=2):
    """One device: ``steps`` decode programs of 1 s, each holding one
    paged-kernel call and collectives of ``collective_s`` in all (an
    all-reduce by GSPMD's name, a psum and a pmax by shard_map's), and
    one prefill holding an all-gather that no decode reader may count."""
    modules, ops = [], []
    for i in range(steps):
        modules.append(Span(f"jit_sharded_serve_step({i})", i, i + 1.0))
        ops.append(Span(f"_vwr_paged_flash_decode_jit.{i}", i, i + kernel_s))
        third = collective_s / 3
        for k, name in enumerate((f"all-reduce.{i}", f"psum.{i}",
                                  f"pmax.{i}")):
            t = i + 0.5 + k * third
            ops.append(Span(name, t, t + third))
        ops.append(Span(f"fusion.{i}", i + 0.9, i + 0.95))
    modules.append(Span("jit_prefill_step(9)", steps, steps + 1.0))
    ops.append(Span("all-gather.9", steps, steps + 0.5))
    return {"modules": modules, "ops": ops}


def _rec(chips):
    class Rec:
        pass
    rec = Rec()
    rec.trace = Trace(window=(0.0, 10.0), chips=chips,
                      host=[Span("bench.window", 0.0, 10.0)])
    return rec


def test_collective_time_per_decode_program():
    read = spec.load_reader(REPO, "dist.collective_ms.tp4")
    rec = _rec([_chip(0.2, 0.1), _chip(0.4, 0.3)])
    assert read(rec) == pytest.approx(100.0)      # chip 0: 0.1 s a program
    assert read(_rec([{"modules": [], "ops": []}])) is None
    rec.trace = None
    assert read(rec) is None


def test_paged_kernel_skew_over_chips():
    read = spec.load_reader(REPO, "kernel.paged_decode_shard_skew.tp4")
    balanced = _rec([_chip(0.2, 0.1) for _ in range(4)])
    assert read(balanced) == pytest.approx(1.0)
    skewed = _rec([_chip(0.3, 0.1), _chip(0.3, 0.1), _chip(0.3, 0.1),
                   _chip(0.1, 0.1)])
    assert read(skewed) == pytest.approx(0.3 / 0.25)
    assert read(_rec([_chip(0.2, 0.1)])) is None    # one chip: no skew


@pytest.mark.parametrize("name", ["model.decode_step_roofline.tp4",
                                  "model.decode_mfu.tp4",
                                  "kernel.paged_decode_roofline.tp4"])
def test_tp4_model_readers_count_granite_over_four_chips(name):
    """The cell's shares of a peak read granite's counts for the
    window's steps and divide by the four chips' time: on a synthetic
    trace, each equals the share worked out from the counts."""
    from chipbench import costs
    from chipbench.serve import Step
    cell = spec.load_cell(REPO, CELL)
    m, arch = cell.config["model"], cell.arch
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    rec = _rec([_chip(0.2, 0.1) for _ in range(4)])
    rec.config, rec.arch, rec.peaks = cell.config, arch, peaks
    rec.device = {"count": 4}
    rec.t0, rec.t1 = 0.0, 10.0
    rec.steps = [Step(0.1, 0.9, 16, 16 * 3770), Step(1.1, 1.9, 15, 50000),
                 Step(9.5, 10.5, 16, 60000)]      # the last is outside
    inside = rec.steps[:2]
    if name.startswith("model.decode_mfu"):
        want = 100 * sum(arch.decode_flops(m, s.n_active, s.ctx)
                         for s in inside) / (2.0 * 197e12 * 4)
    else:
        flops, nbytes, busy = (
            (arch.decode_flops, arch.decode_bytes, 2.0)
            if name.startswith("model.") else
            (arch.paged_decode_flops, arch.paged_decode_bytes, 0.4))
        want = 100 * sum(costs.roofline_s(flops(m, s.n_active, s.ctx),
                                          nbytes(m, s.n_active, s.ctx),
                                          peaks)
                         for s in inside) / (4 * busy)
    got = spec.load_reader(REPO, name)(rec)
    assert got == pytest.approx(want, rel=1e-12) and 0 < got < 100
