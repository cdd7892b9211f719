"""What the harness takes from a configuration's architecture file
(``chipbench/architectures/<name>.py``): it is found by the name the
configuration gives, it defines the whole interface, and the dense file
draws the same weights and gives the same counts as the code it
replaced.

The pinned values were computed on the commit before the architecture
files existed, where ``chipbench/weights.py`` drew the weights and
``chipbench/costs.py`` held the dense counts: ``WEIGHT_SHA256`` is
``_digest`` of that ``weights.make`` for the same configuration and key,
and ``READINGS`` is each per-layer reader of that tree on the record
that ``_fixture_record`` builds.  Moving the code moves neither."""
import dataclasses
import hashlib
import json
import shutil
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(REPO))

from chipbench import serve, spec  # noqa: E402
from chipbench.trace import Trace  # noqa: E402

dense = spec.load_architecture(REPO, "dense")


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _tiny(shape):
    cfg = json.loads((DATA / "tiny-gqa.json").read_text())
    if shape == "untied":
        cfg["model"].update(qkv_bias=False, tie_embeddings=False,
                            n_kv_heads=1, dtype="float32")
    return cfg


WEIGHT_SHA256 = {
    # bf16, q/k/v biases, tied head
    "tiny-gqa": "8355df4405385b87d83ec7593d9342be"
                "2c6bf560bb2caac7821512bd2f828be7",
    # float32, no biases, untied head, one KV head
    "untied": "22a38106718c2aba24c6703830078453"
              "aec7b12876b7b8ed89fb7950f8713b81",
}


@pytest.mark.parametrize("shape", sorted(WEIGHT_SHA256))
def test_same_key_draws_the_same_weights(shape):
    w = dense.make(_tiny(shape), jax.random.PRNGKey(7))
    assert _digest(w) == WEIGHT_SHA256[shape]


# -- per-layer readers on the recorded chip traces ---------------------

def _fixture_record(cell_name, fixture):
    """A record of the cell around a recorded trace: one window step per
    decode program (slots, ctx drawn by a fixed rule) and one admission
    per prefill program."""
    cell = spec.load_cell(REPO, cell_name)
    t = Trace.from_json(json.loads((DATA / fixture).read_text()))
    rec = serve.Record(cell=cell_name, config=cell.config, arch=cell.arch,
                       mix=cell.mix, seed=0, t0=0.0, t1=100.0, end=100.0,
                       peaks=spec.peaks_for("TPU v5 lite"),
                       device={"count": 1}, trace=t)
    n = len(t.modules("jit_sharded_serve_step"))
    slots = cell.config["deployment"]["slots"]
    rec.steps = [serve.Step(1.0 + i, 1.5 + i, slots - i % 3,
                            (slots - i % 3) * (1000 + 37 * i))
                 for i in range(n)]
    rec.admits = [serve.Admit(50.0 + i, 50.5 + i, [64 + 32 * i])
                  for i in range(len(t.modules("jit_prefill_step")))]
    return rec, {m.name: m for m in cell.metrics}


READINGS = {
    ("qwen05b.longctx.sat", "trace_fixture.json"): {
        "engine.decode_step_ms.sat": 1185.180801,
        "model.decode_mfu.sat": 0.010345000606859946,
        "model.decode_step_roofline.sat": 0.3396173178359854,
        "kernel.paged_decode_roofline.sat": 0.27589653945099296,
        "device.idle_share.sat": 0.23313642203375284},
    ("qwen05b.chat.r80", "trace_fixture_spans.json"): {
        "engine.decode_step_ms.r80": 117.2887073333333,
        "engine.prefill_ms_p95.r80": 2.6808848000000127,
        "model.decode_mfu.r80": 0.2807368802626759,
        "model.prefill_mfu.r80": 9.80889864946535,
        "kernel.paged_decode_roofline.r80": 11.301196609365569,
        "device.idle_share.r80": 4.850743842026128,
        "engine.prefill_write_ms_p95.r80": 25.31910925000002},
}


@pytest.mark.parametrize("cell,fixture,metric", [
    (*key, name) for key, values in READINGS.items() for name in values])
def test_reader_reads_as_before_on_recorded_trace(cell, fixture, metric):
    rec, metrics = _fixture_record(cell, fixture)
    assert metrics[metric].read(rec) == READINGS[cell, fixture][metric]


# -- configuration -> architecture --------------------------------------

def test_model_config_builds_nested_sections():
    """A ``moe`` or ``mla`` object in a configuration file's ``model``
    becomes the dataclass its ``ModelConfig`` field is typed as."""
    from repro.common.config import MLAConfig, MoEConfig
    from repro.configs import get_config, reduced
    want = reduced(get_config("deepseek-v3-671b"))
    model = json.loads(json.dumps(dataclasses.asdict(want)))
    del model["kernel_impl"]
    got = serve.model_config({"model": model}, want.kernel_impl)
    assert isinstance(got.moe, MoEConfig) and got.moe == want.moe
    assert isinstance(got.mla, MLAConfig) and got.mla == want.mla
    assert got == want


def test_configuration_without_architecture_is_refused(tmp_path):
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    path = tmp_path / "chipbench" / "configs" / "qwen1.5-0.5b.json"
    cfg = json.loads(path.read_text())
    del cfg["architecture"]
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="qwen1.5-0.5b.json"):
        spec.load_cell(tmp_path, "qwen05b.longctx.sat")


def test_architecture_file_must_define_the_interface(tmp_path):
    folder = tmp_path / "chipbench" / "architectures"
    folder.mkdir(parents=True)
    (folder / "partial.py").write_text("".join(
        f"def {n}(*a, **kw):\n    pass\n\n\n" for n in spec.INTERFACE
        if n != "paged_decode_bytes"))
    with pytest.raises(AttributeError, match="paged_decode_bytes"):
        spec.load_architecture(tmp_path, "partial")
    with pytest.raises(FileNotFoundError):
        spec.load_architecture(tmp_path, "absent")
