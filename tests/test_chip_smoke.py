"""Rehearsals of ``chip_smoke.py`` on the CPU: its one-chip phases and
its four-chip phase (on four virtual devices) at ``reduced()`` widths,
with interpret-mode kernels; and its refusal to run without a TPU."""
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as CS  # noqa: E402
from repro.configs import get_config, reduced  # noqa: E402
from repro.models import lm  # noqa: E402

SMALL = CS.Workload(slots=2, n_requests=3, prompt_lens=(8, 12),
                    gen_range=(2, 4), page_size=4, compare_prompt=8)


@pytest.fixture(autouse=True)
def _prior_blocks(monkeypatch):
    """Block sizes from the cost-model prior, as the script runs."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config(CS.ARCH))
    return cfg, lm.init(cfg, jax.random.PRNGKey(CS.SEED))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_serve_phase_finishes_every_request(model, kv_dtype):
    cfg, params = model
    dev = jax.devices()[:1]
    res = CS.serve_phase(CS.make_engine(cfg, params, SMALL, dev,
                                        kv_dtype=kv_dtype), SMALL)
    assert res["problems"] == []
    assert res["finished"] == SMALL.n_requests
    gens = [r.gen for r in CS.requests(cfg.vocab, SMALL)]
    assert [len(res["tokens"][i]) for i in range(len(gens))] == gens


def test_compare_phase_pallas_matches_xla(model):
    cfg, params = model
    dev = jax.devices()[:1]
    pallas = CS.make_engine(cfg, params, SMALL, dev)
    xla = CS.make_engine(cfg, params, SMALL, dev, kernel_impl="xla")
    res = CS.compare_phase(pallas, xla, SMALL)
    assert res["problems"] == []
    for r in res["logits"].values():
        assert r["greedy_agree"] and r["rel"] < 1e-4, r
    # the decode step the script inspects compiles (on the CPU its
    # kernels are interpreted, so it holds no tpu_custom_call)
    assert "HloModule" in CS.decode_hlo(pallas)


def test_one_chip_run_on_cpu(model, capsys):
    """The whole one-chip run: every check passes but the Mosaic one —
    on the CPU the kernels are interpreted, so no tpu_custom_call."""
    cfg, params = model
    problems = CS.run_one_chip(cfg, params, SMALL, jax.devices()[:1],
                               CS.CompileMeter())
    assert problems == [f"{kv}: no tpu_custom_call in the compiled "
                        "decode step" for kv in ("bf16", "int8")]
    out = capsys.readouterr().out
    assert "serve int8 pools: 3/3 FINISHED" in out
    assert "pallas vs xla: greedy streams identical in" in out


def test_four_chip_phase_on_virtual_devices():
    body = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, ".")
        import jax
        import chip_smoke as CS
        from repro.configs import get_config, reduced
        from repro.models import lm
        cfg = reduced(get_config(CS.ARCH))
        params = lm.init(cfg, jax.random.PRNGKey(CS.SEED))
        w = CS.Workload(slots=2, n_requests=3, prompt_lens=(8, 12),
                        gen_range=(2, 4), page_size=4, compare_prompt=8)
        res = CS.four_chip_phase(cfg, params, w, jax.devices())
        assert len(jax.devices()) == 4
        assert res["problems"] == [], res["problems"]
        assert res["diverge_at"] == [None] * 3, res
        assert CS.run_four_chips(cfg, params, w, jax.devices(),
                                 CS.CompileMeter()) == []
        print("ok")
    """)
    r = subprocess.run([sys.executable, "-c", body], cwd=ROOT,
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu", "REPRO_AUTOTUNE": "0"})
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.strip().endswith("ok")


def _run_script(cwd):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_script_refuses_to_run_without_a_tpu():
    r = _run_script(ROOT)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run_script(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_divergence_marks_first_differing_token():
    want = {0: [1, 2, 3], 1: [4, 5], 2: [6, 7, 8]}
    got = {0: [1, 2, 3], 1: [4, 9], 2: [6, 7]}
    assert CS.divergence(want, got) == [None, 1, 2]


def test_requests_are_seeded():
    a = CS.requests(512, SMALL)
    b = CS.requests(512, SMALL)
    assert [r.gen for r in a] == [r.gen for r in b]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.tokens, y.tokens)
        assert len(x.tokens) in SMALL.prompt_lens
