"""The dense architecture's float32 reference
(``chipbench/architectures/dense.py``) against the serving engine, at a
small size on the CPU: prefill written into pages, then paged decode
through the cache, teacher-forced on the same tokens, agree with the
reference's one pass to float32 rounding, on the XLA and the Pallas
path.  Two shapes of the dense decoder: grouped-query attention with
q/k/v biases and a tied head (as qwen1.5-0.5b), and without biases and
with an untied head (as granite-3-8b)."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from chipbench import serve, spec  # noqa: E402

dense = spec.load_architecture(REPO, "dense")

BASE = json.loads((Path(__file__).resolve().parent / "data"
                   / "tiny-gqa.json").read_text())
SHAPES = {
    "bias-tied": {},
    "untied": {"qkv_bias": False, "tie_embeddings": False,
               "n_kv_heads": 1},
}
P, GEN = 21, 7
# float32 on both sides; the program accumulates in another order
# (flash-decode's online softmax, page-wise attention), so they agree
# to float32 rounding compounded over two layers, not bit for bit
RTOL = 1e-4


def _config(shape):
    cfg = json.loads(json.dumps(BASE))
    cfg["model"].update(SHAPES[shape], dtype="float32")
    return cfg


def _engine_logits(config, w, prompt, impl):
    """Prefill ``prompt``, then decode teacher-forced on tokens drawn
    from the seed; logits of every position from P-1 on."""
    from repro.engine import DecodeEngine, EngineConfig
    eng = DecodeEngine(serve.model_config(config, impl), EngineConfig(
        batch=1, max_len=64, paged=True, page_size=16, n_pages=4),
        params=w)
    logits, cache = eng.prefill({"tokens": jnp.asarray(prompt)[None]})
    out = [np.asarray(logits[0])]
    forced = np.random.default_rng(1).integers(2, 500, (GEN,))
    table = eng.default_block_table()
    for i, tok in enumerate(forced[:-1]):
        logits, cache = eng.decode_step(jnp.asarray([tok], jnp.int32),
                                        P + i, cache, block_table=table)
        out.append(np.asarray(logits[0]))
    V = config["model"]["vocab"]
    return np.stack(out)[:, :V], forced


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_reference_matches_engine(shape, impl):
    config = _config(shape)
    w = dense.make(config, jax.random.PRNGKey(3))
    prompt = np.random.default_rng(0).integers(2, 500, (P,)).astype(
        np.int32)
    got, forced = _engine_logits(config, w, prompt, impl)
    seq = np.concatenate([prompt, forced[:-1]]).astype(np.int32)
    want = dense.logits(w, config["model"], seq,
                            np.arange(P - 1, P - 1 + GEN))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < RTOL, err


def test_served_gap_is_zero_for_reference_tokens():
    config = _config("bias-tied")
    w = dense.make(config, jax.random.PRNGKey(4))
    prompt = np.random.default_rng(2).integers(2, 500, (P,)).astype(
        np.int32)
    seq, served = list(prompt), []
    for _ in range(5):               # greedy decoding by the reference
        lg = dense.logits(w, config["model"], np.asarray(seq, np.int32),
                              np.asarray([len(seq) - 1]))
        served.append(int(lg[0].argmax()))
        seq.append(served[-1])
    gaps = dense.served_gaps(w, config["model"], prompt,
                                 np.asarray(served))
    assert gaps.shape == (5,) and np.all(gaps == 0)
    wrong = np.asarray(served)
    wrong[2] = (wrong[2] + 1) % 500
    assert dense.served_gaps(w, config["model"], prompt, wrong)[2] > 0
