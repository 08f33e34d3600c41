"""The model arithmetic kept with the benchmark: parameter and FLOP
counts from the configuration file, the peaks table, and the weights the
benchmark makes against the program's own layout."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import flops  # noqa: E402
import harness as H  # noqa: E402

MODEL = json.loads((BENCH / "configs" / "smollm135m-train.json")
                   .read_text())["model"]


def test_smollm_135m_parameter_count():
    c = flops.param_counts(MODEL)
    # embedding and blocks, as the program's ArchConfig.param_count counts
    assert c["embedding"] + c["blocks"] == 134_514_432
    # with the final norm's scales: every parameter of the model
    assert sum(c.values()) == 134_515_008
    assert c["lm_head"] == 0


def test_train_flops_count_attention_and_every_matmul_weight():
    seq = 512
    n_mm = flops.matmul_params(MODEL)
    assert n_mm == 134_514_432 - 30 * 2 * 576
    per_token = flops.train_flops_per_token(MODEL, seq)
    assert per_token == 6 * n_mm + 12 * 30 * 576 * seq
    assert flops.train_flops_per_step(MODEL, 8, seq) == per_token * 8 * seq


def test_peaks_table_is_keyed_by_device_kind():
    p = H.load_peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(H.BenchError):
        H.load_peak("TPU v9 imaginary")
    with pytest.raises(H.BenchError):
        H.load_peak("cpu")


def test_benchmark_weights_have_the_programs_layout():
    import jax
    from reference import lm
    from repro.configs.base import get_config
    from repro.models import transformer as T

    want = jax.eval_shape(lambda: T.init_model(
        get_config("smollm-135m"), jax.random.PRNGKey(0),
        dtype=jax.numpy.float32))
    got = jax.eval_shape(lambda: lm.init_params(
        lm._Frozen(MODEL), jax.random.PRNGKey(0)))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    n = sum(x.size for x in jax.tree.leaves(got))
    assert n == sum(flops.param_counts(MODEL).values())


def test_benchmark_tokens_are_the_programs_stream():
    from reference import lm
    from repro.data.pipeline import DataConfig, TokenPipeline

    pipe = TokenPipeline(DataConfig(vocab=49152, batch=8, seq=512,
                                    seed=2 ** 31 + 7))
    for step in (0, 1, 5):
        assert (lm.batch(2 ** 31 + 7, step, 8, 512, 49152)
                == pipe.batch_at(step)).all()
    rows = lm.batch(3, 0, 8, 512, 49152)
    assert len({r.tobytes() for r in rows}) == 8


def test_the_step_probe_compiles_one_program_for_every_seed():
    """The readings taken off the launcher's state take the seed's key as
    an argument: two seeds lower to the same program, so a new seed finds
    it in the compile cache and set-up does not compile."""
    import jax
    from reference import lm

    drv = H.load_module(BENCH / "drivers" / "train.py")
    small = dict(MODEL, hidden_size=48, intermediate_size=96,
                 num_hidden_layers=2, num_attention_heads=3,
                 num_key_value_heads=1, head_dim=16, vocab_size=256)
    frozen = lm._Frozen(small)
    params = lm.make_params_from_seed(small, 1)
    texts = [drv._change_norms.lower(lm, frozen, params,
                                     jax.random.PRNGKey(seed)).as_text()
             for seed in (5, 2 ** 31 + 77)]
    assert texts[0] == texts[1]
    # the seed's own weights have not changed, to float32 rounding
    norms = drv._change_norms(lm, frozen, params, jax.random.PRNGKey(1))
    assert float(jax.numpy.max(norms)) < 1e-5 * float(
        jax.numpy.min(lm.leaf_norms(params)))
