"""The Moonlight-16B-A3B training cell: its configuration file against the
program's architecture, the launcher's first steps against
``reference/moonlight.py`` at the smoke size on the CPU, the bfloat16
control, the model FLOP count and the expert-load reader."""

import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness as H  # noqa: E402

CELL = "moonlight16b-train-monitored"

#: the share at the program's smoke size (``moonlight-16b-a3b-ep8
#: --smoke``): 2 of 16 experts held, 4 a token, a dense layer and 2 MoE
MOON_SMOKE = dict(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=4, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  intermediate_size=96, moe_intermediate_size=32,
                  n_routed_experts=2, router_experts=16,
                  num_experts_per_tok=4, n_shared_experts=2,
                  num_hidden_layers=3, first_k_dense_replace=1,
                  vocab_size=256)

#: model FLOPs of one step of the cell's share at batch 2 x seq 4096
MOONLIGHT_FLOPS_PER_STEP = 21_585_431_887_872

#: The launcher against the reference on the CPU, where both compute in
#: float32 and differ in the order of their sums alone (the program sorts
#: tokens by expert, the reference weighs every held expert densely):
#: 1e-5 of a loss and 1e-4 of a leaf's norm are some hundred roundings.
TOLERANCE = {"loss_gap": 1e-5, "grad_gap": 1e-4, "update_gap": 1e-4}


@pytest.fixture(autouse=True)
def _own_compile_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def small_cell(telemetry: bool) -> H.Cell:
    cell = H.resolve_cell(CELL)
    cell.config["launcher_args"] = ["--smoke", "--remat"]
    cell.config["model"] = dict(cell.config["model"], **MOON_SMOKE)
    cell.traffic.update(batch=2, seq=32, telemetry=telemetry)
    return cell


def test_the_configuration_is_the_programs_architecture_cut_as_stated():
    """The file's model group is the published config at the share's
    values (also at the top level, where the catalog keys stand), its
    cuts are the ones ``BENCHMARK.json`` lists, and the program's
    architecture of that name has every one of its sizes."""
    from repro.configs.base import get_config

    cfg = json.loads((BENCH / "configs" / "moonlight16b-train.json")
                     .read_text())
    m = cfg["model"]
    for k, v in m.items():
        if k not in ("router_experts", "expert_offset"):
            assert cfg[k] == v, k
    entry = {c["name"]: c for c in H.load_benchmark()["configs"]}[
        "moonlight16b-train"]
    assert sorted(cfg["published"]) == sorted(entry["reduced"])
    assert cfg["published"] == {"n_routed_experts": 64,
                                "num_hidden_layers": 27,
                                "vocab_size": 163840}
    a = get_config(cfg["arch"])
    assert (a.d_model, a.n_heads, a.n_kv_heads, a.d_ff, a.d_expert) == (
        m["hidden_size"], m["num_attention_heads"],
        m["num_key_value_heads"], m["intermediate_size"],
        m["moe_intermediate_size"])
    assert (a.kv_lora_rank, a.head_dim, a.qk_rope_dim, a.v_head_dim) == (
        m["kv_lora_rank"], m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
        m["qk_rope_head_dim"], m["v_head_dim"])
    assert (a.n_experts, a.experts_held, a.expert_offset, a.top_k,
            a.n_shared_experts) == (
        m["router_experts"], m["n_routed_experts"], m["expert_offset"],
        m["num_experts_per_tok"], m["n_shared_experts"])
    assert (a.n_layers, a.first_dense, a.vocab, a.tie_embeddings) == (
        m["num_hidden_layers"], m["first_k_dense_replace"],
        m["vocab_size"], m["tie_word_embeddings"])
    assert (a.router_score, a.router_scale, a.rope_theta, a.rms_eps) == (
        m["scoring_func"], m["routed_scaling_factor"], m["rope_theta"],
        m["rms_norm_eps"])
    assert m["q_lora_rank"] is None and m["norm_topk_prob"]
    assert (m["n_group"], m["topk_group"]) == (1, 1)


def test_reference_weights_have_the_programs_layout():
    import jax
    from reference import moonlight
    from repro.configs.base import get_config
    from repro.models import transformer as T

    m = json.loads((BENCH / "configs" / "moonlight16b-train.json")
                   .read_text())["model"]
    arch = get_config("moonlight-16b-a3b-ep8")
    want = jax.eval_shape(lambda: T.init_model(
        arch, jax.random.PRNGKey(0), dtype=jax.numpy.float32))
    got = jax.eval_shape(lambda: moonlight.init_params(
        moonlight._Frozen(m), jax.random.PRNGKey(0)))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert sum(x.size for x in jax.tree.leaves(got)) \
        == arch.param_count() + arch.d_model


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    """The launcher's first three steps (``--smoke --remat``) against the
    reference, and the reference in bfloat16 and on half the batch in
    the program's place (``bench/drivers/train.py``'s ``calibrate``)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("jc")))
        cell = small_cell(telemetry=False)
        return H.driver_for(cell).calibrate(cell, 2 ** 31 + 99)


def test_the_launchers_steps_match_the_reference(readings):
    for k, tol in TOLERANCE.items():
        assert readings["program"][k] <= tol, (k, readings["program"])


@pytest.mark.parametrize("side", ["control", "half_batch"])
def test_the_bfloat16_control_and_half_a_batch_fail(readings, side):
    assert any(readings[side][k] > tol for k, tol in TOLERANCE.items()), \
        readings[side]


def test_train_flops_per_step_is_pinned():
    """6 per multiply-accumulate weight a token touches, with 0.75 of a
    held expert a token, plus scores and weighted sum per layer."""
    cell = H.resolve_cell(CELL)
    m = cell.config["model"]
    got = cell.reference.train_flops_per_step(m, 2, 4096)
    assert got == MOONLIGHT_FLOPS_PER_STEP
    attn = (2048 * 16 * 192 + 2048 * (512 + 64) + 512 * 16 * 256
            + 16 * 128 * 2048)
    moe = 2048 * 64 + (6 * 8 / 64 + 2) * 3 * 2048 * 1408
    macs = 6 * attn + 3 * 2048 * 11264 + 5 * moe + 2048 * 20480
    assert got == (6 * macs + 6 * 6 * 4096 * 16 * 320) * 2 * 4096


def test_a_monitored_run_is_correct_and_reads_the_expert_load():
    from repro.core import spans

    cell = small_cell(telemetry=True)
    ctx = H.Context(cell, 2 ** 31 + 5, 1.0, False, Path("unused"),
                    {"bf16_flops_per_s": 197e12}, time.perf_counter())
    outcome = H.driver_for(cell).run(ctx)
    assert outcome.checks and all(c.ok for c in outcome.checks), \
        outcome.checks
    syncs = spans.within("train.sync", *outcome.window)
    # every step counts its held experts' pairs; at this size the tokens
    # of a step can all pass the two held experts by
    assert syncs and all({"expert_tokens", "expert_load_max"} <= set(s.counts)
                         for s in syncs)
    assert sum(s.counts["expert_tokens"] for s in syncs) > 0
    ctx.trace = True
    ctx.trace_summary = {"busy_s": 0.9, "window_s": 1.0, "idle_share": 0.1,
                         "device_ops": [], "idle_gaps": []}
    metrics = H.metric_values(cell, ctx, outcome)
    assert metrics["expert_load_skew.train"]["value"] >= 1.0
    assert 0 < metrics["train_mfu"]["value"]
    assert "telemetry_ms_per_window" in metrics


def test_expert_load_skew_is_the_busiest_over_the_mean_expert():
    from repro.core import spans

    cell = H.resolve_cell(CELL)
    reader = H.reader_for(cell, "expert_load_skew.train")
    t0 = spans.clock()
    for most in (1536, 768):
        with spans.span("train.sync"):
            spans.count("expert_tokens", 5 * 8 * 768)
            spans.count("expert_load_max", most)
    with spans.span("train.sync"):
        pass                               # a step of a dense model
    out = H.Outcome(end_to_end={}, checks=[], attempted=2, failed=0,
                    window=(t0, spans.clock()))
    run = H.Run(cell, H.Recorder(), out, None, None)
    # 5 MoE layers x 8 held experts: a mean of 768 a held expert
    assert reader.read(run) == pytest.approx((2.0 + 1.0) / 2)
    empty = H.Outcome(end_to_end={}, checks=[], attempted=0, failed=0,
                      window=(spans.clock(), spans.clock()))
    assert reader.read(H.Run(cell, H.Recorder(), empty, None, None)) is None
