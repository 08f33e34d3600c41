"""End-to-end system behaviour: training convergence, crash-resume,
straggler mitigation loop."""

import numpy as np
import pytest

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def test_training_loss_decreases(tmp_path):
    """Memorisation check: a fixed uniform batch must be learnable well
    below the uniform-entropy floor."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.launch.steps import CellPlan, make_train_step
    from repro.models import transformer as T
    from repro.optim import adamw
    cfg = get_config("smollm-135m", smoke=True)
    params = T.init_model(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=2000,
                                min_lr_frac=1.0)
    opt = adamw.init_state(params, opt_cfg)
    step = jax.jit(make_train_step(
        cfg, CellPlan(grad_accum=1, remat=False,
                      param_dtype=jnp.float32), opt_cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                cfg.vocab)
    losses = []
    for _ in range(40):
        params, opt, loss, _ = step(params, opt, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def test_launcher_stream_is_learnable():
    """The launcher's own token stream has a law to fit: over 24 steps the
    loss falls.  A uniform stream cannot fall below ln(vocab), so a
    training run on it could not show that the loop learns."""
    from repro.launch.train import main
    losses = main(["--arch", "smollm-135m", "--smoke", "--steps", "24",
                   "--batch", "8", "--seq", "128", "--lr", "1e-3",
                   "--log-every", "100"])
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] - 0.1, (losses[0], losses[-1])


def test_token_stream_is_zipf_like():
    from repro.data.pipeline import DataConfig, TokenPipeline
    vocab = 49152
    toks = TokenPipeline(DataConfig(vocab=vocab, batch=16, seq=1024)) \
        .batch_at(0).ravel()
    assert toks.min() >= 0 and toks.max() < vocab
    counts = np.bincount(toks, minlength=vocab)
    p = counts[counts > 0] / toks.size
    entropy = float(-(p * np.log(p)).sum())
    assert entropy < np.log(vocab) - 2.0          # far from uniform
    assert counts[:16].sum() > counts[-4096:].sum()   # head outweighs tail


def test_bench_harness_fails_when_a_bench_raises(monkeypatch, capsys):
    """A bench that raises still prints its row, and the harness exits
    nonzero: a broken phase cannot pass as a run."""
    import importlib.util
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    spec = importlib.util.spec_from_file_location(
        "bench_run", root / "benchmarks" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    import benchmarks.paper as paper
    import repro.compile_cache as cc

    def bench_boom():
        raise RuntimeError("boom")

    monkeypatch.setattr(paper, "ALL", [bench_boom])
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    monkeypatch.setenv("BENCH_ONLY", "bench_boom")
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (None, 0)
    assert "bench_boom,0,EXCEPTION:RuntimeError:boom" in \
        capsys.readouterr().out


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and the code sets no other
    directory; without it the cache is one fixed, git-ignored directory
    of the checkout."""
    import jax
    from pathlib import Path

    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = Path(__file__).resolve().parents[1]
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_crash_resume_continuity(tmp_path):
    """Training N steps straight equals training with a crash+resume in the
    middle (same data stream, same final loss)."""
    from repro.launch.train import main
    ck1 = str(tmp_path / "a")
    ck2 = str(tmp_path / "b")
    full = main(["--arch", "smollm-135m", "--smoke", "--steps", "20",
                 "--batch", "2", "--seq", "32", "--ckpt-dir", ck1,
                 "--ckpt-every", "10", "--log-every", "100"])
    main(["--arch", "smollm-135m", "--smoke", "--steps", "10",
          "--batch", "2", "--seq", "32", "--ckpt-dir", ck2,
          "--ckpt-every", "10", "--log-every", "100"])
    resumed = main(["--arch", "smollm-135m", "--smoke", "--steps", "20",
                    "--batch", "2", "--seq", "32", "--ckpt-dir", ck2,
                    "--resume", "--log-every", "100"])
    assert resumed[-1] == pytest.approx(full[-1], rel=1e-4)


def test_mitigation_policy_actions():
    from repro.distributed.telemetry import MitigationPolicy, PodVerdict
    pol = MitigationPolicy(n_shards=4)
    assert pol.plan(PodVerdict(False, None, None, 0, "none"))["action"] \
        == "none"
    plan = pol.plan(PodVerdict(True, "core", 5, 4.0, "rebalance"))
    assert plan["action"] == "rebalance"
    w = plan["shard_weights"]
    assert w.sum() == pytest.approx(1.0) and w[1] < w[0]
    plan = pol.plan(PodVerdict(True, "core", 5, 12.0,
                               "exclude_and_restart"))
    assert plan["action"] == "exclude_and_restart"
    assert plan["exclude"] == ("core", 5)


def test_pod_link_failure_detected():
    from repro.core.failures import FailSlow
    from repro.distributed.telemetry import (PodDetector, PodSimulator,
                                             PodTelemetryConfig)
    cfg = PodTelemetryConfig(mesh_w=4, mesh_h=4)
    pod = PodSimulator(cfg, step_flops=5e12, collective_bytes=4e9, seed=1)
    pod.inject(FailSlow("link", 11, 0.0, 1e9, 8.0))
    det = PodDetector(cfg)
    v = det.analyse(pod.run_steps(48))
    assert v.flagged and v.kind == "link" and v.location == 11
