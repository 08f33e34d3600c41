"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Every kernel call takes its ``interpret`` flag from the shared backend
resolution (``repro.kernels.resolve_interpret``): the interpreter on the
CPU, the compiled kernel on a TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from proptest import given, settings, st

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture
def interpret():
    from repro.kernels import resolve_interpret
    return resolve_interpret()


@pytest.mark.parametrize("backend,asked,expect", [
    ("cpu", None, True), ("cpu", True, True), ("cpu", False, False),
    ("tpu", None, False), ("tpu", False, False),
    ("tpu", True, RuntimeError), ("gpu", None, RuntimeError),
])
def test_interpret_resolution(monkeypatch, backend, asked, expect):
    """Interpret mode on the CPU, compiled on a TPU, never the emulator
    on a TPU, and no silent choice on any other backend."""
    from repro.kernels import resolve_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expect is RuntimeError:
        with pytest.raises(RuntimeError):
            resolve_interpret(asked)
    else:
        assert resolve_interpret(asked) is expect


# ---------------------------------------------------------------------------
# sketch_update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,m,H,L,n", [(1, 32, 2, 8, 300), (2, 64, 4, 16,
                                       700), (4, 128, 8, 32, 500)])
def test_sketch_kernel_matches_ref(d, m, H, L, n, interpret):
    from repro.core.sketch import SketchParams, split_key
    from repro.kernels.sketch_update import ops as O, ref as R
    p = SketchParams(d=d, m=m, H=H, L=L)
    rng = np.random.default_rng(d * 100 + m)
    keys = rng.integers(0, 80, size=n).astype(np.int64) * 0x9E3779B9
    lo, hi = split_key(keys)
    dur = rng.random(n).astype(np.float32)
    val = (rng.random(n) * 5).astype(np.float32)
    t = np.cumsum(rng.random(n)).astype(np.float32)
    args = tuple(jnp.asarray(x) for x in (lo, hi, dur, val, t))
    st_r = R.insert_batch(R.make_state(p), *args, H=p.H)
    st_p = O.insert(O.make_state(p), *args, params=p, impl="pallas",
                    block=128, interpret=interpret)
    for k in st_r:
        a, b = np.asarray(st_r[k]), np.asarray(st_p[k])
        if a.dtype.kind == "i":
            assert np.array_equal(a, b), k
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                       err_msg=k)


def test_sketch_kernel_matches_numpy_oracle(interpret):
    from repro.core.sketch import FailSlowSketch, SketchParams, split_key
    from repro.kernels.sketch_update import ops as O
    p = SketchParams(d=2, m=64, H=4, L=16)
    rng = np.random.default_rng(3)
    n = 400
    keys = rng.integers(0, 50, size=n).astype(np.int64) * 31337
    lo, hi = split_key(keys)
    dur = rng.random(n).astype(np.float32)
    oracle = FailSlowSketch(p)
    oracle.insert_stream(keys, dur, dur * 2, np.arange(n, dtype=float))
    st = O.insert(O.make_state(p), jnp.asarray(lo), jnp.asarray(hi),
                  jnp.asarray(dur), jnp.asarray(dur * 2),
                  jnp.asarray(np.arange(n, dtype=np.float32)),
                  params=p, impl="pallas", interpret=interpret)
    pats = {q.key & 0x7FFFFFFF: q for q in O.patterns(st)}
    exp = {int(k) & 0x7FFFFFFF: v for k, v in oracle.stage2.items()}
    assert set(pats) == set(exp)
    for k, q in pats.items():
        assert q.count == exp[k].count


@pytest.mark.parametrize("d,m,H,L,n", [
    (1, 32, 2, 8, 300),      # small tables, frequent Stage-2 FIFO evictions
    (2, 64, 4, 16, 700),
    (4, 16, 1, 4, 400),      # H=1: every record promotes; L=4: evict-heavy
    (3, 8, 2, 8, 500),       # heavy Stage-1 bucket collisions
])
def test_sketch_batched_matches_scan_ref(d, m, H, L, n):
    """The vectorized multi-record path is bit-identical to the
    sequential lax.scan reference on integer state."""
    from repro.core.sketch import SketchParams, split_key
    from repro.kernels.sketch_update import ops as O, ref as R
    p = SketchParams(d=d, m=m, H=H, L=L)
    rng = np.random.default_rng(7 * d + m)
    keys = rng.integers(0, 60, size=n).astype(np.int64) * 0x9E3779B9
    lo, hi = split_key(keys)
    dur = rng.random(n).astype(np.float32)
    val = (rng.random(n) * 5).astype(np.float32)
    t = np.cumsum(rng.random(n)).astype(np.float32)
    args = tuple(jnp.asarray(x) for x in (lo, hi, dur, val, t))
    st_r = R.insert_batch(R.make_state(p), *args, H=p.H)
    st_b = O.insert(O.make_state(p), *args, params=p, impl="batched")
    for k in st_r:
        a, b = np.asarray(st_r[k]), np.asarray(st_b[k])
        if a.dtype.kind == "i":
            assert np.array_equal(a, b), k
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6,
                                       err_msg=k)


@pytest.mark.parametrize("seed,d,m,H,L", [
    (0, 2, 64, 4, 16), (1, 1, 16, 2, 4), (2, 3, 32, 8, 8)])
def test_sketch_batched_matches_numpy_oracle(seed, d, m, H, L):
    """Algorithm-1 ground truth: random record streams through the numpy
    oracle vs the vectorized batch path — identical Stage-1 tables and
    identical live Stage-2 pattern sets (incl. FIFO-eviction victims)."""
    from repro.core.sketch import FailSlowSketch, SketchParams, split_key
    from repro.kernels.sketch_update import ops as O
    p = SketchParams(d=d, m=m, H=H, L=L)
    rng = np.random.default_rng(seed)
    n = 600
    keys = rng.integers(0, 40, size=n).astype(np.int64) * 31337
    lo, hi = split_key(keys)
    dur = rng.random(n).astype(np.float32)
    ts = np.arange(n, dtype=np.float32)
    oracle = FailSlowSketch(p)
    oracle.insert_stream(keys, dur, dur * 2, ts.astype(float))
    st = O.insert(O.make_state(p), jnp.asarray(lo), jnp.asarray(hi),
                  jnp.asarray(dur), jnp.asarray(dur * 2), jnp.asarray(ts),
                  params=p, impl="batched")
    # Stage-1 tables bit-identical
    assert np.array_equal(np.asarray(st["freq"]), oracle.freq)
    assert np.array_equal(np.asarray(st["valid"]),
                          oracle.valid.astype(np.int32))
    assert np.array_equal(np.asarray(st["keys_lo"]) * np.asarray(st["valid"]),
                          oracle.keys_lo * oracle.valid)
    # live Stage-2 patterns identical (key set, counts, arrival order)
    pats = {q.key: q for q in O.patterns(st)}
    assert set(pats) == set(int(k) for k in oracle.stage2)
    for k, q in pats.items():
        exp = oracle.stage2[k]
        assert q.count == exp.count
        assert q.arrival == exp.arrival
        assert q.sum_dur == pytest.approx(exp.sum_dur, rel=1e-5)
        assert q.min_dur == pytest.approx(exp.min_dur, rel=1e-6)
    if L <= 8:
        assert oracle.n_evicted > 0      # the stream exercised eviction


def test_sketch_batched_promotion_and_evict_edges():
    """Deterministic Stage-1/Stage-2 edge cases: promotion exactly at H,
    decrement-clear-claim of a contested bucket, FIFO eviction order."""
    from repro.core.sketch import FailSlowSketch, SketchParams, split_key
    from repro.kernels.sketch_update import ops as O
    p = SketchParams(d=1, m=1, H=3, L=2)     # one bucket: force the races
    # key 7 ×3 (promotes at freq 3), key 9 ×6 (3 decrements clear the
    # bucket, 3 claims re-promote), key 5 ×6 (same dance) → the third
    # Stage-2 pattern FIFO-evicts the oldest (key 7)
    keys = np.array([7] * 3 + [9] * 6 + [5] * 6, dtype=np.int64)
    lo, hi = split_key(keys)
    n = len(keys)
    dur = np.full(n, 0.5, np.float32)
    ts = np.arange(n, dtype=np.float32)
    oracle = FailSlowSketch(p)
    oracle.insert_stream(keys, dur, dur, ts.astype(float))
    st = O.insert(O.make_state(p), jnp.asarray(lo), jnp.asarray(hi),
                  jnp.asarray(dur), jnp.asarray(dur), jnp.asarray(ts),
                  params=p, impl="batched")
    assert np.array_equal(np.asarray(st["freq"]), oracle.freq)
    live = {q.key: q.count for q in O.patterns(st)}
    assert live == {int(k): v.count for k, v in oracle.stage2.items()}
    assert oracle.n_evicted == 1 and 7 not in live   # FIFO victim


def _assert_pattern_parity(got, exp, *, check_stats=True):
    """Merged patterns() parity: exact keys/counts/arrivals, f32-tolerance
    statistics."""
    got = {p.key: p for p in got}
    exp = {p.key: p for p in exp}
    assert set(got) == set(exp)
    for k, q in got.items():
        e = exp[k]
        assert q.count == e.count and q.arrival == e.arrival, k
        if check_stats:
            assert q.sum_dur == pytest.approx(e.sum_dur, rel=1e-4)
            assert q.sum_val == pytest.approx(e.sum_val, rel=1e-4)
            assert q.min_dur == pytest.approx(e.min_dur, rel=1e-5)
            assert q.t_first == pytest.approx(e.t_first, rel=1e-4)
            assert q.t_last == pytest.approx(e.t_last, rel=1e-4)


@pytest.mark.parametrize("impl", ["batched", "pallas"])
def test_sketch_drain_matches_oracle_under_eviction(impl, interpret):
    """Forced Stage-2 eviction pressure (small L, many distinct promoted
    keys): the drained-eviction stream preserves every FIFO victim, so
    merged patterns() — live + drained — equals the numpy oracle's.
    Without the drain the packed paths silently lose evicted patterns."""
    from repro.core.sketch import FailSlowSketch, SketchParams, split_key
    from repro.kernels.sketch_update import ops as O
    p = SketchParams(d=2, m=64, H=2, L=4)   # L=4 ≪ distinct promoted keys
    rng = np.random.default_rng(11)
    n = 500
    keys = rng.integers(0, 40, size=n).astype(np.int64) * 31337
    lo, hi = split_key(keys)
    dur = rng.random(n).astype(np.float32)
    ts = np.arange(n, dtype=np.float32)
    oracle = FailSlowSketch(p)
    oracle.insert_stream(keys, dur, dur * 2, ts.astype(float))
    assert oracle.n_evicted > p.L            # pressure actually applied
    st, dr = O.insert(O.make_state(p), jnp.asarray(lo), jnp.asarray(hi),
                      jnp.asarray(dur), jnp.asarray(dur * 2),
                      jnp.asarray(ts), params=p, impl=impl,
                      drain=O.make_drain(n), interpret=interpret)
    assert int(np.asarray(dr["d_n"])) == oracle.n_evicted
    _assert_pattern_parity(O.patterns(st, dr),
                           oracle.patterns(include_drained=True))
    # drain-less call still returns the live-only view, unchanged state
    st2 = O.insert(O.make_state(p), jnp.asarray(lo), jnp.asarray(hi),
                   jnp.asarray(dur), jnp.asarray(dur * 2),
                   jnp.asarray(ts), params=p, impl=impl,
                   interpret=interpret)
    for k in st2:
        assert np.array_equal(np.asarray(st[k]), np.asarray(st2[k])), k


@pytest.mark.parametrize("d,m,H,L,n,block,cap", [
    (2, 1024, 4, 2048, 600, 256, 600),  # the pod detector's geometry
    (2, 16, 1, 4, 400, 128, 5),         # drain saturates past 5 rows
    (3, 8, 2, 8, 257, 256, 257),        # one record past a block edge
])
def test_sketch_kernel_smem_layout_matches_batched(d, m, H, L, n, block,
                                                   cap, interpret):
    """The chip layout — trace blocks, flattened Stage-1 tables and
    scalars in SMEM, Stage-2 and drain rows as masked VMEM lane stores —
    carries state and drain exactly like the batched scan, including a
    drain too small for its evictions."""
    from repro.core.sketch import SketchParams, split_key
    from repro.kernels.sketch_update import batched as V, ops as O
    p = SketchParams(d=d, m=m, H=H, L=L)
    rng = np.random.default_rng(d * 1000 + L)
    keys = rng.integers(0, 300, size=n).astype(np.int64) * 0x9E3779B9
    lo, hi = split_key(keys)
    dur = rng.random(n).astype(np.float32)
    val = (rng.random(n) * 5).astype(np.float32)
    t = np.cumsum(rng.random(n)).astype(np.float32)
    args = tuple(jnp.asarray(x) for x in (lo, hi, dur, val, t))
    want = V.insert_batch_drained(O.make_state(p), O.make_drain(cap),
                                  *args, H=p.H)
    got = O.insert(O.make_state(p), *args, params=p, impl="pallas",
                   block=block, drain=O.make_drain(cap),
                   interpret=interpret)
    for w_part, g_part in zip(want, got):
        for k in w_part:
            a, b = np.asarray(w_part[k]), np.asarray(g_part[k])
            assert a.shape == b.shape, k
            if a.dtype.kind == "i":
                assert np.array_equal(a, b), k
            else:
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6,
                                           err_msg=k)
    if cap < n:
        assert int(np.asarray(got[1]["d_n"])) <= cap


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31), st.integers(1, 3),
       st.sampled_from([8, 16, 64]), st.integers(1, 8),
       st.sampled_from([2, 4, 16]))
def test_sketch_run_path_matches_insert_run_oracle(seed, d, m, H, L):
    """Run-compressed batched insertion ≡ FailSlowSketch.insert_run over
    randomized runs: bit-identical Stage-1 tables, identical eviction
    structure, merged patterns (live + drained) equal."""
    from repro.core.sketch import FailSlowSketch, SketchParams, split_key
    from repro.kernels.sketch_update import ops as O
    p = SketchParams(d=d, m=m, H=H, L=L)
    rng = np.random.default_rng(seed)
    n = 150
    keys = rng.integers(0, 20, size=n).astype(np.int64) * 0x9E3779B9
    reps = rng.integers(1, 12, size=n)      # spans r<H, r≈H and r≫H
    durs = rng.random(n)
    vals = rng.random(n) * 3
    t0s = np.cumsum(rng.random(n))
    dts = rng.random(n) * 0.01
    oracle = FailSlowSketch(p)
    oracle.insert_runs(keys, reps, durs, vals, t0s, dts)
    lo, hi = split_key(keys)
    st, dr = O.insert_runs(
        O.make_state(p), O.make_drain(n), jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(reps.astype(np.int32)),
        jnp.asarray(durs.astype(np.float32)),
        jnp.asarray(vals.astype(np.float32)),
        jnp.asarray(t0s.astype(np.float32)),
        jnp.asarray(dts.astype(np.float32)), params=p)
    assert np.array_equal(np.asarray(st["freq"]), oracle.freq)
    assert np.array_equal(np.asarray(st["valid"]),
                          oracle.valid.astype(np.int32))
    assert int(np.asarray(dr["d_n"])) == oracle.n_evicted
    _assert_pattern_parity(O.patterns(st, dr),
                           oracle.patterns(include_drained=True))


def test_sketch_run_path_promotion_and_steal_branches():
    """Deterministic run-path edge cases against per-record expansion:

    * mid-run promotion boundary — a key with prior Stage-1 freq f0
      promotes exactly at record k = H − f0 − 1 of the run, so the
      Stage-2 count must be r − (H − f0 − 1);
    * bucket steal — a contested bucket with freq f0 < r is cleared by f0
      decrements, record f0 claims it, and promotion happens at record
      k = f0 + H − 1;
    * pure decrement — r ≤ f0 never promotes and may clear the bucket.
    """
    from repro.core.sketch import FailSlowSketch, SketchParams, split_key
    from repro.kernels.sketch_update import ops as O
    p = SketchParams(d=1, m=1, H=4, L=4)     # one bucket: force the races
    #       key  r    scenario
    runs = [(7,  2),  # f0: 0→2 (claims empty bucket, below H)
            (7,  5),  # mid-run boundary: f0=2, promotes at k=H-f0-1=1 → n=4
            (9,  3),  # decrement only: r=3 ≤ f0=7 → freq 4, key 7 keeps it
            (9,  9),  # steal: f0=4 cleared, record 4 claims, k=4+H-1=7 → n=2
            (5,  3)]  # decrement only again (f0=5 after steal ... )
    keys = np.array([k for k, _ in runs], dtype=np.int64)
    reps = np.array([r for _, r in runs], dtype=np.int64)
    n = len(runs)
    durs = np.full(n, 0.25)
    t0s = np.arange(n, dtype=np.float64) * 10
    dts = np.full(n, 0.5)
    oracle = FailSlowSketch(p)
    oracle.insert_runs(keys, reps, durs, durs * 2, t0s, dts)
    # pin the branch arithmetic itself, not only oracle parity
    assert oracle.stage2[7].count == 4       # r=5 − first_promo(k=1)
    assert oracle.stage2[9].count == 2       # r=9 − first_promo(k=7)
    assert 5 not in oracle.stage2
    lo, hi = split_key(keys)
    st, dr = O.insert_runs(
        O.make_state(p), O.make_drain(n), jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(reps.astype(np.int32)),
        jnp.asarray(durs.astype(np.float32)),
        jnp.asarray((durs * 2).astype(np.float32)),
        jnp.asarray(t0s.astype(np.float32)),
        jnp.asarray(dts.astype(np.float32)), params=p)
    assert np.array_equal(np.asarray(st["freq"]), oracle.freq)
    _assert_pattern_parity(O.patterns(st, dr),
                           oracle.patterns(include_drained=True))


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,t,hq,hk,d,causal,win,dtype", [
    (2, 128, 128, 4, 2, 64, True, None, jnp.float32),
    (1, 256, 256, 2, 2, 32, True, 64, jnp.float32),
    (2, 100, 200, 4, 1, 16, False, None, jnp.float32),
    (1, 1, 384, 8, 4, 64, True, None, jnp.float32),
    (1, 128, 128, 2, 2, 64, True, None, jnp.bfloat16),
])
def test_flash_attention_sweep(b, s, t, hq, hk, d, causal, win, dtype,
                              interpret):
    from repro.kernels.flash_attention.ops import gqa_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, hk, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, hk, d)).astype(dtype)
    a = gqa_attention(q, k, v, causal=causal, window=win, impl="pallas",
                      q_block=64, kv_block=64, interpret=interpret)
    r = gqa_attention(q, k, v, causal=causal, window=win, impl="ref")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(r, np.float32), atol=tol,
                               rtol=tol)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 96, 4, 32, 2, 16, 32),
    (1, 200, 2, 16, 1, 8, 64),
    (2, 64, 8, 8, 4, 8, 16),
    # the chip's layout: lane-wide chunks of the [BH, 1, S] dt row, a
    # padded tail, and a per-head decay read from SMEM
    (1, 300, 3, 64, 1, 128, 128),
    (2, 256, 2, 32, 2, 16, 128),
])
def test_ssd_kernel_sweep(b, s, h, p, g, n, chunk, interpret):
    from repro.kernels.ssd_scan.ops import ssd
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.3
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bb = jax.random.normal(ks[3], (b, s, g, n)) * 0.4
    cc = jax.random.normal(ks[4], (b, s, g, n)) * 0.4
    yp, sp = ssd(x, dt, a, bb, cc, impl="pallas", chunk=chunk,
                 interpret=interpret)
    yr, sr = ssd(x, dt, a, bb, cc, impl="ref")
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), atol=2e-4,
                               rtol=2e-4)


def test_model_ssd_matches_recurrence():
    from repro.kernels.ssd_scan.ops import ssd
    from repro.models.mamba2 import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    b, s, h, p, g, n = 2, 80, 4, 16, 2, 8
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.3
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bb = jax.random.normal(ks[3], (b, s, g, n)) * 0.4
    cc = jax.random.normal(ks[4], (b, s, g, n)) * 0.4
    ym, sm = ssd_chunked(x, dt, a, bb, cc, chunk=32)
    yr, sr = ssd(x, dt, a, bb, cc, impl="ref")
    np.testing.assert_allclose(np.asarray(ym), np.asarray(yr), atol=2e-4,
                               rtol=2e-4)


# ---------------------------------------------------------------------------
# failrank_step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [40, 130, 260])
def test_failrank_step_sweep(n, interpret):
    from repro.kernels.failrank_step.kernel import failrank_step
    from repro.kernels.failrank_step.ref import failrank_step_ref
    rng = np.random.default_rng(n)
    w = rng.random((n, n)).astype(np.float32)
    w /= w.sum(1, keepdims=True)
    l = rng.random((n, n)).astype(np.float32)
    s = rng.random(n).astype(np.float32)
    s0 = rng.random(n).astype(np.float32)
    sp, lp = failrank_step(jnp.asarray(w), jnp.asarray(l), jnp.asarray(s),
                           jnp.asarray(s0), interpret=interpret)
    sr, lr = failrank_step_ref(jnp.asarray(w), jnp.asarray(l),
                               jnp.asarray(s), jnp.asarray(s0))
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lr), atol=1e-5)


def test_failrank_dense_matches_coo_pipeline(interpret):
    from repro.core.failrank import failrank
    from repro.core.failures import FailSlow
    from repro.core.graph import build_workload
    from repro.core.routing import Mesh2D
    from repro.core.sloth import Sloth
    from repro.kernels.failrank_step.ops import failrank_dense
    sloth = Sloth(build_workload("darknet19"), Mesh2D(4))
    v = sloth.detect([FailSlow("core", 5, 1.0, 8.0)], seed=0)
    r_coo = failrank(v.mcg)
    _, s_raw, _, _ = failrank_dense(v.mcg, impl="pallas",
                                    interpret=interpret)
    np.testing.assert_allclose(s_raw, r_coo.raw_node_scores, atol=1e-4)
