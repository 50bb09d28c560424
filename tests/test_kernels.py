"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.bloom import pack_bits


def _ell_inputs(rng, q, v, d, semiring):
    nbr = rng.integers(0, v + 1, size=(v, d)).astype(np.int32)  # v = identity slot
    w = rng.integers(1, 10, size=(v, d)).astype(np.float32)
    if semiring == "pr_sum":
        states = np.concatenate(
            [rng.random((q, v), np.float32), np.zeros((q, 1), np.float32)], 1
        )
        carry = np.full((q, v), 0.15, np.float32)
    else:
        states = np.concatenate(
            [rng.random((q, v), np.float32) * 10, np.full((q, 1), np.inf, np.float32)], 1
        )
        carry = rng.random((q, v)).astype(np.float32) * 10
    return jnp.asarray(states), jnp.asarray(nbr), jnp.asarray(w), jnp.asarray(carry)


@pytest.mark.parametrize("semiring", ["min_plus", "min_hop", "min_label", "pr_sum"])
@pytest.mark.parametrize("q,v,d", [(1, 16, 4), (3, 100, 8), (2, 257, 16), (4, 128, 32)])
def test_ell_spmv_matches_ref(semiring, q, v, d):
    rng = np.random.default_rng(hash((semiring, q, v, d)) % 2**31)
    states, nbr, w, carry = _ell_inputs(rng, q, v, d, semiring)
    got = ops.spmv(states, nbr, w, carry, semiring=semiring, block_v=64, interpret=True)
    want = ref.ell_spmv_ref(states, nbr, w, carry, semiring=semiring)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("n,s", [(8, 4), (100, 8), (513, 16), (1024, 32)])
def test_diff_lookup_matches_ref(n, s):
    rng = np.random.default_rng(n * 1000 + s)
    iters = np.sort(rng.integers(0, 60, size=(n, s)), axis=1).astype(np.int32)
    counts = rng.integers(0, s + 1, size=n)
    imax = np.iinfo(np.int32).max
    for r in range(n):
        iters[r, counts[r]:] = imax
    vals = rng.random((n, s)).astype(np.float32)
    qi = rng.integers(0, 70, size=n).astype(np.int32)
    gv, gi, gf = ops.lookup(jnp.asarray(iters), jnp.asarray(vals), jnp.asarray(qi),
                            block_n=128, interpret=True)
    wv, wi, wf = ref.diff_lookup_ref(jnp.asarray(iters), jnp.asarray(vals), jnp.asarray(qi))
    np.testing.assert_array_equal(gf, wf)
    np.testing.assert_array_equal(gi, wi)
    np.testing.assert_allclose(np.where(gf, gv, 0), np.where(wf, wv, 0), rtol=1e-6)


@pytest.mark.parametrize("q,n,mbits,k", [(1, 64, 1 << 10, 2), (3, 500, 1 << 12, 4), (2, 1024, 1 << 14, 6)])
def test_bloom_kernel_matches_ref_and_filter(q, n, mbits, k):
    from repro.core import bloom as bl

    rng = np.random.default_rng(q * n)
    flt = bl.make((q,), mbits, num_hashes=k)
    v = jnp.asarray(rng.integers(0, 5000, size=(q, n)), jnp.int32)
    i = jnp.asarray(rng.integers(0, 64, size=(q, n)), jnp.int32)
    mask = jnp.asarray(rng.random((q, n)) < 0.5)
    salt = jnp.arange(q, dtype=jnp.int32)
    flt = bl.insert(flt, v, i, mask, salt=salt[:, None])
    words = pack_bits(flt.bits)

    got = ops.bloom(words, v, i, salt, num_hashes=k, block_n=256, interpret=True)
    want = ref.bloom_query_ref(words, v, i, salt, num_hashes=k)
    np.testing.assert_array_equal(got, want)
    # kernel agrees with the pure filter, and never false-negatives
    pure = bl.query(flt, v, i, salt=salt[:, None])
    np.testing.assert_array_equal(got, pure)
    assert bool(jnp.all(jnp.where(mask, got, True)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,sq,sk,d,causal",
    [
        (1, 2, 2, 128, 128, 64, True),
        (2, 4, 2, 256, 256, 32, True),   # GQA 2:1
        (1, 8, 1, 128, 256, 64, False),  # MQA, cross-length
    ],
)
def test_flash_attention_matches_ref(b, hq, hkv, sq, sk, d, causal, dtype):
    rng = np.random.default_rng(sq + sk + hq)
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dtype)
    got = ops.attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_engine_step_equals_kernel_spmv():
    """The Pallas kernel computes the same IFE step as the engine's segment path."""
    from repro.core import queries as q
    from repro.core.engine import GraphArrays, ife_step
    from repro.core.graph import DynamicGraph
    from repro.data.graphgen import powerlaw_graph

    edges = powerlaw_graph(60, 240, seed=5)
    g = DynamicGraph(60, edges, capacity=512)
    eng = q.sssp(g, sources=[0, 7], max_iters=48)
    snap = g.snapshot()
    nbr, w, _ = snap.to_ell()
    cur = eng.state.cur
    states = jnp.concatenate([cur, jnp.full((2, 1), jnp.inf)], axis=1)
    got = ops.spmv(states, jnp.asarray(nbr), jnp.asarray(w), cur, semiring="min_plus", interpret=True)
    want = ife_step(eng.cfg, cur, GraphArrays.from_snapshot(snap))
    np.testing.assert_allclose(got, want)


def test_forced_interpret_mode_on_tpu_raises(monkeypatch):
    """On a TPU backend the default resolves to compiled Mosaic, and forcing
    interpret mode is refused, through the engine's ELL step too."""
    from repro.core import engine, plan
    from repro.kernels import interpret

    monkeypatch.setattr(interpret.jax, "default_backend", lambda: "tpu")
    assert interpret.resolve_interpret(None) is False
    assert interpret.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        interpret.resolve_interpret(True)
    p = plan.sssp(0, max_iters=4)
    cfg = engine.EngineConfig(
        num_queries=1, num_vertices=8, max_iters=4, semiring=p.semiring,
        backend="ell", interpret=True,
    )
    g = engine.GraphArrays(
        *(jnp.zeros(4, dt) for dt in (jnp.int32, jnp.int32, jnp.float32, bool)),
        out_degree=jnp.zeros(8, jnp.int32),
        in_degree=jnp.zeros(8, jnp.int32),
        nbr=jnp.full((8, 8), 8, jnp.int32),
        ell_w=jnp.zeros((8, 8), jnp.float32),
    )
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        engine.ell_step(cfg, jnp.zeros((1, 8), jnp.float32), g)
