"""SDIMEngine backend equivalence: ``pallas`` (interpret mode on CPU) vs
``xla`` vs the literal Eq. 9/11/12 collision-gather oracle, on NON-paper
shapes — ragged L/C, singletons, all-masked rows — and both hash families."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sdim
from repro.core.engine import EngineConfig, SDIMEngine, resolve_backend

SHAPES = [
    # (B, L, C, d, m, tau)
    (2, 1, 4, 32, 12, 2),      # L=1 (single behavior)
    (2, 100, 8, 32, 12, 2),    # L not a multiple of block_l
    (1, 257, 1, 16, 8, 2),     # ragged L and C=1
    (2, 64, 33, 64, 48, 3),    # ragged C, paper m/τ
    (2, 256, 128, 64, 48, 3),  # paper-aligned shape
]
FAMILIES = ["dense", "srht"]


def _engines(d, m, tau, family):
    base = EngineConfig(m=m, tau=tau, d=d, family=family, hash_seed=7,
                        block_l=128, block_c=128)
    xla = SDIMEngine(dataclasses.replace(base, backend="xla"))
    pallas = SDIMEngine(dataclasses.replace(base, backend="pallas",
                                            interpret=jax.default_backend() != "tpu"))
    return xla, pallas


def _inputs(B, L, C, d, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    seq = jax.random.normal(k1, (B, L, d))
    q = jax.random.normal(k2, (B, C, d))
    mask = (jax.random.uniform(k3, (B, L)) > 0.3).astype(jnp.float32)
    return seq, q, mask


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("shape", SHAPES)
def test_pallas_backend_matches_xla(shape, family):
    B, L, C, d, m, tau = shape
    seq, q, mask = _inputs(B, L, C, d)
    ex, ep = _engines(d, m, tau, family)
    assert bool(jnp.all(ex.R == ep.R))  # same family, same seed
    np.testing.assert_allclose(ep.encode(seq, mask), ex.encode(seq, mask),
                               rtol=1e-5, atol=1e-5)
    table = ex.encode(seq, mask)
    np.testing.assert_allclose(ep.query(q, table), ex.query(q, table),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ep.attend(q, seq, mask), ex.attend(q, seq, mask),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ep.serve(q, seq, mask), ex.serve(q, seq, mask),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("shape", SHAPES)
def test_backends_match_gather_oracle(shape, family):
    B, L, C, d, m, tau = shape
    seq, q, mask = _inputs(B, L, C, d)
    ex, ep = _engines(d, m, tau, family)
    oracle = sdim.sdim_attention_gather(q, seq, mask, ex.R, tau)
    np.testing.assert_allclose(ex.attend(q, seq, mask), oracle,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ep.attend(q, seq, mask), oracle,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ep.serve(q, seq, mask), oracle,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_all_masked_rows_finite_and_equal(backend):
    B, L, C, d, m, tau = 2, 64, 8, 32, 12, 2
    seq, q, _ = _inputs(B, L, C, d)
    mask = jnp.zeros((B, L))
    ex, ep = _engines(d, m, tau, "dense")
    e = ex if backend == "xla" else ep
    out = e.attend(q, seq, mask)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, jnp.zeros_like(out), atol=1e-6)
    assert bool(jnp.all(jnp.isfinite(e.serve(q, seq, mask))))


def test_single_query_vector_shape():
    """(B, d) queries (training layout) work on both backends."""
    B, L, d, m, tau = 2, 100, 32, 12, 2
    seq, q, mask = _inputs(B, L, 4, d)
    q1 = q[:, 0]
    ex, ep = _engines(d, m, tau, "dense")
    a = ex.attend(q1, seq, mask)
    b = ep.attend(q1, seq, mask)
    assert a.shape == b.shape == (B, d)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_engine_update_backend_parity_and_oracle():
    """Batched slot update: xla (segment_sum) == pallas (scatter kernel) ==
    the kernels' ref, with duplicate unsorted slots and a mask."""
    from repro.kernels.sdim_update.ref import sdim_update_ref

    N, B, E, d, m, tau = 5, 7, 3, 32, 12, 2
    ex, ep = _engines(d, m, tau, "dense")
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    store = jax.random.normal(k1, (N, m // tau, 1 << tau, d))
    events = jax.random.normal(k2, (B, E, d))
    mask = (jax.random.uniform(k3, (B, E)) > 0.4).astype(jnp.float32)
    slots = jnp.asarray(np.array([3, 0, 3, 1, 4, 3, 0], np.int32))
    oracle = sdim_update_ref(store, slots, events, mask, ex.R, tau)
    np.testing.assert_allclose(ex.update(store, slots, events, mask), oracle,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ep.update(store, slots, events, mask), oracle,
                               rtol=1e-5, atol=1e-5)
    # mask=None means every event is valid
    np.testing.assert_allclose(
        ex.update(store, slots, events),
        ex.update(store, slots, events, jnp.ones((B, E))),
        rtol=1e-6, atol=1e-6)


def test_engine_update_is_incremental_encode():
    """update on a zero store == encode of the same behaviors (Eq. 8: the
    table is a sum, so events fold in exactly)."""
    B, E, d, m, tau = 3, 4, 32, 12, 2
    ex, _ = _engines(d, m, tau, "dense")
    events = jax.random.normal(jax.random.PRNGKey(6), (B, E, d))
    store = jnp.zeros((B, m // tau, 1 << tau, d))
    out = ex.update(store, jnp.arange(B), events)
    np.testing.assert_allclose(out, ex.encode(events), rtol=1e-5, atol=1e-6)


def test_engine_from_interest_threads_kernel_params():
    """Regression: block_l/block_c/interpret must survive the
    InterestConfig -> EngineConfig hop (they used to be dropped)."""
    from repro.core.engine import engine_from_interest
    from repro.core.interest import InterestConfig

    icfg = InterestConfig(kind="sdim", m=12, tau=2, d=16, backend="pallas",
                          block_l=32, block_c=16, interpret=True)
    eng = engine_from_interest(icfg)
    assert eng.cfg.block_l == 32
    assert eng.cfg.block_c == 16
    assert eng.cfg.interpret is True and eng.interpret is True
    # defaults still apply for configs that don't carry the knobs
    eng2 = engine_from_interest(InterestConfig(kind="sdim", m=12, tau=2, d=16))
    assert eng2.cfg.block_l == EngineConfig().block_l
    assert eng2.cfg.interpret is None


def test_auto_backend_resolves():
    assert resolve_backend("auto") in ("xla", "pallas")
    assert resolve_backend("xla") == "xla"
    cfg = EngineConfig(backend="auto", m=12, tau=2, d=16)
    assert SDIMEngine(cfg).backend == resolve_backend("auto")


def test_interpret_mode_refused_on_tpu(monkeypatch):
    """On a TPU the kernels must compile: an explicit interpret=True is an
    error there, and the default never interprets."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret"):
        _ = SDIMEngine(EngineConfig(backend="pallas", interpret=True)).interpret
    eng = SDIMEngine(EngineConfig(backend="auto"))
    assert eng.backend == "pallas" and eng.interpret is False


def test_srht_family_is_a_real_hash_family():
    """Densified SRHT projections equal the FWHT-chain projections, so the
    engine's srht family IS the O(m·log d) family, just GEMM-materialized."""
    from repro.core import simhash

    d, m = 48, 24
    h = simhash.srht_hashes(jax.random.PRNGKey(3), m, d)
    x = jax.random.normal(jax.random.PRNGKey(4), (64, d))
    np.testing.assert_allclose(x @ h.dense_matrix().T, h.project(x),
                               rtol=1e-4, atol=1e-4)


def test_model_level_backend_parity():
    """Whole CTR serving stack flips backend via one config flag and agrees."""
    from repro.core.interest import InterestConfig
    from repro.data.synthetic import SyntheticCTRConfig, generate_batch
    from repro.models.ctr import CTRModel, CTRConfig

    scores = {}
    for backend in ("xla", "pallas"):
        cfg = CTRConfig(arch="din", n_items=500, n_cats=20, long_len=100,
                        short_len=8, mlp_hidden=(16,),
                        interest=InterestConfig(kind="sdim", m=12, tau=2,
                                                backend=backend))
        model = CTRModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        raw = generate_batch(SyntheticCTRConfig(hist_len=100, n_items=500,
                                                n_cats=20), 1, 0)
        user = {k: jnp.asarray(v) for k, v in raw.items() if k.startswith("hist")}
        rng = np.random.default_rng(0)
        ci = jnp.asarray(rng.integers(0, 500, 16).astype(np.int32))
        cc = jnp.asarray(rng.integers(0, 20, 16).astype(np.int32))
        scores[backend] = model.score_candidates(params, user, ci, cc,
                                                 jnp.zeros((16, 4)))
    np.testing.assert_allclose(scores["xla"], scores["pallas"],
                               rtol=1e-4, atol=1e-4)
