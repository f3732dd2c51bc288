"""Lane-packed embedding tables (``Embedding.pack``): the serving layout of
tables narrower than 128 lanes gathers bit for bit what the table as made
gathers, and the serving pair stores its tables that way."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.interest import InterestConfig
from repro.models.ctr import CTRConfig, CTRModel
from repro.nn.layers import LANES, Embedding
from repro.serve.ctr_server import CTRServer

N_ITEMS, N_CATS = 301, 37                  # neither a multiple of any k


@pytest.mark.parametrize("vocab", [256, 301])
@pytest.mark.parametrize("dim", [16, 32, 64])
def test_packed_gather_is_bit_identical(dim, vocab):
    emb = Embedding(vocab, dim)
    params = emb.init(jax.random.PRNGKey(dim))
    packed = emb.pack(params)
    k = LANES // dim
    assert packed["table"].shape == (-(-vocab // k), LANES)
    assert emb.rows_per_packed_row(packed) == k
    every = jnp.arange(vocab)
    np.testing.assert_array_equal(np.asarray(emb.apply(packed, every)),
                                  np.asarray(params["table"]))
    ids = jax.random.randint(jax.random.PRNGKey(1), (3, 5, 7), 0, vocab)
    np.testing.assert_array_equal(np.asarray(emb.apply(packed, ids)),
                                  np.asarray(emb.apply(params, ids)))


@pytest.mark.parametrize("dim", [48, 128, 64])
def test_pack_leaves_other_tables_and_packed_ones_alone(dim):
    """A width that does not divide 128, or is 128, stays as made; a packed
    table packs to itself."""
    emb = Embedding(40, dim)
    params = emb.init(jax.random.PRNGKey(0))
    once = emb.pack(params)
    if LANES % dim or dim == LANES:
        assert once["table"] is params["table"]
        assert emb.rows_per_packed_row(once) == 1
    else:
        assert once["table"].shape[-1] == LANES
    assert emb.pack(once)["table"] is once["table"]


def _model(embed_dim):
    cfg = CTRConfig(arch="din", n_items=N_ITEMS, n_cats=N_CATS, long_len=24,
                    short_len=8, mlp_hidden=(16,), embed_dim=embed_dim,
                    interest=InterestConfig(kind="sdim", m=12, tau=2))
    model = CTRModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _requests(model, n=3):
    cfg = model.cfg
    rng = np.random.default_rng(2)
    reqs = []
    for u in range(n):
        hist = {"hist_items": rng.integers(0, N_ITEMS, (1, cfg.long_len)),
                "hist_cats": rng.integers(0, N_CATS, (1, cfg.long_len)),
                "hist_mask": np.ones((1, cfg.long_len), np.float32)}
        c = 2 + u                                          # ragged candidates
        reqs.append((u, hist, rng.integers(0, N_ITEMS, c),
                     rng.integers(0, N_CATS, c),
                     rng.standard_normal((c, cfg.ctx_dim)).astype(np.float32)))
    return reqs


@pytest.mark.parametrize("fused,table_dtype", [(False, jnp.float32),
                                               (True, jnp.int8)])
def test_server_scores_match_unpacked_tables(monkeypatch, fused,
                                             table_dtype):
    """The two-dispatch and the int8 fused deployments serve the same
    tables and the same scores from packed tables as from the tables as
    made."""
    model, params = _model(32)
    kw = dict(capacity=4, wire_dtype=jnp.float32, fused=fused,
              table_dtype=table_dtype)
    packed = CTRServer.build(model, params, "decoupled", **kw)
    with monkeypatch.context() as m:
        m.setattr(model, "pack_tables", lambda p: p)
        plain = CTRServer.build(model, params, "decoupled", **kw)
    assert model.n_packed_tables(packed.params) == 2
    # the BSE server's ingest reads the very arrays the scorer reads
    assert packed.bse.params["item_emb"]["table"] is \
        packed.params["item_emb"]["table"]
    assert model.n_packed_tables(plain.params) == 0
    reqs = _requests(model)
    got, want = packed.handle_requests(reqs), plain.handle_requests(reqs)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    users = [r[0] for r in reqs]
    np.testing.assert_array_equal(np.asarray(packed.bse.fetch_many(users)),
                                  np.asarray(plain.bse.fetch_many(users)))


@pytest.mark.parametrize("embed_dim,n_packed", [(64, 2), (32, 2), (128, 0)])
def test_packed_tables_gauge(embed_dim, n_packed):
    model, params = _model(embed_dim)
    server = CTRServer.build(model, params, "decoupled", capacity=4)
    gauges = server.metrics.snapshot()["gauges"]
    assert gauges["ctr.packed_tables"] == n_packed
    # a server made from a built server's params packs nothing again
    again = CTRServer(model, server.params, server.bse, mode="decoupled")
    assert again.params["item_emb"]["table"] is \
        server.params["item_emb"]["table"]


def _ingest(server, reqs):
    bse = server.bse
    bse.ingest_histories(
        [r[0] for r in reqs],
        *(np.concatenate([r[1][k] for r in reqs])
          for k in ("hist_items", "hist_cats", "hist_mask")))
    if bse.async_ingest is not None:
        bse.async_ingest.flush()


@pytest.mark.parametrize("async_ingest", [False, True])
def test_refresh_packs_pushed_params(async_ingest):
    """A model push with tables as made leaves the BSE server's ingest
    embedding from packed tables, and re-ingested users score as before."""
    model, params = _model(32)
    server = CTRServer.build(model, params, "decoupled", capacity=4,
                             wire_dtype=jnp.float32,
                             async_ingest=async_ingest)
    reqs = _requests(model)
    _ingest(server, reqs)
    before = server.handle_requests(reqs)
    server.bse.refresh_params(params)
    assert model.n_packed_tables(server.bse.params) == 2
    assert server.bse.params["item_emb"]["table"].shape == \
        server.params["item_emb"]["table"].shape
    _ingest(server, reqs)
    for a, b in zip(server.handle_requests(reqs), before):
        np.testing.assert_array_equal(a, b)
