"""The tower seam: a configuration's short-term tower is the file
``towers/<arch>.py``, found by its ``arch``.

DIN went behind the seam without moving a number: the weights, the
reference's outputs and the operation counts equal, bit for bit, those
of a frozen copy of the code that served DIN before the seam existed. A
tower written as a new file is found and used through the seam, with no
edit to any harness file, and an ``arch`` with no file is an error that
names the file it looked for."""
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import checks, harness, reference, shapes, spec  # noqa: E402
from chipbench import weights  # noqa: E402
from test_chipbench_faults import CONFIGS, toy  # noqa: E402

# ---------------------------------------------------------------------------
# A frozen copy of the DIN code as it stood in weights.py, reference.py,
# checks.py and shapes.py before the seam: the numbers it gives are the
# ones the two DIN configurations were measured with.
# ---------------------------------------------------------------------------
DEFAULT = jax.lax.Precision.DEFAULT


def frozen_head_dims(cfg):
    e = 2 * cfg["embed_dim"]
    return (3 * e + cfg["ctx_dim"], *cfg["mlp_hidden"], 1)


@partial(jax.jit, static_argnames=("n_items", "n_cats", "embed_dim", "m",
                                   "dims", "emb_std"))
def frozen_make_jit(key, *, n_items, n_cats, embed_dim, m, dims, emb_std):
    ks = jax.random.split(key, 3 + 2 * (len(dims) - 1))
    head = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        head[f"fc{i}"] = {
            "w": jax.random.normal(ks[3 + 2 * i], (a, b)) / jnp.sqrt(a),
            "b": 0.01 * jax.random.normal(ks[4 + 2 * i], (b,)),
        }
    return {
        "item_emb": {"table": emb_std * jax.random.normal(
            ks[0], (n_items, embed_dim))},
        "cat_emb": {"table": emb_std * jax.random.normal(
            ks[1], (n_cats, embed_dim))},
        "interest": {"buffers": {"R": jax.random.normal(
            ks[2], (m, 2 * embed_dim))}},
        "head": head,
    }


def frozen_make(cfg, key):
    return frozen_make_jit(key, n_items=cfg["n_items"], n_cats=cfg["n_cats"],
                           embed_dim=cfg["embed_dim"], m=cfg["m"],
                           dims=frozen_head_dims(cfg),
                           emb_std=cfg["emb_std"])


def frozen_short_interest(q, seq, mask, dtype):
    e = q.shape[-1]
    s = jnp.einsum("ce,se->cs", q.astype(dtype), seq.astype(dtype),
                   precision=DEFAULT, preferred_element_type=dtype)
    s = s.astype(jnp.float32) / jnp.sqrt(jnp.float32(e))
    s = jnp.where(mask[None, :] > 0, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    return jnp.einsum("cs,se->ce", p, seq.astype(dtype), precision=DEFAULT,
                      preferred_element_type=dtype)


@partial(jax.jit, static_argnames=("cfg", "dtype", "wire"))
def frozen_serve(w, table, ci, cc, ctx, hi, hc, hm, *, cfg, dtype, wire):
    c = dict(cfg)
    R = w["interest"]["buffers"]["R"]
    wd = jnp.dtype(wire)

    def one(tb, ci, cc, ctx, hi, hc, hm):
        q = reference.embed(w, ci, cc, c["n_items"], c["n_cats"], dtype)
        if c["fused"]:
            long = reference.interest(q, tb, R, c["tau"], dtype).astype(wd)
            sent = long
        else:
            tb = tb.astype(wd)
            long = reference.interest(q, tb, R, c["tau"], dtype)
            sent = long
        seq = reference.embed(w, hi, hc, c["n_items"], c["n_cats"], dtype)
        short = frozen_short_interest(q, seq, hm, dtype)
        x = jnp.concatenate([q.astype(dtype), short.astype(dtype),
                             long.astype(dtype), ctx.astype(dtype)], axis=-1)
        return (sent.astype(jnp.float32),
                reference.head(w, x, dtype).astype(jnp.float32))

    return jax.vmap(one)(table, ci, cc, ctx, hi, hc, hm)


def frozen_reference_outputs(cfg, w, hist, req, *, dtype, qmax, wire):
    rc = tuple((k, cfg[k]) for k in ("n_items", "n_cats", "tau", "fused"))
    tables = reference.user_tables(
        w, jnp.asarray(hist["items"]), jnp.asarray(hist["cats"]),
        jnp.asarray(hist["mask"]), cfg=rc, dtype=dtype, qmax=qmax)
    sent, scores = frozen_serve(
        w, tables[jnp.asarray(req["slot"])], jnp.asarray(req["cand_items"]),
        jnp.asarray(req["cand_cats"]), jnp.asarray(req["ctx"]),
        jnp.asarray(req["hi"]), jnp.asarray(req["hc"]),
        jnp.asarray(req["hm"]), cfg=rc, dtype=dtype, wire=wire)
    return {"tables": np.asarray(tables, np.float32),
            "interest": np.asarray(sent, np.float32),
            "scores": np.asarray(scores, np.float32)}


def frozen_request_flops(C, cfg):
    w = shapes.widths(cfg)
    attn = 2 * 2 * C * w["s"] * w["e"]
    dims = (3 * w["e"] + w["ctx"], *w["mlp"], 1)
    mlp = 2 * C * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    quantized = cfg.get("qmax") is not None
    return attn + mlp + shapes.sdim_read_flops(1, C, cfg, dequant=quantized)


# ---------------------------------------------------------------------------
def inputs(cfg, users=5, n=7, C=16, seed=0):
    """Histories of ``users`` users and ``n`` requests on them, as
    ``checks.reference_outputs`` takes them."""
    rng = np.random.default_rng(seed)
    L, s = cfg["long_len"], cfg["short_len"]
    ids = lambda shape: rng.integers(0, 1 << 20, shape).astype(np.int32)
    hist = {"items": ids((users, L)), "cats": ids((users, L)),
            "mask": (rng.random((users, L)) < 0.8).astype(np.float32)}
    req = {"slot": rng.integers(0, users, n), "cand_items": ids((n, C)),
           "cand_cats": ids((n, C)),
           "ctx": rng.integers(0, 2, (n, C, cfg["ctx_dim"])).astype(
               np.float32),
           "hi": ids((n, s)), "hc": ids((n, s)),
           "hm": (rng.random((n, s)) < 0.9).astype(np.float32)}
    return hist, req


def same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(a, b)


@pytest.mark.parametrize("name", CONFIGS)
def test_the_seam_gives_dins_weights_bit_for_bit(name):
    cfg = toy(name)
    key = weights.model_key(2**31 + 77)
    got, want = weights.make(cfg, key), frozen_make(cfg, key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(same(a, b) for a, b in zip(jax.tree.leaves(got),
                                          jax.tree.leaves(want)))


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("precision", ["configured", "control"])
def test_the_seam_gives_dins_reference_bit_for_bit(name, precision):
    cfg = toy(name)
    w = weights.make(cfg, weights.model_key(5))
    hist, req = inputs(cfg)
    kw = dict(dtype=jnp.float32, qmax=cfg.get("qmax"),
              wire=cfg["wire_dtype"])
    if precision == "control":
        ctl = cfg["control"]
        kw.update(dtype=jnp.dtype(ctl.get("dtype", "float32")),
                  qmax=ctl.get("qmax", cfg.get("qmax")))
    got = checks.reference_outputs(cfg, w, hist, req, **kw)
    want = frozen_reference_outputs(cfg, w, hist, req, **kw)
    assert set(got) == set(want) == set(checks.NAMES)
    for n in checks.NAMES:
        assert same(got[n], want[n]), n


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("size", ["toy", "paper"])
def test_the_seam_gives_dins_operation_counts(name, size):
    cfg = toy(name) if size == "toy" else spec.load_cell(
        {"sdim_paper_fp32": "fp32_read",
         "sdim_paper_int8_fused": "int8f_read"}[name]).config
    for C in (1, 16, 128):
        assert shapes.request_flops(C, cfg) == frozen_request_flops(C, cfg)


TOY_TOWER = '''
"""A tower for the test: the masked mean of the window through a
projection of width toy_k."""
import jax
import jax.numpy as jnp


def config_kwargs(cfg):
    return {"gru_dim": cfg["toy_k"]}


def head_in(cfg):
    return 4 * cfg["embed_dim"] + cfg["toy_k"] + cfg["ctx_dim"]


def params(key, cfg):
    return {"toy_proj": jax.random.normal(
        key, (2 * cfg["embed_dim"], cfg["toy_k"]))}


def short_interest(w, q, seq, mask, *, cfg, dtype):
    m = mask[:, None].astype(dtype)
    mean = jnp.sum(seq.astype(dtype) * m, 0) / jnp.maximum(jnp.sum(m), 1.0)
    out = mean @ w["toy_proj"].astype(dtype)
    return jnp.broadcast_to(out, (q.shape[0], cfg["toy_k"]))


def flops(C, cfg):
    return 7 * C
'''


def test_a_new_tower_is_found_by_its_arch(tmp_path, monkeypatch):
    din = toy("sdim_paper_fp32")
    key = weights.model_key(11)
    w_din = weights.make(din, key)
    (tmp_path / "towers").mkdir()
    (tmp_path / "towers" / "toy_mean.py").write_text(TOY_TOWER)
    monkeypatch.setattr(spec, "BENCH_DIR", str(tmp_path))
    cfg = dict(din, arch="toy_mean", toy_k=5)

    mc = harness.model_config(cfg)
    assert (mc.arch, mc.gru_dim) == ("toy_mean", 5)

    w = weights.make(cfg, key)
    assert w["toy_proj"].shape == (16, 5)
    # the tower's key is its own: every shared weight is DIN's
    for k in ("item_emb", "cat_emb", "interest"):
        assert all(same(a, b) for a, b in zip(jax.tree.leaves(w[k]),
                                              jax.tree.leaves(w_din[k])))
    head_in = 2 * 16 + 5 + 4
    assert w["head"]["fc0"]["w"].shape == (head_in, 32)

    dims = (head_in, 32, 16, 1)
    mlp = 2 * 16 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    assert shapes.tower_flops(16, cfg) == 7 * 16 + mlp

    hist, req = inputs(cfg)
    got = checks.reference_outputs(cfg, w, hist, req, dtype=jnp.float32,
                                   qmax=None, wire=cfg["wire_dtype"])
    for r in range(len(req["slot"])):
        q = reference.embed(w, req["cand_items"][r], req["cand_cats"][r],
                            cfg["n_items"], cfg["n_cats"], jnp.float32)
        seq = reference.embed(w, req["hi"][r], req["hc"][r], cfg["n_items"],
                              cfg["n_cats"], jnp.float32)
        m = req["hm"][r][:, None]
        short = np.sum(seq * m, 0) / max(m.sum(), 1.0) @ w["toy_proj"]
        x = np.concatenate([q, np.broadcast_to(short, (16, 5)),
                            got["interest"][r], req["ctx"][r]], axis=-1)
        np.testing.assert_allclose(
            got["scores"][r], reference.head(w, jnp.asarray(x), jnp.float32),
            rtol=1e-4, atol=1e-5)


def test_an_arch_without_a_tower_names_the_missing_file():
    cfg = dict(toy("sdim_paper_fp32"), arch="no_such_tower")
    missing = r"towers/no_such_tower\.py"
    with pytest.raises(FileNotFoundError, match=missing):
        weights.make(cfg, weights.model_key(1))
    with pytest.raises(FileNotFoundError, match=missing):
        shapes.request_flops(128, cfg)
    with pytest.raises(FileNotFoundError, match=missing):
        harness.model_config(cfg)
