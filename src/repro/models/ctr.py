"""CTR model family with a pluggable long-term-interest module.

Architectures (``CTRConfig.arch``):
  * ``din``       — the paper's own online model (Fig. 3): short-term target
                    attention + long-term interest module + MLP head.
  * ``wide_deep`` — Wide&Deep [1606.07792]: 40 sparse fields, wide linear +
                    deep MLP (1024-512-256), concat interaction.
  * ``bst``       — Behavior Sequence Transformer [1905.06874]: target item
                    appended to the short sequence, 1 transformer block.
  * ``dien``      — DIEN [1809.03672]: GRU interest extraction + AUGRU
                    interest evolution against the target.
  * ``bert4rec``  — BERT4Rec [1904.06690]: bidirectional encoder over the
                    recent sequence (encoder-only: no decode shapes).

Every arch takes ``interest.kind`` ∈ {sdim, target, avg, sim_hard, eta,
ubr4ctr, none, …} for the long-term branch — the paper's "architecture-free"
claim (§4.4) realized as a config axis. Behaviors are represented as
concat(item_emb, cat_emb) (2·embed_dim), the DIN convention.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.interest import InterestConfig, InterestModule
from repro.core.target_attention import target_attention
from repro.nn.attention import GQAttention
from repro.nn.layers import Embedding, LayerNorm, Linear, MLP
from repro.nn.module import KeyGen
from repro.nn.rnn import AUGRU, GRU

Params = Any


@dataclasses.dataclass(frozen=True)
class CTRConfig:
    arch: str = "din"
    n_items: int = 2_000_000
    n_cats: int = 10_000
    embed_dim: int = 32
    short_len: int = 16
    long_len: int = 1024
    mlp_hidden: tuple = (1024, 512, 256)
    interest: InterestConfig = InterestConfig()
    ctx_dim: int = 4
    # wide_deep
    n_sparse: int = 40
    field_vocab: int = 1_000_000
    # bst / bert4rec
    n_heads: int = 8
    n_blocks: int = 1
    # dien
    gru_dim: int = 108
    unroll_scans: bool = False  # unrolled lowering (accurate roofline counts)
    emb_init: float = 0.01      # embedding init std (benchmarks use larger)

    @property
    def behavior_dim(self) -> int:
        return 2 * self.embed_dim


# ---------------------------------------------------------------------------
# Small bidirectional encoder block (BST / BERT4Rec)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EncoderBlock:
    d_model: int
    n_heads: int

    def _attn(self):
        hd = self.d_model // self.n_heads
        return GQAttention(self.d_model, self.n_heads, self.n_heads, hd,
                           use_bias=True, causal=False)

    def init(self, key) -> Params:
        kg = KeyGen(key)
        return {
            "attn": self._attn().init(kg()),
            "ln1": LayerNorm(self.d_model).init(kg()),
            "mlp": MLP(self.d_model, [4 * self.d_model, self.d_model], "gelu").init(kg()),
            "ln2": LayerNorm(self.d_model).init(kg()),
        }

    def apply(self, params, x, mask=None):
        # post-LN (BST/BERT convention); mask (B, T) -> bidirectional pad mask
        attn_mask = None
        if mask is not None:
            B, T = mask.shape
            attn_mask = jnp.broadcast_to((mask[:, None, :] > 0), (B, T, T))
        h = self._attn().apply(params["attn"], x, mask=attn_mask)
        x = LayerNorm(self.d_model).apply(params["ln1"], x + h)
        h = MLP(self.d_model, [4 * self.d_model, self.d_model], "gelu").apply(params["mlp"], x)
        return LayerNorm(self.d_model).apply(params["ln2"], x + h)


# ---------------------------------------------------------------------------
# The CTR model
# ---------------------------------------------------------------------------
class CTRModel:
    def __init__(self, cfg: CTRConfig):
        self.cfg = cfg
        self.interest = InterestModule(
            dataclasses.replace(cfg.interest, d=cfg.behavior_dim)
        )

    # ---------------- init ----------------
    def init(self, key) -> Params:
        cfg = self.cfg
        kg = KeyGen(key)
        e = cfg.behavior_dim
        p: dict[str, Params] = {
            "item_emb": Embedding(cfg.n_items, cfg.embed_dim, cfg.emb_init).init(kg()),
            "cat_emb": Embedding(cfg.n_cats, cfg.embed_dim, cfg.emb_init).init(kg()),
            "interest": self.interest.init(kg()),
        }
        head_in = self._head_in_dim()
        p["head"] = MLP(head_in, [*cfg.mlp_hidden, 1], "relu").init(kg())

        if cfg.arch == "wide_deep":
            p["field_tables"] = {
                f"f{i}": 0.01 * jax.random.normal(kg(), (cfg.field_vocab, cfg.embed_dim))
                for i in range(cfg.n_sparse)
            }
            p["wide"] = {
                f"f{i}": jnp.zeros((cfg.field_vocab, 1)) for i in range(cfg.n_sparse)
            }
            p["wide_bias"] = jnp.zeros((1,))
        elif cfg.arch == "bst":
            kb = KeyGen(kg())
            p["pos_emb"] = 0.01 * jax.random.normal(kg(), (cfg.short_len + 1, e))
            p["blocks"] = [
                EncoderBlock(e, cfg.n_heads).init(kb()) for _ in range(cfg.n_blocks)
            ]
        elif cfg.arch == "dien":
            p["gru"] = GRU(e, cfg.gru_dim).init(kg())
            p["augru"] = AUGRU(cfg.gru_dim, cfg.gru_dim).init(kg())
            p["att_proj"] = Linear(e, cfg.gru_dim, False).init(kg())
        elif cfg.arch == "bert4rec":
            kb = KeyGen(kg())
            p["in_proj"] = Linear(e, cfg.embed_dim, True).init(kg())
            p["pos_emb"] = 0.01 * jax.random.normal(kg(), (cfg.short_len, cfg.embed_dim))
            p["blocks"] = [
                EncoderBlock(cfg.embed_dim, cfg.n_heads).init(kb())
                for _ in range(cfg.n_blocks)
            ]
        return p

    def _head_in_dim(self) -> int:
        cfg = self.cfg
        e = cfg.behavior_dim
        long_dim = 0 if cfg.interest.kind == "none" else e
        if cfg.arch == "din":
            return e + e + long_dim + cfg.ctx_dim          # target + short TA + long
        if cfg.arch == "wide_deep":
            return cfg.n_sparse * cfg.embed_dim + e + long_dim + cfg.ctx_dim
        if cfg.arch == "bst":
            return e + e + long_dim + cfg.ctx_dim          # target + seq rep + long
        if cfg.arch == "dien":
            return e + cfg.gru_dim + long_dim + cfg.ctx_dim
        if cfg.arch == "bert4rec":
            return e + cfg.embed_dim + long_dim + cfg.ctx_dim
        raise ValueError(cfg.arch)

    # ---------------- shared featurization ----------------
    def _tables(self):
        cfg = self.cfg
        return {"item_emb": Embedding(cfg.n_items, cfg.embed_dim),
                "cat_emb": Embedding(cfg.n_cats, cfg.embed_dim)}

    def pack_tables(self, params) -> Params:
        """``params`` with the item and category tables in the serving
        layout (``Embedding.pack``); every forward reads either layout."""
        return {**params, **{name: emb.pack(params[name])
                             for name, emb in self._tables().items()}}

    def n_packed_tables(self, params) -> int:
        """How many of the item and category tables ``params`` stores
        packed."""
        return sum(emb.rows_per_packed_row(params[name]) > 1
                   for name, emb in self._tables().items())

    def _embed_behaviors(self, params, items, cats):
        # hash trick: raw id spaces fold into the table (industry convention)
        tables = self._tables()
        ie = tables["item_emb"].apply(params["item_emb"], items % self.cfg.n_items)
        ce = tables["cat_emb"].apply(params["cat_emb"], cats % self.cfg.n_cats)
        return jnp.concatenate([ie, ce], axis=-1)

    def _short_slice(self, batch):
        """Most recent short_len behaviors (history is padded at the front)."""
        s = self.cfg.short_len
        return (
            batch["hist_items"][:, -s:],
            batch["hist_cats"][:, -s:],
            batch["hist_mask"][:, -s:],
        )

    # ---------------- short-term branches ----------------
    def _short_rep(self, params, batch, target_e):
        cfg = self.cfg
        items, cats, mask = self._short_slice(batch)
        seq_e = self._embed_behaviors(params, items, cats)     # (B, s, e)

        if cfg.arch in ("din", "wide_deep"):
            return target_attention(target_e, seq_e, mask)

        if cfg.arch == "bst":
            x = jnp.concatenate([seq_e, target_e[:, None, :]], axis=1)
            x = x + params["pos_emb"][None]
            m = jnp.concatenate([mask, jnp.ones((mask.shape[0], 1), mask.dtype)], axis=1)
            for bp in params["blocks"]:
                x = EncoderBlock(cfg.behavior_dim, cfg.n_heads).apply(bp, x, m)
            return x[:, -1]                                    # target-position output

        if cfg.arch == "dien":
            hs, _ = GRU(cfg.behavior_dim, cfg.gru_dim).apply(
                params["gru"], seq_e, mask=mask, unroll=cfg.unroll_scans)
            tproj = Linear(cfg.behavior_dim, cfg.gru_dim, False).apply(
                params["att_proj"], target_e
            )
            att = jax.nn.softmax(
                jnp.where(mask > 0,
                          jnp.einsum("bd,btd->bt", tproj, hs) / jnp.sqrt(1.0 * cfg.gru_dim),
                          -1e30),
                axis=-1,
            )
            _, h_T = AUGRU(cfg.gru_dim, cfg.gru_dim).apply(
                params["augru"], hs, att, mask=mask, unroll=cfg.unroll_scans)
            return h_T

        if cfg.arch == "bert4rec":
            x = Linear(cfg.behavior_dim, cfg.embed_dim, True).apply(params["in_proj"], seq_e)
            x = x + params["pos_emb"][None]
            for bp in params["blocks"]:
                x = EncoderBlock(cfg.embed_dim, cfg.n_heads).apply(bp, x, mask)
            m = mask[..., None]
            return jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)

        raise ValueError(cfg.arch)

    # ---------------- forward ----------------
    def apply(self, params, batch) -> jax.Array:
        """Pointwise CTR logits (B,)."""
        cfg = self.cfg
        target_e = self._embed_behaviors(params, batch["cand_item"], batch["cand_cat"])
        feats = [target_e]

        feats.append(self._short_rep(params, batch, target_e))

        if cfg.interest.kind != "none":
            long_e = self._embed_behaviors(params, batch["hist_items"], batch["hist_cats"])
            long_out = self.interest.apply(
                params["interest"], target_e, long_e, batch["hist_mask"],
                seq_cat=batch["hist_cats"], q_cat=batch["cand_cat"],
            )
            feats.append(long_out)

        if cfg.arch == "wide_deep":
            field_e = [
                jnp.take(params["field_tables"][f"f{i}"], batch["sparse_ids"][:, i], axis=0)
                for i in range(cfg.n_sparse)
            ]
            feats = [jnp.concatenate(field_e, axis=-1)] + feats[1:]  # concat interaction
            wide = sum(
                jnp.take(params["wide"][f"f{i}"], batch["sparse_ids"][:, i], axis=0)
                for i in range(cfg.n_sparse)
            ) + params["wide_bias"]

        feats.append(batch["ctx"].astype(target_e.dtype))
        deep = MLP(self._head_in_dim(), [*cfg.mlp_hidden, 1], "relu").apply(
            params["head"], jnp.concatenate(feats, axis=-1)
        )[..., 0]
        if cfg.arch == "wide_deep":
            deep = deep + wide[..., 0]
        return deep

    def loss(self, params, batch):
        logits = self.apply(params, batch)
        y = batch["label"]
        ll = jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
        return jnp.mean(ll), logits

    # ---------------- serving ----------------
    @property
    def engine(self):
        """The SDIM compute engine (backend dispatch lives there)."""
        assert self.cfg.interest.kind == "sdim"
        return self.interest.engine

    def encode_bse_table(self, params, user_batch):
        """BSE-server step: embed the user's long history and encode it into
        the (G, U, d) bucket table — everything candidate-independent."""
        long_e = self._embed_behaviors(
            params, user_batch["hist_items"], user_batch["hist_cats"]
        )                                                       # (1, L, e)
        R = params["interest"]["buffers"]["R"]
        return self.engine.encode(long_e, user_batch["hist_mask"], R=R)  # (1, G, U, e)

    def score_candidates(self, params, user_batch, cand_items, cand_cats, ctx,
                         sparse_ids=None, bucket_table=None):
        """One user's state vs C candidates — the CTR-server hot path.

        user_batch: dict with hist_* of shape (1, L); cand_*: (C,). Uses the
        (B=1, C, d) multi-candidate path of the interest module so SDIM
        encodes the sequence ONCE for all C candidates. ``sparse_ids`` (C,
        n_sparse) supplies wide_deep's candidate-filled field ids.
        ``bucket_table`` (1, G, U, e): if given (the decoupled-BSE deployment),
        the long branch reads buckets directly and the raw long history is
        never touched — the paper's latency-free path.

        This is the B=1 case of ``score_candidates_many``, so the per-request
        and micro-batched deployments cannot drift apart."""
        return self.score_candidates_many(
            params, user_batch, cand_items[None], cand_cats[None], ctx[None],
            sparse_ids=None if sparse_ids is None else sparse_ids[None],
            bucket_tables=bucket_table,
        )[0]

    def score_candidates_many(self, params, user_batch, cand_items, cand_cats,
                              ctx, sparse_ids=None, bucket_tables=None,
                              interest=None):
        """A micro-batch of B requests in ONE dispatch — row i of the output
        is ``score_candidates`` of request i.

        user_batch: dict with hist_* of shape (B, L); cand_*: (B, C); ctx:
        (B, C, ctx_dim). ``bucket_tables`` (B, G, U, e) is the decoupled-BSE
        deployment (one ``TableStore`` gather feeds all B long branches);
        without it the sdim path runs ONE batched ``engine.serve`` over the
        padded (B, C, d) candidate block. ``interest`` (B, C, e) injects
        PRECOMPUTED long-term interest vectors — the fused-serve deployment,
        where ``BSEServer.serve_candidates`` already ran the query inside
        the megakernel and only the C·e interest crossed the wire; the long
        branch then does no SDIM compute at all. ``sparse_ids``
        (B, C, n_sparse) supplies wide_deep's fields. Returns (B, C)
        logits."""
        cfg = self.cfg
        B, C = cand_items.shape
        e = cfg.behavior_dim
        target_e = self._embed_behaviors(params, cand_items, cand_cats)  # (B, C, e)
        tflat = target_e.reshape(B * C, e)

        def per_pair(x):  # (B, ...) user-side -> (B*C, ...) request pairs
            return jnp.reshape(
                jnp.broadcast_to(x[:, None], (B, C, *x.shape[1:])),
                (B * C, *x.shape[1:]))

        # the pair view only feeds the short-term branch (``_short_slice``
        # keeps the recent window), so broadcast just that window instead of
        # materializing (B·C, L) copies of the full history
        s = cfg.short_len
        pair = {
            "hist_items": per_pair(user_batch["hist_items"][:, -s:]),
            "hist_cats": per_pair(user_batch["hist_cats"][:, -s:]),
            "hist_mask": per_pair(user_batch["hist_mask"][:, -s:]),
            "cand_item": cand_items.reshape(B * C),
            "cand_cat": cand_cats.reshape(B * C),
            "ctx": ctx.reshape(B * C, -1),
        }
        feats = [tflat, self._short_rep(params, pair, tflat)]

        if cfg.interest.kind != "none":
            if interest is not None:
                assert cfg.interest.kind == "sdim"
                long_out = interest
            elif bucket_tables is not None:
                assert cfg.interest.kind == "sdim"
                R = params["interest"]["buffers"]["R"]
                long_out = self.engine.query(target_e, bucket_tables, R=R)
            elif cfg.interest.kind == "sdim":
                long_e = self._embed_behaviors(
                    params, user_batch["hist_items"], user_batch["hist_cats"]
                )                                                  # (B, L, e)
                R = params["interest"]["buffers"]["R"]
                long_out = self.engine.serve(
                    target_e, long_e, user_batch["hist_mask"], R=R)
            else:
                long_e = self._embed_behaviors(
                    params, user_batch["hist_items"], user_batch["hist_cats"]
                )
                long_out = self.interest.apply(
                    params["interest"], target_e, long_e,
                    user_batch["hist_mask"],
                    seq_cat=user_batch["hist_cats"], q_cat=cand_cats,
                )                                                  # (B, C, e)
            feats.append(long_out.reshape(B * C, e).astype(tflat.dtype))

        wide = None
        if cfg.arch == "wide_deep":
            assert sparse_ids is not None, \
                "wide_deep serving needs sparse_ids (B, C, n_sparse)"
            sids = sparse_ids.reshape(B * C, cfg.n_sparse)
            field_e = [
                jnp.take(params["field_tables"][f"f{i}"], sids[:, i], axis=0)
                for i in range(cfg.n_sparse)
            ]
            feats = [jnp.concatenate(field_e, axis=-1)] + feats[1:]
            wide = sum(
                jnp.take(params["wide"][f"f{i}"], sids[:, i], axis=0)
                for i in range(cfg.n_sparse)
            ) + params["wide_bias"]

        feats.append(pair["ctx"].astype(tflat.dtype))
        out = MLP(self._head_in_dim(), [*cfg.mlp_hidden, 1], "relu").apply(
            params["head"], jnp.concatenate(feats, axis=-1)
        )[..., 0]
        if wide is not None:
            out = out + wide[..., 0]
        return out.reshape(B, C)
