"""Operations and bytes the served algorithm needs, from its shapes.

These count what the mathematics requires, not what a kernel happens to
issue, so a share of the roofline built on them cannot pass 100% unless
the time leaves out part of the work. Widths come from a configuration
file (``configs/*.json``): ``e`` is the behaviour width (2 x embed_dim),
``m`` the number of hash functions, ``G = m / tau`` signature groups and
``U = 2**tau`` buckets per group.
"""
from __future__ import annotations


def widths(cfg: dict) -> dict:
    e = 2 * cfg["embed_dim"]
    G = cfg["m"] // cfg["tau"]
    return {"e": e, "m": cfg["m"], "G": G, "U": 1 << cfg["tau"],
            "s": cfg["short_len"], "ctx": cfg["ctx_dim"],
            "mlp": tuple(cfg["mlp_hidden"])}


def sdim_read_flops(B: int, C: int, cfg: dict, dequant: bool = False) -> int:
    """One SDIM read of B users' tables by C candidates each: SimHash
    projection of every candidate (2·m·e), the gather-and-sum of its G
    buckets written as one (G·U)-wide one-hot product (2·G·U·e), and the
    l2 normalisation of every bucket row (3·G·U·e per user). ``dequant``
    adds the product by the per-row scale (G·U·e per user)."""
    w = widths(cfg)
    rows = w["G"] * w["U"] * w["e"]
    per_user = 3 * rows + (rows if dequant else 0)
    return B * C * 2 * (w["m"] * w["e"] + rows) + B * per_user


def sdim_read_bytes(B: int, C: int, cfg: dict, table_itemsize: int,
                    scales: bool = False) -> int:
    """HBM bytes one SDIM read must move: the candidates (fp32) in, the
    B tables in their stored or wire dtype (plus fp32 per-row scales for a
    quantized store), the (m, e) projection once, and the fp32 interest
    vectors out."""
    w = widths(cfg)
    rows = w["G"] * w["U"]
    table = B * rows * w["e"] * table_itemsize
    scale = B * rows * 4 if scales else 0
    q_out = 2 * B * C * w["e"] * 4
    return table + scale + q_out + w["m"] * w["e"] * 4


def tower_flops(C: int, cfg: dict) -> int:
    """Per request: DIN short-term target attention of C candidates over
    the recent window (scores and weighted sum, 2·2·s·e each) and the MLP
    head on [target, short, long, ctx] (3·e + ctx inputs)."""
    w = widths(cfg)
    attn = 2 * 2 * C * w["s"] * w["e"]
    dims = (3 * w["e"] + w["ctx"], *w["mlp"], 1)
    mlp = 2 * C * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return attn + mlp


def request_flops(C: int, cfg: dict) -> int:
    """Model operations of one served request of C candidates."""
    quantized = cfg.get("qmax") is not None
    return tower_flops(C, cfg) + sdim_read_flops(1, C, cfg, dequant=quantized)


def least_time(B: int, C: int, cfg: dict, peaks, table_itemsize: int,
               quantized: bool = False) -> float:
    """Roofline time of one SDIM read on a chip with ``peaks``: the larger
    of its operations over the bf16 peak and its bytes over HBM bandwidth.
    ``quantized`` adds the dequantisation and the per-row scales."""
    flops = sdim_read_flops(B, C, cfg, dequant=quantized)
    nbytes = sdim_read_bytes(B, C, cfg, table_itemsize, scales=quantized)
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes)
