"""The comparison that decides ``correct``.

After the window, a sample of the requests the program finished, drawn
from the seed, is run through the plain reference (``reference.py``) with
weights and histories made again from the seed. Three numbers are
compared, each against its limit from the configuration file:

* ``tables``: the BSE tables of the sampled users as the store holds
  them after ``ingest_histories`` (``sdim_bucket``; dequantised for an
  int8 store);
* ``interest``: the long-term interest vectors of the sampled requests
  (what ``sdim_query`` makes of the fetched tables, or what
  ``sdim_fused_serve`` returns);
* ``scores``: the scores the window served.

Each is the largest absolute gap over the sample, as a share of the
reference's largest magnitude (which must be nonzero).
"""
from __future__ import annotations

import numpy as np

NAMES = ("tables", "interest", "scores")


def gap(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} against reference {want.shape}")
    scale = float(np.max(np.abs(want)))
    if scale == 0.0:
        raise ValueError("the reference is all zeros")
    return float(np.max(np.abs(got - want))) / scale


def sample(n: int, seed: int, k: int) -> np.ndarray:
    """k request indices out of n, drawn from the seed, sorted."""
    rng = np.random.default_rng([int(seed), 3])
    return np.sort(rng.choice(n, size=min(k, n), replace=False))


def reference_outputs(cfg: dict, w: dict, hist: dict, req: dict, *,
                      dtype, qmax, wire) -> dict:
    """Reference tables of the sampled users and, for the sampled
    requests, the interest as sent and the scores. ``hist`` holds the
    users' (U, L) histories; ``req`` the requests' inputs and ``slot``,
    each request's row in ``hist``."""
    import jax.numpy as jnp

    from chipbench import reference, spec

    # all of the configuration, so that its tower reads any key
    rc = spec.frozen(cfg)
    tables = reference.user_tables(
        w, jnp.asarray(hist["items"]), jnp.asarray(hist["cats"]),
        jnp.asarray(hist["mask"]), cfg=rc, dtype=dtype, qmax=qmax)
    sent, scores = reference.serve(
        w, tables[jnp.asarray(req["slot"])], jnp.asarray(req["cand_items"]),
        jnp.asarray(req["cand_cats"]), jnp.asarray(req["ctx"]),
        jnp.asarray(req["hi"]), jnp.asarray(req["hc"]),
        jnp.asarray(req["hm"]), cfg=rc, dtype=dtype, wire=wire)
    return {"tables": np.asarray(tables, np.float32),
            "interest": np.asarray(sent, np.float32),
            "scores": np.asarray(scores, np.float32)}


def compare(got: dict, want: dict, limits: dict) -> dict:
    """{name: {"value": gap, "limit": limit}} for every compared number."""
    return {n: {"value": gap(got[n], want[n]), "limit": limits[n]}
            for n in NAMES}


def passed(result: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in result.values())
