#!/usr/bin/env python
"""Chip smoke test: the decoupled SDIM serving path on a TPU at the paper's
full widths (``configs/sdim_paper.FULL``: 10M x 64 item table, behaviour
dim d=128, m=48, tau=3, L=1024, MLP 1024-512-256; weights random from a
seed), driven through the entry points a user calls.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded table store over 4 chips

One chip: ``CTRServer.build(..., "decoupled")`` with ``backend="auto"``
must resolve to the compiled Pallas kernels. A few dozen users' L=1024
histories are ingested (``sdim_bucket``), a batch of events is folded in
(``sdim_update`` on the fp32 store), and bursts of C=128 candidates are
served through the two-dispatch deployment (``sdim_query``) and the
``--fused-serve`` one (``sdim_fused_serve``), on fp32 and int8 tables. Every
phase is compared with the same servers built on ``backend="xla"`` from the
same params and data: the tables, the interest vectors each kernel returns,
and the scores.

Four chips: a ``ShardedTableStore`` over a 4-device mesh (ingest, event
fold, fused serve; fp32 and int8), compared with the one-device store.

Earlier lines report widths, bytes, errors and wall times for information.
The last line is the JSON verdict ``{"ok": true, "device": {...}}``; it is
printed only when every phase passed. Without a TPU the script exits
nonzero before doing anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_USERS = 48          # users ingested and served
BURST = 8             # requests per serving burst
N_CANDIDATES = 128    # C per request
EVENT_ROWS = 64       # rows of the folded event batch (users repeat)
EVENTS_PER_ROW = 8
# Both servers see the same params and data and hash at full fp32
# precision, so they bucket identically. On a TPU v5e and in CPU interpret
# mode the kernels' tables, interest vectors and scores equal the XLA
# server's exactly; RTOL leaves room for fp32 summation order only. A wrong
# bucket in one of the G groups moves an interest vector by up to 2/G of
# its norm, so the interest check rejects it even where the MLP's scores
# would not show it. Sharding changes nothing: the same kernels read the
# same rows.
RTOL = 1e-5


def check(name: str, got: list, want: list, tol: float) -> None:
    """Each array of ``got`` must agree with its reference in ``want`` to
    ``tol`` times the reference's largest magnitude, which must be nonzero."""
    import numpy as np

    err = ref_max = 0.0
    for g, w in zip(got, want):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        scale = float(np.max(np.abs(w)))
        if scale == 0.0:
            raise AssertionError(f"{name}: the reference is all zeros")
        err = max(err, float(np.max(np.abs(g - w))) / scale)
        ref_max = max(ref_max, scale)
    print(f"  {name}: max error {err:.3e} of the reference's max "
          f"(largest reference magnitude {ref_max:.3e}; tolerance {tol:.3g})")
    if not err <= tol:
        raise AssertionError(f"{name}: error {err} above tolerance {tol}")


def event_batch(cfg, users: list, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    rows = [users[i] for i in rng.integers(0, len(users), EVENT_ROWS)]
    shape = (EVENT_ROWS, EVENTS_PER_ROW)
    return (rows, rng.integers(0, cfg.n_items, shape).astype(np.int32),
            rng.integers(0, cfg.n_cats, shape).astype(np.int32))


def ingest(server, requests, events) -> None:
    import numpy as np

    bse = server.bse
    bse.ingest_histories(
        [r[0] for r in requests],
        *(np.concatenate([np.asarray(r[1][k]) for r in requests])
          for k in ("hist_items", "hist_cats", "hist_mask")))
    if events is not None:
        bse.ingest_events(*events)


def user_rows(server, users):
    store = server.bse.store
    return store.rows(store.slots(users))


def interests(server, requests) -> list:
    """The (B, C, d) long-term interest of each burst, as the server's
    scores consume it: ``engine.query`` (``sdim_query``) over
    ``fetch_many``'s tables on the two-dispatch deployment,
    ``serve_candidates`` (``sdim_fused_serve``) on the fused one."""
    import jax.numpy as jnp

    params, bse = server.params, server.bse
    out = []
    for lo in range(0, len(requests), BURST):
        burst = requests[lo:lo + BURST]
        users = [r[0] for r in burst]
        target_e = server._embed_targets(
            params, jnp.stack([r[2] for r in burst]),
            jnp.stack([r[3] for r in burst]))
        if server.fused:
            out.append(bse.serve_candidates(users, target_e))
        else:
            out.append(server.model.engine.query(
                target_e, bse.fetch_many(users),
                R=params["interest"]["buffers"]["R"]))
    return out


def serve(server, requests) -> tuple[list, float, float]:
    """Scores of every request, the first burst's wall time (it compiles)
    and the mean wall time of the others."""
    import numpy as np

    from repro.launch.serve import serve_requests

    times, scores = [], []
    for lo in range(0, len(requests), BURST):
        t0 = time.perf_counter()
        scores += serve_requests(server, requests[lo:lo + BURST], BURST)
        times.append(time.perf_counter() - t0)
    return scores, times[0], float(np.mean(times[1:]))


def run_one_chip(cfg, backend: str) -> None:
    """Every single-device phase on ``backend`` against ``backend="xla"``."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import build_ctr_server, synthetic_requests

    on_chip = jax.devices()[0].platform == "tpu"
    requests = synthetic_requests(cfg, N_USERS, N_CANDIDATES)
    users = [r[0] for r in requests]
    events = event_batch(cfg, users, seed=1)
    params = None
    for table_dtype in (jnp.float32, jnp.int8):
        name = jnp.dtype(table_dtype).name
        servers = {}
        for bk in (backend, "xla"):
            t0 = time.perf_counter()
            # the two-dispatch deployment, then the --fused-serve one
            for fused in (False, True):
                model, params, srv = build_ctr_server(
                    cfg, backend=bk, params=params, table_dtype=table_dtype,
                    fused=fused)
                servers[bk, fused] = srv
                eng = model.engine
                assert eng.backend == ("xla" if bk == "xla" else "pallas")
                assert bk == "xla" or eng.interpret == (not on_chip), \
                    (bk, eng.interpret)
            print(f"[{name}] {bk}: engine backend {eng.backend}, interpret "
                  f"{eng.interpret} (built in "
                  f"{time.perf_counter() - t0:.2f} s)")
        mine = [servers[backend, f] for f in (False, True)]
        refs = [servers["xla", f] for f in (False, True)]

        t0 = time.perf_counter()
        for srv in mine:
            ingest(srv, requests, None)
            jax.block_until_ready(srv.bse.store.data)
        print(f"[{name}] ingest of {N_USERS} histories into both stores: "
              f"{time.perf_counter() - t0:.2f} s (compile included)")
        for srv in refs:
            ingest(srv, requests, None)
        check(f"[{name}] ingest tables", [user_rows(s, users) for s in mine],
              [user_rows(s, users) for s in refs], RTOL)

        t0 = time.perf_counter()
        for srv in mine:
            srv.bse.ingest_events(*events)
            jax.block_until_ready(srv.bse.store.data)
        print(f"[{name}] event fold of {EVENT_ROWS}x{EVENTS_PER_ROW} "
              f"events into both stores: {time.perf_counter() - t0:.2f} s "
              f"(compile included)")
        for srv in refs:
            srv.bse.ingest_events(*events)
        check(f"[{name}] event-fold tables",
              [user_rows(s, users) for s in mine],
              [user_rows(s, users) for s in refs], RTOL)
        store = mine[0].bse.store
        print(f"[{name}] store {store.data.shape} {store.data.dtype}: "
              f"{store.row_nbytes()} B per user, "
              f"{mine[0].bse.table_bytes()} B served per user")

        for path, srv, ref in zip(("two-dispatch", "fused"), mine, refs):
            got, first, steady = serve(srv, requests)
            want = serve(ref, requests)[0]
            print(f"[{name}] {path} serve, {len(requests) // BURST} bursts "
                  f"of {BURST}x{N_CANDIDATES}: first {first:.2f} s "
                  f"(compile included), then {1e3 * steady:.2f} ms/burst")
            check(f"[{name}] {path} interest", interests(srv, requests),
                  interests(ref, requests), RTOL)
            check(f"[{name}] {path} scores", got, want, RTOL)


def run_sharded(cfg, backend: str, n_chips: int) -> None:
    """The row-sharded store over ``n_chips`` devices against one device."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import (build_ctr_server, build_mesh,
                                    synthetic_requests)

    mesh = build_mesh(n_chips)
    requests = synthetic_requests(cfg, N_USERS, N_CANDIDATES)
    users = [r[0] for r in requests]
    events = event_batch(cfg, users, seed=1)
    params = None
    for table_dtype in (jnp.float32, jnp.int8):
        name = jnp.dtype(table_dtype).name
        _, params, one = build_ctr_server(cfg, backend=backend, params=params,
                                          table_dtype=table_dtype, fused=True)
        model, _, sharded = build_ctr_server(
            cfg, backend=backend, params=params, table_dtype=table_dtype,
            fused=True, mesh=mesh)
        eng = model.engine
        print(f"[{name}] sharded over {sharded.bse.store.n_shards} devices "
              f"{[str(d) for d in jax.devices()[:n_chips]]}, engine "
              f"{eng.backend} (interpret {eng.interpret})")
        for srv in (sharded, one):
            ingest(srv, requests, events)
        check(f"[{name}] sharded ingest + event-fold tables",
              [user_rows(sharded, users)], [user_rows(one, users)], RTOL)
        got, first, steady = serve(sharded, requests)
        want = serve(one, requests)[0]
        print(f"[{name}] sharded fused serve: first {first:.2f} s (compile "
              f"included), then {1e3 * steady:.2f} ms/burst")
        check(f"[{name}] sharded fused interest",
              interests(sharded, requests), interests(one, requests), RTOL)
        check(f"[{name}] sharded fused scores", got, want, RTOL)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the sharded-store phase over 4 chips")
    args = p.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import sdim_paper
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    cfg = sdim_paper.FULL
    ic = cfg.interest
    print(f"device: {devices[0].device_kind} x{len(devices)}; config FULL: "
          f"{cfg.n_items} items x {cfg.embed_dim}, d={cfg.behavior_dim}, "
          f"m={ic.m}, tau={ic.tau}, L={cfg.long_len}, C={N_CANDIDATES}, "
          f"MLP {'-'.join(map(str, cfg.mlp_hidden))}")
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(cfg, backend="auto")
    else:
        run_sharded(cfg, backend="auto", n_chips=args.chips)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
