"""The check that decides ``correct`` fails a broken timed path and the
control, and passes a sound one.

A run is driven on the CPU at a toy size through the whole harness (the
look for a chip is skipped), with a fault planted in the program under
the timed path: a bucket table written wrong at ingest, an interest
vector altered where the kernel produces it, the long-term interest
altered inside the scoring program alone (where the two-dispatch path's
``sdim_query`` runs, which the ``interest`` number recomputes on the side
and so cannot see), a score altered where the scorer produces it. The
control, the reference itself in the precision
below the configuration's, must fail too."""
import json
import os
import sys

import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import checks, harness, spec  # noqa: E402

TOY = {"n_items": 1000, "n_cats": 50, "embed_dim": 8, "short_len": 8,
       "long_len": 32, "mlp_hidden": [32, 16], "m": 12, "tau": 2,
       "population": 1024}
MIX = {"rate_per_s": 150, "zipf_s": 1.1, "candidates": 16,
       "in_interest_share": 0.5, "max_burst": 2}
CONFIGS = ("sdim_paper_fp32", "sdim_paper_int8_fused")


def toy(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(TOY)
    return cfg


def shift_group0(t):
    """Every user's group-0 buckets moved by one: a wrong bucket."""
    return t.at[:, 0].set(jnp.roll(t[:, 0], 1, axis=1))


def plant(monkeypatch, fault):
    from repro.core.engine import SDIMEngine
    from repro.serve.ctr_server import CTRServer

    if fault == "tables":
        encode = SDIMEngine.encode
        monkeypatch.setattr(SDIMEngine, "encode",
                            lambda self, *a, **k: shift_group0(
                                encode(self, *a, **k)))
    elif fault == "interest":
        query, fused = SDIMEngine.query, SDIMEngine.serve_fused
        monkeypatch.setattr(SDIMEngine, "query",
                            lambda self, q, t, R=None: query(
                                self, q, shift_group0(t), R))
        monkeypatch.setattr(SDIMEngine, "serve_fused",
                            lambda self, *a, **k: fused(
                                self, *a, **k).at[:, 0].multiply(1.5))
    elif fault == "in_scorer":
        from repro.models.ctr import CTRModel

        many = CTRModel.score_candidates_many

        def bad_many(self, *a, bucket_tables=None, interest=None, **k):
            if bucket_tables is not None:
                bucket_tables = shift_group0(bucket_tables)
            if interest is not None:
                interest = interest.at[:, 0].multiply(1.5)
            return many(self, *a, bucket_tables=bucket_tables,
                        interest=interest, **k)

        monkeypatch.setattr(CTRModel, "score_candidates_many", bad_many)
    elif fault == "scores":
        dispatch = CTRServer._dispatch

        def bad(self, fn, *args):
            s = dispatch(self, fn, *args)
            return s.at[0, 0].add(0.1 * jnp.max(jnp.abs(s)))

        monkeypatch.setattr(CTRServer, "_dispatch", bad)


def run_once(cfg):
    cell = spec.Cell("toy", 1, cfg["name"], cfg, "toy", MIX, [], [])
    run = harness.Run(cell, 2**31 + 77, 0.5, False, log=lambda *a: None)
    run.require_chip = False
    run.setup()
    run.window()
    assert run.failed == 0 and run.errors == 0 and run.compiles.n == 0
    return run.check()


# the number each fault has to fail
CAUGHT_BY = {"tables": "tables", "interest": "interest",
             "in_scorer": "scores", "scores": "scores"}


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("fault", [None, "tables", "interest", "in_scorer",
                                   "scores"])
def test_check_fails_each_fault(monkeypatch, name, fault):
    if fault is not None:
        plant(monkeypatch, fault)
    result = run_once(toy(name))
    assert checks.passed(result) == (fault is None), result
    if fault is not None:
        caught = CAUGHT_BY[fault]
        assert result[caught]["value"] > result[caught]["limit"], result


@pytest.mark.parametrize("name", CONFIGS)
def test_control_comes_out_not_correct(name):
    import control

    for seed in (1, 2**31 + 5, 3):
        assert not checks.passed(control.readings(toy(name), MIX, seed,
                                                  1.0))
