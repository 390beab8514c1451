"""``correct`` at a small size on the CPU: a sound run passes; the timed
path broken underneath fails; the control (the reference in float8) fails.
The harness's look for a chip is skipped; everything else of a run is
driven as on the chip, with the cells' own limits."""

import argparse
import time

import pytest

import small
import bench
import run

TRAIN = "qwen3-1.7b-ketlin.train-8x1024"
SERVE = "qwen3-1.7b.serve-chat"
SEED = 6_000_000_007


def _run(cell, kind, fault=None, seconds=0.3, tmp=None):
    args = argparse.Namespace(workload=cell, seed=SEED, seconds=seconds, trace=0)
    return run.run_cell(args, require_tpu=False, t_start=time.perf_counter(),
                        overrides=small.overrides(cell, kind), fault=fault,
                        out_dir=str(tmp), compile_cache=False)


def test_train_sound_run_is_correct(tmp_path):
    res = _run(TRAIN, "ket", tmp=tmp_path)
    assert res["correct"], res["compared"]
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_caught(fault, tmp_path):
    res = _run(TRAIN, "ket", fault=fault, tmp=tmp_path)
    assert not res["correct"], res["compared"]


def test_train_control_fails():
    """The reference computed in float8, put in the program's place."""
    import generator as TR
    from drivers import train as D
    cell = bench.resolve(TRAIN)
    o = small.overrides(TRAIN, "ket")
    conf = {**cell.config, **o["config"]}
    wl = {**cell.workload, **o["workload"]}
    data = TR.MarkovBatches({**cell.traffic, **o["traffic"]}, conf["vocab_size"], SEED)
    key = D.seed_key(SEED)
    ref = D.reference_steps(cell.reference, conf, wl, data, key)
    ctl = D.reference_steps(cell.reference, conf, wl, data, key, quant="fp8")
    checks = D.compare(wl, ctl["losses"], ctl["g1"], ctl["dp"], ref)
    assert not all(c.ok for c in checks), checks


def test_serve_sound_run_is_correct(tmp_path):
    res = _run(SERVE, "dense", seconds=3.0, tmp=tmp_path)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_serve_fault_is_caught(fault, tmp_path):
    res = _run(SERVE, "dense", fault=fault, seconds=3.0, tmp=tmp_path)
    assert not res["correct"], res["compared"]


def test_serve_control_fails():
    """Teacher-forced on prompts with continuations, the float8 reference's
    first choice at some position lies below the float32 reference's best
    by more than the limit."""
    import numpy as np
    from drivers import serve_open as D
    cell = bench.resolve(SERVE)
    o = small.overrides(SERVE, "dense")
    conf = {**cell.config, **o["config"]}
    rng = np.random.default_rng(0)
    sample = [(rng.integers(0, conf["vocab_size"], 8).tolist(),
               rng.integers(0, conf["vocab_size"], 40).tolist()) for _ in range(4)]
    gap, n = D.served_gap(cell.reference, conf, D.seed_key(SEED), sample,
                          o["workload"]["max_len"], quant="fp8", control=True)
    assert n == 160
    assert gap > o["workload"]["limits"]["serve_gap"], gap
