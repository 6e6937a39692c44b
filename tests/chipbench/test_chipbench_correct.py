"""The comparison that decides ``correct``, on the CPU.

A whole run of the harness (the chip check left out) at a small size,
with Pallas in interpret mode: a sound run is correct; a run whose timed
path is broken underneath, at the one seam every launch goes through
(``EngineCore._timed_call``), is not.  The control, the reference in the
precision below the program's, fails the cell's limit at the cell's own
size.  The run line itself refuses to run without a TPU, and without the
program beside it.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

CELL = "nr100.slot_bulk"


def _cell_cfg():
    return harness.cell(harness.load_benchmark(), CELL)[1]


def _tiny_nr():
    """The cell's configuration, limit included, at a small size."""
    return dict(_cell_cfg(), prbs=9, antennas=8, layers=4, rhs=6)


OPEN = {"loop": "open", "rate_per_s": 20.0, "pool": 2}
CLOSED = {"loop": "closed", "outstanding": 2, "pool": 2}


def _run(cfg=None, traffic=OPEN, seconds=0.4, seed=2 ** 31 + 7):
    bench = harness.load_benchmark()
    return harness.run_cell(bench, CELL, seed, seconds, False,
                            time.monotonic(), cfg=cfg or _tiny_nr(),
                            traffic=traffic)


def _break(monkeypatch, fault):
    """Apply ``fault(res, padded, state)`` to every launch's answers."""
    from repro.serve.core import EngineCore
    orig = EngineCore._timed_call
    state = {}

    def broken(self, fn, padded, device=None, fault_ctx=None):
        res, dt = orig(self, fn, padded, device, fault_ctx)
        return fault(np.array(res), padded, state), dt

    monkeypatch.setattr(EngineCore, "_timed_call", broken)


@pytest.mark.parametrize("traffic", [OPEN, CLOSED],
                         ids=["open_loop", "closed_loop"])
def test_sound_run_is_correct(traffic):
    res = _run(traffic=traffic)
    assert res["correct"], res["checks"]
    # every input of the pool's first pass is compared, and a sample of
    # the later requests
    compared = res["info"]["compared"]
    assert traffic["pool"] * _tiny_nr()["prbs"] <= compared <= res["attempted"]
    assert res["failed"] == 0 and res["info"]["compiles_in_window"] == 0
    # 9 jobs a request on 8 lanes: every request ends in a padded launch,
    # whose filler lanes are not counted as jobs served
    served = res["info"]["jobs_launched_in_window"]
    assert 0 < served <= res["attempted"]
    assert served < res["info"]["launches"] * _tiny_nr()["mux"]["lanes"]
    assert set(res["metrics"]) == {"solves_per_s", "setup_s"}
    c = res["checks"]["max_rel_err"]
    assert c["value"] <= c["limit"]


def _altered(res, padded, state):
    res[0, 0, 0] += 1e-3 * np.max(np.abs(res[0]))
    return res


def _half_left_out(res, padded, state):
    res[res.shape[0] // 2:] = 0.0
    return res


def _stale(res, padded, state):
    prev = state.get("prev")
    state["prev"] = res.copy()
    return prev if prev is not None else res


@pytest.mark.parametrize("fault", [_altered, _half_left_out, _stale],
                         ids=["answer_altered", "half_the_lanes_left_out",
                              "stale_answers"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    _break(monkeypatch, fault)
    res = _run()
    assert not res["correct"]
    c = res["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    cfg = _tiny_nr()
    kind = harness.job_kind(cfg)

    def control(res, padded, state):
        lanes = [tuple(p[i] for p in padded) for i in range(len(padded[0]))]
        return np.asarray(kind.control(cfg, lanes), res.dtype)

    _break(monkeypatch, control)
    res = _run(cfg)
    assert not res["correct"]


def test_control_fails_the_limit_at_the_cells_size():
    """The control's reading on the inputs one run of the cell serves
    (its whole pool of slots, at the cell's sizes) is over the limit."""
    cfg = _cell_cfg()
    bench = harness.load_benchmark()
    _, _, traffic = harness.cell(bench, CELL)
    kind = harness.job_kind(cfg)
    pool = harness.build_pool(kind, cfg, 2 ** 31 + 99, int(traffic["pool"]))
    worst = max(float(np.max(harness.rel_errors(kind.control(cfg, args),
                                                kind.reference(cfg, args))))
                for args in pool)
    assert worst > cfg["limits"]["max_rel_err"]


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    return not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_run_line_refuses_without_a_tpu():
    proc = _cli(ROOT)
    assert proc.returncode != 0 and _no_result(proc)
    assert "not a TPU" in proc.stderr


def test_run_line_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    bench = harness.load_benchmark()
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = _cli(tmp_path)
    assert proc.returncode != 0 and _no_result(proc)
    assert "cannot import the program" in proc.stderr


def test_result_line_puts_the_checks_last(capsys):
    from chipbench import run
    run.emit({"correct": True, "attempted": 1, "failed": 0, "metrics": {},
              "device": {}, "info": {"requests": 1},
              "checks": {"max_rel_err": {"value": 1e-6, "limit": 1e-5}}})
    out, err = capsys.readouterr()
    line = json.loads(out.splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.splitlines()[-1].startswith("check max_rel_err: 1e-06")
