"""A run with the timed path broken underneath has to come out not
correct, and so has the control. Each test drives the rest of a run
(set-up, the closed-loop window, the check) at a small size on the CPU,
without the harness's look for a TPU."""
import time

import pytest

from bench import control, harness
from repro.backend import executors, jax_mesh

SMALL = {"geo": {"n_files": 4, "n_seeds": 400, "clones_per_seed": 10},
         "ptf": {"n_files": 6, "cells_per_file_mean": 3000}}


def run(cell, seed=20261016, **kw):
    """One small run of ``cell`` on the CPU."""
    over = SMALL[cell.split(".")[0]]
    return harness.run_cell(cell, seed, 1.0, False, time.perf_counter(),
                            platform="cpu", config_override=over, **kw)


@pytest.mark.parametrize("cell", ["ptf.ptf2", "geo.sweep"])
def test_sound_run_is_correct(cell):
    r = run(cell)
    assert r["correct"] and r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", ["ptf.ptf2", "geo.sweep"])
def test_control_is_not_correct(cell):
    r = run(cell, answer=control.strict_count)
    assert not r["correct"]
    assert r["checks"]["wrong_answers"]["value"] > 0


def _answer_altered(monkeypatch):
    orig = executors.PallasJoinExecutor.dispatch

    def dispatch(self, batch, eps, arrays=None):
        return orig(self, batch, eps, arrays=arrays).at[0].add(1)
    monkeypatch.setattr(executors.PallasJoinExecutor, "dispatch", dispatch)


def _half_left_out(monkeypatch):
    orig = executors.PallasJoinExecutor.iter_batches

    def iter_batches(self, tasks, eps, by_node=False):
        batches, stats = orig(self, tasks, eps, by_node=by_node)
        return batches[::2], stats
    monkeypatch.setattr(executors.PallasJoinExecutor, "iter_batches",
                        iter_batches)


def _state_unchanged(monkeypatch):
    orig = jax_mesh.JaxMeshBackend.execute
    last = {}

    def execute(self, query, report):
        e = orig(self, query, report)
        if "matches" in last:
            e.matches = last["matches"]
        last["matches"] = e.matches
        return e
    monkeypatch.setattr(jax_mesh.JaxMeshBackend, "execute", execute)


@pytest.mark.parametrize("fault", [_answer_altered, _half_left_out,
                                   _state_unchanged])
@pytest.mark.parametrize("cell", ["ptf.ptf2", "geo.sweep"])
def test_broken_path_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    r = run(cell)
    assert not r["correct"]
