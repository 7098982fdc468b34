"""``correct`` has to come out false when it should.

Run by hand, on the CPU, at a size a test run can hold (not part of tier-1):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

* the control: the plain reference at the nearest precision below the stated
  one, put in the program's place, has to fail a limit, and the program as it
  is has to pass every one;
* the broken path: the harness's look for a chip is skipped, the rest of a run
  is driven with the timed path broken underneath, and ``correct`` has to be
  false: a solver that returns its state unchanged, half of the rows left out,
  a coefficient altered where it is produced.

The cells are BENCHMARK.json's; their rows and limits here are those of
``fixtures/cpu_cells.json``: for this size on a CPU backend (exact float32
wire and storage: ``plain.Precision.stated('cpu')``), set between the readings
in the comments below.  Every verdict is ``run.verdict``'s, the one a
benchmark run gives; at the cell's own size on the chip ``calibrate.py``
gives the same verdicts against ``limits/<workload>.json`` (PERF.md section 6).
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run  # noqa: E402  (benchmark/run.py)

SEED = 2 ** 31 + 21
MANIFEST, CELLS = run.load_json("BENCHMARK.json"), run.cpu_cells()
# program against reference, CPU, 12,288 rows, seeds 5, 7, 2**31+7 and
# 2**31+21: stats_gap <= 1.3e-5, cv gaps <= 4.8e-4 (0.5 / the fold's
# positives, where a training row outranks every validation row: PERF.md
# section 7), refit_coef_gap <= 5.6e-7, train_auroc_gap <= 5.6e-8; bfloat16
# control against reference: stats_gap >= 3.5e-3.  Half of the rows reads
# stats_gap 1.0.  The limits are in fixtures/cpu_cells.json.


def drive(workload):
    tiny = CELLS[workload]
    return run.run_cell(MANIFEST, workload, SEED, 0, False,
                        require_chip=False, rows=tiny["rows"],
                        limits=tiny["limits"])


def over(compared):
    return sorted(k for k, c in compared.items() if c["value"] > c["limit"])


def unchanged_state(mp):
    """The proximal-gradient loop makes no iteration: its state comes back
    as it went in."""
    from transmogrifai_tpu.models import solvers
    loop = solvers._fista_loop
    mp.setattr(solvers, "_fista_loop",
               lambda *a, **k: loop(*a, **dict(k, max_iter=0)))


def half_the_rows(mp):
    """The train reads the first half of the rows it was given."""
    from transmogrifai_tpu.workflow import Workflow
    read = Workflow.generate_raw_data

    def half(self):
        batch = read(self)
        return batch.take_rows(np.arange(len(batch) // 2))
    mp.setattr(Workflow, "generate_raw_data", half)


def altered_answer(mp):
    """One coefficient of every linear fit is altered where it is produced."""
    from transmogrifai_tpu.models import linear, solvers
    unscale = solvers.unscale_params

    def bent(res, mean, scale, n_classes):
        out = unscale(res, mean, scale, n_classes)
        return out._replace(coef=out.coef.at[0].add(0.01))
    mp.setattr(solvers, "unscale_params", bent)
    mp.setattr(linear, "unscale_params", bent)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct_and_control_is_not(workload):
    import jax
    from benchmark.reference import common, plain
    tiny = CELLS[workload]
    cell = run.Cell(MANIFEST, workload, tiny["rows"], tiny["limits"])
    data = cell.program.make_data(cell.rows, SEED, cell.config)
    rec = run.one_train(cell, data, "cpu")
    assert not rec["why_failed"], rec["why_failed"]
    run.drop_program_state()
    p = rec["produced"]
    ask = cell.reference.question(p)
    ref = cell.reference.reference(data, cell.config,
                                   plain.Precision.stated("cpu"), ask,
                                   seed=SEED)
    ok, sound = run.verdict(cell, [p], ref)
    assert ok, sound
    low = cell.reference.reference(data, cell.config,
                                   plain.Precision.control("cpu"), ask,
                                   seed=SEED)
    ok, control = run.verdict(
        cell, [common.as_produced(low, p, cell.config)], ref)
    assert not ok and over(control), control
    jax.clear_caches()


@pytest.mark.parametrize("fault", [unchanged_state, half_the_rows,
                                   altered_answer],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_broken_path_is_not_correct(workload, fault, monkeypatch):
    import jax
    jax.clear_caches()
    fault(monkeypatch)
    res = drive(workload)
    monkeypatch.undo()
    jax.clear_caches()
    assert res["correct"] is False, json.dumps(res["compared"])
    assert res["failed"] == 0 and over(res["compared"]), res
