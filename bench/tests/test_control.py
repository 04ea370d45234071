"""The controls come out not correct: the fit reference computed in
bfloat16, and the scores computed in three bf16 passes, each put in the
program's place, fail a limit of the cell they stand for."""

import jax
import jax.numpy as jnp

import common
import fit_driver
import serve_driver
import tiny


def _fails(numbers, limits):
    return any(not v <= limits[k] for k, v in numbers.items())


def test_bf16_fit_control_fails(tmp_path):
    root = tiny.make_root(tmp_path)
    cell = common.load_cell("tiny.wave", root)
    run = fit_driver.FitRun(cell, 5, common.Phases(common.CompileClock()),
                            jax.devices()[:1], 1.0)
    run.release()
    ref = run.reference()
    sound = fit_driver.fit_numbers(run.theta0, run.readings(), ref)
    control = fit_driver.fit_numbers(run.theta0,
                                     run.reference(jnp.bfloat16), ref)
    assert not _fails(sound, cell.limits), sound
    assert _fails(control, cell.limits), control


def test_three_pass_score_control_fails(tmp_path):
    root = tiny.make_root(tmp_path)
    cell = common.load_cell("tiny.serve", root)
    run = serve_driver.ServeRun(cell, 5, common.Phases(common.CompileClock()),
                                jax.devices()[:1], 1.0)
    run.window(1.0, common.NullSpan)
    run.release()
    assert not _fails(run.numbers("program"), cell.limits)
    assert _fails(run.numbers("control"), cell.limits)
