"""A run with its timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run of a tiny cell (``tiny.py``) with one fault planted in the program:
a step that returns its state unchanged, half of the ratings left out of
every gradient (the rest counted twice), and a served answer altered
where it is produced.  A one-chip cell has no exchange between chips to
leave out."""

import pytest

import calibrate
import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def test_sound_runs_are_correct(root):
    for cell in ("tiny.wave", "tiny.serve"):
        res, _, err = tiny.run_cell(root, cell)
        assert res["correct"], err[-2000:]
        assert list(res)[-1] == "checks"


def test_wave_state_unchanged(root, monkeypatch):
    from repro.core import waves

    monkeypatch.setattr(waves, "wave_step",
                        lambda problem, state, tables, **kw: state)
    res, _, _ = tiny.run_cell(root, "tiny.wave")
    assert not res["correct"]
    assert res["checks"]["change_step1"]["value"] == pytest.approx(1.0)


def test_wave_half_the_ratings(root):
    with calibrate.half_ratings():
        res, _, _ = tiny.run_cell(root, "tiny.wave")
    assert not res["correct"]


def test_served_answer_altered(root, monkeypatch):
    from repro.serving import engine

    orig = engine.ServingEngine._execute
    calls = {"n": 0}

    def altered(self, req):
        items, scores = orig(self, req)
        calls["n"] += 1
        if calls["n"] == 20:          # one answer of the window
            items = items.copy()
            items[0, 0] = (items[0, 0] + 1) % self.num_items
        return items, scores

    monkeypatch.setattr(engine.ServingEngine, "_execute", altered)
    res, _, _ = tiny.run_cell(root, "tiny.serve")
    assert not res["correct"]
