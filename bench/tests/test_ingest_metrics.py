"""The two readers of the ingest layer, on hand-built registry
snapshots: ``ingest_s`` sums the ``ingest.*`` spans, ``padding_share``
reads its gauge, and both give ``None`` where the program recorded
neither (a program that predates them)."""

import importlib.util
import pathlib

import pytest

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run(histograms=None, gauges=None):
    return {"trace": None, "window": {"rounds": 8},
            "obs": {"histograms": histograms or {}, "gauges": gauges or {}}}


def test_ingest_s_sums_the_ingest_spans():
    hists = {"span_seconds{name=ingest.sort}": {"count": 2, "sum": 1.25},
             "span_seconds{name=ingest.upload}": {"count": 1, "sum": 0.5},
             "span_seconds{name=fit.round}": {"count": 40, "sum": 9.0},
             "ingest_append_seconds": {"count": 3, "sum": 7.0}}
    assert reader("ingest_s")(run(hists)) == pytest.approx(1.75)


def test_padding_share_reads_the_gauge():
    got = reader("padding_share")(run(gauges={"ingest_padding_share": 0.128,
                                              "ingest_free_slots": 9.0}))
    assert got == pytest.approx(0.128)


@pytest.mark.parametrize("name", ["ingest_s", "padding_share"])
def test_missing_names_read_none(name):
    other = run({"span_seconds{name=fit.round}": {"count": 4, "sum": 1.0},
                 "span_seconds{name=ingest.sort}": {"count": 0, "sum": 0.0}},
                {"ingest_free_slots": 3.0})
    assert reader(name)(other) is None
    assert reader(name)({"obs": {}}) is None
