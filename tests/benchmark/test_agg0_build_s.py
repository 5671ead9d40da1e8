"""The reader of the ``agg0.build`` program span (PR 26), beside PR 25's
program-span readers in ``test_scopered.py``: first build of the process,
nothing on a program without the span or without span tables (a parent
commit), and a manifest entry that keeps the rules."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import manifest  # noqa: E402

NAME = "agg0_build_s"


def _reader():
    return manifest.load_module(os.path.join(BENCH, "layer_metrics",
                                             NAME + ".py"))


@pytest.mark.parametrize("durations,want", [
    (None, None),                   # the program never opened the span
    ([2.5], 2.5),
    ([2.5, 0.7], 2.5),              # a second data set: the first build
])
def test_reader_reads_the_first_build(monkeypatch, durations, want):
    from sgcn_tpu.obs import tracing

    spans = {"step.dispatch": {"count": 1, "total_s": 1.0, "parent": None,
                               "durations": [1.0]}}
    if durations is not None:
        spans["agg0.build"] = {"count": len(durations),
                               "total_s": sum(durations), "parent": "step",
                               "durations": durations}
    monkeypatch.setattr(tracing, "_spans", spans)
    for run in ({"trace": {}}, {"trace": {"epochs": 3}}):
        assert _reader().read(run) == want


def test_reader_reads_nothing_on_a_program_without_span_tables(monkeypatch):
    from sgcn_tpu.obs import tracing

    monkeypatch.delattr(tracing, "span_totals")
    assert _reader().read({"trace": {"epochs": 1}}) is None


def test_manifest_entry_keeps_the_rules():
    b = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = b["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "s", "better": "lower",
                     "source": "program_span", "layer": "step_program",
                     "moves": "setup_s"}
    with open(os.path.join(ROOT, "PERF.md")) as fh:
        perf = fh.read()
    assert f"`{NAME}`" in perf and "| `step_program` " in perf
    for w in b["workloads"]:        # no ``workloads`` key: every cell
        assert NAME in [n for n, _, _ in manifest.resolve(w["name"]).per_layer]


def test_the_trainer_opens_the_span_the_reader_reads():
    import numpy as np

    from sgcn_tpu.io.datasets import er_graph
    from sgcn_tpu.obs import tracing
    from sgcn_tpu.parallel import build_comm_plan
    from sgcn_tpu.prep import normalize_adjacency
    from sgcn_tpu.train import FullBatchTrainer, make_train_data

    tracing.reset_spans()
    n = 48
    plan = build_comm_plan(normalize_adjacency(er_graph(n, 6, seed=0)),
                           np.zeros(n, np.int64), 1)
    tr = FullBatchTrainer(plan, fin=6, widths=[4, 3], seed=0)
    rng = np.random.default_rng(0)
    data = make_train_data(plan, rng.standard_normal((n, 6)),
                           rng.integers(0, 3, n))
    tr.step(data)
    tr.step(data)
    got = _reader().read({"trace": {}})
    assert got == tracing.span_totals()["agg0.build"]["durations"][0] > 0
    assert tracing.span_totals()["agg0.build"]["count"] == 1
