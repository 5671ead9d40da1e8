"""The benchmark's own checks (CPU, seconds): the manifest resolves, cells are
data, inputs repeat from a seed, the trace reduction and the cost model give
hand-computed figures, the plain reference matches the trainer, and every
cell rehearses end to end.  Nothing here touches a TPU or describes a
topology, at import time or later.
"""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest
import scipy.sparse as sp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import costmodel  # noqa: E402
import inputs  # noqa: E402
import manifest  # noqa: E402
import tracered  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")     # the driver's rule
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench() -> dict:
    return manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))


# ------------------------------------------------------------------ manifest
def test_manifest_is_well_formed_and_every_cell_resolves():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in b[key]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert not set(c["reduced"]) & {"widths", "f_in", "classes"}
        # the file gives the reason for every key the manifest lists
        assert set(c["reduced"]) == set(manifest.read_json(
            os.path.join(ROOT, c["file"]))["reduced"])
        assert any(w["config"] == c["name"] for w in b["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs)) and 2 <= len(pairs) <= 24
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(pairs) // 4)
    assert all(len(x["why"]) <= 200 for x in b["configs"] + b["workloads"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] == 0.1
    with open(os.path.join(ROOT, "PERF.md")) as fh:
        perf = fh.read()
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
    for w in b["workloads"]:
        cell = manifest.resolve(w["name"])
        assert cell.chips in (1, 4) and cell.chips == cell.traffic["k"]
        got = {n for n, _, _ in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2 and cell.per_layer
        for fn in ("build", "warm", "sample", "traced", "release",
                   "first_updates", "reference_losses", "logits_pair"):
            assert callable(getattr(cell.runner, fn))
        for m in b["per_layer"]:
            assert m["source"] in SOURCES and m["moves"] in e2e
            # a layer is named as a row of PERF.md's layer table names it
            assert LAYER.match(m["layer"]) and f"| `{m['layer']}` " in perf
            if w["name"] in m.get("workloads", [w["name"]]):
                # a layer metric is reported only where what it moves is
                assert m["moves"] in got


RING = '''import numpy as np
def edges(n, rng, graph):
    src = np.arange(n)
    return src, (src + 1 + rng.integers(0, graph["reach"], size=n)) % n
'''


def test_a_cell_a_config_a_generator_and_a_metric_are_added_as_files(tmp_path):
    """A later PR adds files and manifest entries and edits no file."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: hashlib.sha256(p.read_bytes()).hexdigest()
              for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}
    cfg = manifest.read_json(os.path.join(
        BENCH, "configs", "gcn-products-2x128.json"))
    cfg.update(name="gcn-tiny", n=500, widths=[8, 3], classes=3,
               graph={"generator": "ring", "seed": 1, "reach": 5})
    (tmp_path / "benchmark/configs/gcn-tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "benchmark/generators/ring.py").write_text(RING)
    (tmp_path / "benchmark/traffic/fullbatch-t2.json").write_text(json.dumps(
        {"kind": "fullbatch", "k": 1, "trace_steps": 2}))
    (tmp_path / "benchmark/layer_metrics/warm_s.py").write_text(
        "def read(run):\n    return run['spans']['warm.step'][0]\n")
    b = _bench()
    b["configs"].append({"name": "gcn-tiny", "source": "test",
                         "file": "benchmark/configs/gcn-tiny.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny.fullbatch-t2", "config": "gcn-tiny",
                           "traffic": "fullbatch-t2", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({"name": "warm_s", "unit": "s", "better": "lower",
                           "source": "host_clock", "layer": "step_program",
                           "moves": "setup_s",
                           "workloads": ["tiny.fullbatch-t2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    copy = manifest.load_module(str(tmp_path / "benchmark/manifest.py"))
    # load_module names by path relative to ITS OWN benchmark directory
    copy.HERE, copy.ROOT = str(tmp_path / "benchmark"), str(tmp_path)
    cell = copy.resolve("tiny.fullbatch-t2")
    assert cell.config["n"] == 500 and cell.traffic["trace_steps"] == 2
    assert "warm_s" in [n for n, _, _ in cell.per_layer]
    assert "warm_s" not in [
        n for n, _, _ in copy.resolve("products.fullbatch").per_layer]
    copy_inputs = manifest.load_module(str(tmp_path / "benchmark/inputs.py"))
    indptr, indices, _ = copy_inputs.generate_graph(500, cell.config["graph"])
    # n edges, both directions, and the loops; every row its loop and its edge
    assert indptr[-1] == len(indices) == 1500 and np.all(np.diff(indptr) >= 2)
    after = {p: hashlib.sha256(p.read_bytes()).hexdigest() for p in before}
    assert after == before


# -------------------------------------------------------------------- inputs
GRAPH = {"generator": "dcsbm", "seed": 3, "avg_deg": 10, "ncomm": 5,
         "p_in": 0.8, "alpha": 2.5}


def test_the_generator_repeats_bit_for_bit_and_normalizes_like_the_program():
    from sgcn_tpu.prep import normalize_adjacency

    n, graph = 400, GRAPH
    one, two = inputs.generate_graph(n, graph), inputs.generate_graph(n, graph)
    assert all(np.array_equal(a, b) for a, b in zip(one, two))
    other = inputs.generate_graph(n, dict(graph, seed=4))
    assert not np.array_equal(one[1][:100], other[1][:100])
    indptr, indices, data = one
    ahat = sp.csr_matrix((data, indices, indptr), shape=(n, n))
    assert (ahat != ahat.T).nnz == 0 and np.all(ahat.diagonal() > 0)
    assert np.all(np.diff(indptr) >= 1)
    pattern = ahat.copy()
    pattern.data[:] = 1.0
    want = normalize_adjacency(pattern)         # strips and re-adds the loop
    assert np.array_equal(want.indices, indices)
    np.testing.assert_allclose(want.data, data, rtol=1e-6)


def test_features_and_labels_repeat_from_the_seed():
    f1, l1 = inputs.features_and_labels(300, 16, 5, seed=7)
    f2, l2 = inputs.features_and_labels(300, 16, 5, seed=7)
    f3, _ = inputs.features_and_labels(300, 16, 5, seed=8)
    assert np.array_equal(f1, f2) and np.array_equal(l1, l2)
    assert f1.dtype == np.float32 and l1.dtype == np.int32
    assert not np.array_equal(f1, f3) and set(l1) <= set(range(5))


def test_graph_cache_is_keyed_and_memory_mapped(tmp_path, monkeypatch):
    monkeypatch.setattr(inputs, "CACHE_DIR", str(tmp_path))
    a1, hit1 = inputs.load_graph(200, GRAPH)
    a2, hit2 = inputs.load_graph(200, GRAPH)
    _, hit3 = inputs.load_graph(200, dict(GRAPH, seed=9))
    assert (hit1, hit2, hit3) == (False, True, False)
    assert (a1 != a2).nnz == 0
    assert not [p for p in os.listdir(tmp_path) if ".tmp" in p]


# ----------------------------------------------------------- trace reduction
def _synthetic_planes():
    """One device, two runs of program P (0–100 µs, 200–300 µs).  Run 1: a
    while (10–90) over three ops (10–30, 30–50, 60–90), an all-reduce
    (90–100) and an async all-to-all (20–45) that compute covers but for
    nothing.  Host spans on the same clock."""
    us = 1e3
    ops = [["while.1", 10 * us, 80 * us, {}],
           ["fusion.1", 10 * us, 20 * us, {"tf_op": "jit(f)/jvp()/gather:",
                                           "hlo_category": "custom fusion"}],
           # a neighbour cut to whole ns overlaps by one; a marker op cut to
           # no time at all starts where a real op does: neither is nesting
           ["fusion.2", 30 * us - 1, 20 * us + 1,
            {"tf_op": "jit(f)/transpose(jvp())/gather:",
             "hlo_category": "custom fusion"}],
           ["custom-call.7", 30 * us - 1, 0.0, {}],
           ["dot.3", 60 * us, 30 * us, {"tf_op": "jit(f)/dot_general:"}],
           # an instruction named after the JAX primitive, not the opcode
           ["psum_invariant.4", 90 * us, 10 * us,
            {"hlo_category": "all-reduce"}],
           ["fusion.1", 200 * us, 50 * us, {}]]
    return [
        {"name": "/device:TPU:0", "lines": {
            "XLA Modules": [["P(1)", 0.0, 100 * us, {}],
                            ["tiny(2)", 150 * us, 1 * us, {}],
                            ["P(1)", 200 * us, 100 * us, {}]],
            "XLA Ops": ops,
            "Async XLA Ops": [["all_to_all.9", 20 * us, 25 * us, {}]]}},
        {"name": "/host:CPU", "lines": {"python": [
            ["bench.step.dispatch", 0.0, 8 * us, {}],
            ["bench.step.readback", 8 * us, 150 * us, {}],
            ["not.ours", 0.0, 500 * us, {}]]}},
    ]


def test_trace_reduction_gives_hand_computed_figures():
    planes = _synthetic_planes()
    red = tracered.reduce_trace(planes, runs=1)
    # window: start of run 1 to start of run 2 = 200 µs
    assert red["window_s"] == pytest.approx(200e-6)
    # leaves: 10–50, 60–100 → 80 µs; the async 20–45 lies inside compute
    assert red["busy_s"] == pytest.approx(80e-6)
    # collectives: all-reduce 10 µs + async all-to-all 25 µs = 35 µs, of
    # which only the all-reduce has no other op beside it
    assert red["collective_s"] == pytest.approx(35e-6)
    assert red["exposed_collective_s"] == pytest.approx(10e-6)
    # the compute-only union leaves the all-reduce out; one chip waits for none
    assert red["compute_s"] == pytest.approx(70e-6)
    assert red["collective_wait_s"] == 0.0
    # gaps ≥ 20 µs: 100–200 (readback covers 100–158 of it); 0–10, 50–60
    # are launch cadence
    assert red["gaps"] == {"bench.step.readback": pytest.approx(100e-6)}
    assert red["primitives"] == {"gather": pytest.approx(40e-6, rel=1e-3),
                                 "dot_general": pytest.approx(30e-6),
                                 "": pytest.approx(10e-6)}
    assert red["ops"] == {
        "fusion [custom fusion] gather": pytest.approx(20e-6),
        "fusion [custom fusion] gather (bwd)": pytest.approx(20e-6, rel=1e-3),
        "dot dot_general": pytest.approx(30e-6),
        "psum_invariant [all-reduce]": pytest.approx(10e-6)}    # no while
    # with both runs in the window it ends with the second run
    two = tracered.reduce_trace(planes, runs=2)
    assert two["window_s"] == pytest.approx(300e-6)
    assert two["busy_s"] == pytest.approx(130e-6)
    with pytest.raises(ValueError, match="window needs 3"):
        tracered.reduce_trace(planes, runs=3)
    assert tracered.reduce_trace(planes[1:], runs=1) is None   # no device


def test_interval_arithmetic():
    u = tracered.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [[0, 3], [5, 8]] and tracered.length(u) == 6
    assert tracered.overlap_len(u, [[2, 6]]) == 2
    assert tracered.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_collective_wait_is_what_a_chip_spends_beyond_the_last_to_arrive():
    """Two chips, one program with two all_to_all and a psum.  Chip 1 enters
    the first exchange 30 µs late, chip 0 the psum 5 µs late."""
    us = 1e3

    def chip(name, colls):
        ops = [["fusion.1", 0.0, 10 * us, {}]] + [
            [n, s * us, d * us, {}] for n, s, d in colls]
        return {"name": name, "lines": {
            "XLA Modules": [["P(1)", 0.0, 100 * us, {}]], "XLA Ops": ops}}

    red = tracered.reduce_trace([
        chip("/device:TPU:0", [("all_to_all.5", 10, 40), ("all_to_all.5", 60, 10),
                               ("psum.2", 85, 5)]),
        chip("/device:TPU:1", [("all_to_all.5", 40, 10), ("all_to_all.5", 60, 10),
                               ("psum.2", 80, 10)])], runs=1)
    assert [c["collective_wait_s"] for c in red["per_chip"]] \
        == [pytest.approx(30e-6), pytest.approx(5e-6)]
    assert red["collective_wait_s"] == pytest.approx(17.5e-6)
    assert red["compute_s"] == pytest.approx(10e-6)
    # blocked in a collective counts as busy: idle cannot see the wait
    assert [c["busy_s"] for c in red["per_chip"]] \
        == [pytest.approx(65e-6), pytest.approx(40e-6)]


def test_recorded_tpu_trace_is_not_double_counted():
    """``module ⊃ while ⊃ op``: the op line's durations sum to nearly twice
    the program's run; the leaf union does not."""
    planes = tracered.load_trace_json(os.path.join(
        ROOT, "bench_artifacts", "tpu_epoch.trace.json.gz"))
    dev = tracered.device_planes(planes)[0]
    ops = dev["lines"]["XLA Ops"]
    red = tracered.reduce_trace(planes, runs=1)
    assert red["window_s"] == pytest.approx(0.115182365, rel=1e-6)
    assert sum(e[2] for e in ops) * 1e-9 > 1.9 * red["window_s"]
    assert red["busy_s"] == pytest.approx(0.115162456, rel=1e-6)
    assert red["busy_s"] <= red["window_s"]
    timed = [e for e in ops if e[2] >= tracered.EPS_NS]
    assert len(timed) - len(tracered.leaf_events(ops)) == 1   # while.54
    assert red["collective_s"] == 0.0
    assert max(red["primitives"], key=red["primitives"].get) == "gather"


def test_fixture_round_trip(tmp_path):
    planes = _synthetic_planes()
    path = str(tmp_path / "f.events.json.gz")
    tracered.dump_fixture(planes, path, 0.0, 200e3)
    back = tracered.load_fixture(path)
    assert len(back[0]["lines"]["XLA Ops"]) == 6     # run 2's op is cut
    assert tracered.reduce_trace(back, runs=1)["busy_s"] == pytest.approx(80e-6)


FIXTURES = os.path.join(BENCH, "fixtures")
PRODUCTS = {"nnz": 124380591, "config": manifest.read_json(os.path.join(
    BENCH, "configs", "gcn-products-2x128.json")), "device_kind": "TPU v5 lite"}


def _recorded(cell: str) -> dict:
    """One traced step of ``cell`` on the v5e (my chip runs, PR 22;
    ``benchmark/record_fixture.py``), reduced."""
    red = tracered.reduce_trace(tracered.load_fixture(os.path.join(
        FIXTURES, cell + ".step1.events.json.gz")), 1)
    return dict(red, epochs=1)


def test_reduction_of_the_recorded_four_chip_step_is_pinned():
    """Later PRs compute the same numbers the same way."""
    red = _recorded("products.fullbatch-gp4")
    assert red["chips"] == 4
    assert red["window_s"] == pytest.approx(1.54665244875, rel=1e-9)
    assert red["busy_s"] == pytest.approx(1.54590176275, rel=1e-9)
    assert red["compute_s"] == pytest.approx(1.37465621875, rel=1e-9)
    # three chips wait in the exchange for the third, which computes longest
    assert [round(c["collective_s"], 6) for c in red["per_chip"]] \
        == [0.20328, 0.204107, 0.07527, 0.202325]
    assert [round(c["collective_wait_s"], 6) for c in red["per_chip"]] \
        == [0.162695, 0.163523, 0.034685, 0.161741]
    assert red["collective_s"] == pytest.approx(0.171245544, rel=1e-9)
    assert red["collective_wait_s"] == pytest.approx(0.130660953, rel=1e-9)
    assert red["exposed_collective_s"] == red["collective_s"]     # synchronous
    prims = red["primitives"]
    assert prims["gather"] == pytest.approx(0.648285426, rel=1e-9)
    assert prims["scatter-add"] == pytest.approx(0.5196216255, rel=1e-9)
    assert prims["all_to_all"] == pytest.approx(0.161335055, rel=1e-9)
    assert tracered.top(red["ops"], 1)[0][0] == "fusion [custom fusion] gather"
    assert red["gaps"]["bench.step.readback"] == pytest.approx(2.915668e-3)


def test_reduction_of_the_recorded_one_chip_step_is_pinned():
    red = _recorded("products.fullbatch")
    assert red["chips"] == 1 and red["per_chip"][0]["n_leaf"] == 11348
    assert red["window_s"] == pytest.approx(3.743596089, rel=1e-9)
    assert red["busy_s"] == pytest.approx(3.741327099, rel=1e-9)
    assert red["compute_s"] == red["busy_s"] and red["collective_s"] == 0.0
    assert red["primitives"]["gather"] == pytest.approx(2.533105805, rel=1e-9)
    assert red["ops"]["fusion [custom fusion] gather (bwd)"] \
        == pytest.approx(0.832692515, rel=1e-9)
    assert red["gaps"] == {"bench.step.readback": pytest.approx(2.169479e-3)}


# what each trace reader makes of the recorded steps; None: not in that cell
READINGS = {
    "device_busy_s_per_epoch": (3.741327099, 1.54590176275),
    "idle_share": (0.060609904, 0.048536179),
    # 4 · 124,380,591 rows (÷ 4 chips) over the compute-only seconds
    "agg_rows_per_s": (497522364 / 3.741327099, 124380591 / 1.37465621875),
    # 2 · nnz · 4 B · (100 + 47) lanes ÷ 819 GB/s = 0.178598 s (÷ 4 chips)
    "gather_roofline": (4.7736476, 3.2480443),
    "gather_busy_share": (67.706077, 41.935745),
    "accumulate_busy_share": (9.921775, 7.0916112),
    "scatter_busy_share": (19.703645, 33.612853),
    "dense_busy_share": (0.48432042, 0.27210393),
    "collective_s_per_epoch": (None, 0.171245544),
    "exposed_collective_share": (None, 100.0),
    "collective_wait_share": (None, 8.4479841),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_trace_readers_on_the_recorded_steps(name):
    reader = manifest.load_module(os.path.join(BENCH, "layer_metrics",
                                               name + ".py"))
    for chips, cell, want in zip((1, 4), ("products.fullbatch",
                                          "products.fullbatch-gp4"),
                                 READINGS[name]):
        got = reader.read(dict(PRODUCTS, chips=chips, trace=_recorded(cell)))
        assert got == (want if want is None else pytest.approx(want, rel=1e-6))
    assert reader.read(dict(PRODUCTS, chips=1, trace={})) is None  # no trace


# ---------------------------------------------------------------- cost model
def test_cost_model_equals_a_hand_count():
    """Path 0-1-2-3-4-5 with self-loops: 16 nonzeros, 6 rows; widths 4 → 8 →
    3: the first layer aggregates 4 lanes at least, the second 3."""
    assert costmodel.layer_dims(4, [8, 3]) == [(4, 8), (8, 3)]
    assert costmodel.agg_rows_per_epoch(16, nlayers=2) == 64
    assert costmodel.agg_bytes_per_epoch(16, 4, [8, 3]) == 2 * 16 * 4 * (4 + 3)
    assert costmodel.step_flops(16, 6, 4, [8, 3]) \
        == 2 * (2 * 16 * 4 + 2 * 16 * 3) + 3 * (2 * 6 * 4 * 8 + 2 * 6 * 8 * 3)
    roof = costmodel.roofline(16, 6, 4, [8, 3], "TPU v5 lite")
    assert roof["bound"] == "hbm"
    assert roof["min_s"] == pytest.approx(896 / 819e9)
    with pytest.raises(KeyError, match="no peaks for device kind"):
        costmodel.roofline(16, 6, 4, [8, 3], "cpu")


def test_cost_model_does_not_move_with_the_plan():
    """The same dataset under two layouts of the program's plan gives the
    same count: the reader sees nonzeros, rows and chips, never the plan."""
    import inspect

    for fn in (costmodel.agg_rows_per_epoch, costmodel.agg_bytes_per_epoch,
               costmodel.step_flops, costmodel.roofline):
        assert not {"plan", "shapes"} & set(inspect.signature(fn).parameters)
    assert "sgcn_tpu" not in open(costmodel.__file__).read().split('"""')[2]


# ----------------------------------------------------------------- reference
@pytest.mark.parametrize("k", [1, 4])
def test_reference_matches_the_trainer(k):
    import jax

    from sgcn_tpu.parallel import (build_comm_plan, make_mesh_1d,
                                   shard_stacked)
    from sgcn_tpu.partition import balanced_random_partition
    from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

    ref = manifest.load_module(os.path.join(BENCH, "reference", "gcn_ref.py"))
    n, fin, widths = 300, 12, [16, 5]
    indptr, indices, data = inputs.generate_graph(n, GRAPH)
    ahat = sp.csr_matrix((data, indices, indptr), shape=(n, n))
    feats, labels = inputs.features_and_labels(n, fin, widths[-1], seed=2)
    pv = (np.zeros(n, np.int64) if k == 1
          else balanced_random_partition(n, k, seed=0))
    plan = build_comm_plan(ahat, pv, k)
    mesh = make_mesh_1d(k, devices=jax.devices()[:k])
    tr = FullBatchTrainer(plan, fin=fin, widths=widths, mesh=mesh, seed=2)
    params0 = [np.asarray(w) for w in tr.params]
    d = make_train_data(plan, feats, labels)
    d = TrainData(**shard_stacked(mesh, vars(d)))
    got = [tr.step(d) for _ in range(3)]
    edges = ref.coo_chunks(indptr, indices, data, chunk=1000)
    assert edges[0].shape[1] == 1000 and edges[0].shape[0] > 1   # chunked
    want = ref.training_losses(params0, [(edges, feats, labels)] * 3, 0.01)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert want[2] < want[0] and ref.RTOL <= 1e-3


# ----------------------------------------------------------------- rehearsal
@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """What git would commit of the benchmark, beside links to the program;
    its own ``native/`` so that the partitioner's rebuild never rewrites the
    library other tests have loaded."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "sgcn_tpu"), root / "sgcn_tpu")
    os.makedirs(root / "native")
    for name in ("Makefile", "sgcnpart.cpp"):
        shutil.copy(os.path.join(ROOT, "native", name), root / "native")
    return root


@pytest.mark.parametrize("cell,trace", [("products.fullbatch", 0),
                                        ("products.fullbatch-gp4", 0),
                                        ("products.fullbatch-gp4", 1)])
def test_rehearsal_runs_each_cell_end_to_end(checkout, cell, trace):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "5",
         "--seconds", "1", "--trace", str(trace), "--rehearse"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 3, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[-1].startswith("benchmark rehearsal (cpu, not a result): ")
    with pytest.raises(json.JSONDecodeError):
        json.loads(lines[-1])                   # never a result line
    said = json.loads(lines[-1].split(": ", 1)[1])
    assert all(said["checks"].values()), said
    want = {0: {"epoch_s", "setup_s"}, 1: {"plan_build_s", "compile_s"}}[trace]
    assert want <= set(said["metrics"])
    # a CPU run names no device metric
    assert not {"peak_hbm_gb", "idle_share", "device_busy_s_per_epoch",
                "gather_roofline", "agg_rows_per_s",
                "collective_wait_share"} & set(said["metrics"])
    if trace:
        assert {"km1", "partition_s"} <= set(said["metrics"])
    if cell.endswith("gp4"):       # the second run finds the vector cached
        note = json.loads(next(
            ln for ln in lines if '"setup_s"' in ln)[len("bench: "):])
        assert note["notes"]["partition"]["cache_hit"] == bool(trace)
        assert note["notes"]["partition"]["function"] == "partition_graph"


def test_no_chip_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "products.fullbatch", "--seed", "1", "--seconds", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode == 2 and proc.stdout.strip() == ""
    assert "no chip, no result" in proc.stderr
