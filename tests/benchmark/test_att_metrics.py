"""The benchmark's attention pieces (PR 27): the configuration and cell are
data, the plain reference ``reference/gat_ref.py`` follows the trainer, the
sub-scope readers and ``costmodel_att`` give hand-computed figures, the
sub-scope vocabulary is the program's, and the new cell rehearses end to end.
CPU only; nothing here describes a topology.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
import scipy.sparse as sp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import costmodel_att  # noqa: E402
import inputs  # noqa: E402
import manifest  # noqa: E402
import scopered  # noqa: E402
import scopered_att  # noqa: E402

CELL, CONFIG = "products8-gat.fullbatch", "gat-products-3x4x128"
READERS = ("att_score_s", "att_max_s", "att_norm_s", "att_agg_roofline",
           "att_dense_share")
GRAPH = {"generator": "dcsbm", "seed": 3, "avg_deg": 10, "ncomm": 5,
         "p_in": 0.8, "alpha": 2.5}


def _reader(name):
    return manifest.load_module(os.path.join(BENCH, "layer_metrics",
                                             name + ".py"))


# ------------------------------------------------------------------ manifest
def test_the_configuration_states_the_published_model_and_its_cuts():
    bench = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    cfg = manifest.read_json(os.path.join(ROOT, entry["file"]))
    assert entry["reduced"] == ["chips", "graph", "n", "training"]
    assert set(cfg["reduced"]) == set(entry["reduced"])
    model = cfg["model"]
    # every published width, and the count they give
    assert (cfg["f_in"], cfg["classes"], cfg["params"]) == (100, 47, 751574)
    assert model["heads"] == [4, 4, 4] and model["channels"] == [128, 128, 47]
    assert model["concat"] == [True, True, False] and model["slope"] == 0.2
    assert model["skip"] and model["bias"] and cfg["activation"] == "elu"
    assert cfg["widths"] == [512, 512, 47] and cfg["lr"] == 0.001
    assert cfg["dtype"] == "float32" and cfg["dropout"] == 0.0
    count = 0
    for fin, k, c, cat in zip([100, 512, 512], model["heads"],
                              model["channels"], model["concat"]):
        out = k * c if cat else c
        count += fin * k * c + 2 * k * c + out + fin * out + out
    assert count == cfg["params"]
    assert cfg["n"] == -(-2449029 // 8)
    for key in ("source", "assumed", "deployment", "describes"):
        assert cfg[key]
    cell = manifest.resolve(CELL)
    assert cell.chips == 1 == cell.traffic["k"]
    assert cell.traffic["kind"] == "fullbatch_cfg"
    assert set(READERS) <= {n for n, _, _ in cell.per_layer}
    for name in READERS:
        m = {x["name"]: x for x in bench["per_layer"]}[name]
        assert m["workloads"] == [CELL] and m["layer"] == "device_compute"
        assert m["moves"] == "epoch_s" and m["source"] == "device_trace"
    gcn_cells = ["products.fullbatch", "products.fullbatch-gp4"]
    for other in gcn_cells:
        assert not set(READERS) & {
            n for n, _, _ in manifest.resolve(other).per_layer}
    # the hoist's span does not exist on this path: its metric lists the
    # accepted cells, and every metric without a list is the new cell's too
    by_name = {x["name"]: x for x in bench["per_layer"]}
    assert by_name["agg0_build_s"]["workloads"] == gcn_cells
    resolved = {n for n, _, _ in cell.per_layer}
    assert "agg0_build_s" not in resolved
    assert {n for n, m in by_name.items() if "workloads" not in m} <= resolved


def test_the_sub_scope_vocabulary_is_the_programs():
    from sgcn_tpu.obs import tracing

    vocab = manifest.read_json(os.path.join(BENCH, "scopes_att.json"))
    assert tuple(vocab["subscopes"]) == tracing.SUBSCOPES
    assert vocab["prefix"] == tracing.PREFIX == scopered.PREFIX
    assert scopered_att.SUBSCOPES == tracing.SUBSCOPES
    # a sub-scope is no scope of the accepted vocabulary: scopered skips it
    assert not set(vocab["subscopes"]) & set(scopered.LEAVES)


# ------------------------------------------------------------- the readers
# tf_op strings as the step compiled for a v5e prints them (PR 27, sandbox)
P = "jit(per_chip)/shard_map/"
SCORE_F1 = P + "jvp(sgcn.layer1)/sgcn.agg_slots/sgcn.att_score/exp:"
SCORE_B1 = (P + "transpose(jvp(sgcn.layer1))/sgcn.agg_slots/sgcn.att_score/"
            "select_n:")
SLOTS_F1 = P + "jvp(sgcn.layer1)/sgcn.agg_slots/add:"
TAIL_B0 = P + "transpose(jvp(sgcn.layer0))/sgcn.agg_tail/scatter-add:"
MAX_F0 = P + "jvp(sgcn.layer0)/sgcn.agg_slots/sgcn.att_max/max:"
MAX_TAIL = P + "jvp(sgcn.layer0)/sgcn.agg_tail/sgcn.att_max/scatter-max:"
NORM_F2 = P + "jvp(sgcn.layer2)/sgcn.agg_halo_fold/sgcn.att_norm/div:"
PROJECT = P + "jvp(sgcn.layer2)/sgcn.dense/sgcn.att_project/dot_general:"
DENSE_F0 = P + "jvp(sgcn.layer0)/sgcn.dense/dot_general:"
ELU = P + "jvp(sgcn.layer0)/jit(elu)/select_n:"


def test_sub_scope_of_an_op_is_its_last_sub_scope_token():
    assert scopered_att.sub_of(SCORE_B1) == "att_score"
    assert scopered_att.sub_of(MAX_TAIL) == "att_max"
    assert scopered_att.sub_of(PROJECT) == "att_project"
    assert scopered_att.sub_of(SLOTS_F1) is None
    assert scopered_att.sub_of(ELU) is None and scopered_att.sub_of("") is None
    # and the accepted reduction books the same ops to their leaf scope
    assert scopered.scope_of(SCORE_B1) == ("layer1", "agg_slots", "bwd")
    assert scopered.scope_of(MAX_TAIL) == ("layer0", "agg_tail", "fwd")
    assert scopered.scope_of(NORM_F2) == ("layer2", "agg_halo_fold", "fwd")
    assert scopered.scope_of(PROJECT) == ("layer2", "dense", "fwd")


def _planes():
    """One chip, two runs of program P (0–1000 µs, 1000–2000 µs); the first
    holds the ops below, back to back."""
    us = 1e3
    spec = [(SCORE_F1, 100), (SCORE_B1, 50), (SLOTS_F1, 300), (TAIL_B0, 100),
            (MAX_F0, 40), (MAX_TAIL, 10), (NORM_F2, 20), (PROJECT, 30),
            (DENSE_F0, 150), (ELU, 100)]
    ops, t = [], 0.0
    for i, (tf_op, dur) in enumerate(spec):
        ops.append([f"fusion.{i}", t * us, dur * us, {"tf_op": tf_op}])
        t += dur
    return [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": [["P(1)", 0.0, 1000 * us, {}],
                        ["P(1)", 1000 * us, 1000 * us, {}]],
        "XLA Ops": ops}}]


def test_sub_scope_seconds_on_a_hand_built_trace(monkeypatch):
    red = scopered_att.reduce_subscopes(_planes(), runs=1, epochs=1)
    assert red == {"att_project": pytest.approx(30e-6),
                   "att_max": pytest.approx(50e-6),
                   "att_score": pytest.approx(150e-6),
                   "att_norm": pytest.approx(20e-6)}
    assert scopered_att.reduce_subscopes([], 1, 1) is None
    plain = _planes()
    for ev in plain[0]["lines"]["XLA Ops"]:        # a parent's program
        ev[3]["tf_op"] = ev[3]["tf_op"].replace("sgcn.att_", "att_")
    assert scopered_att.reduce_subscopes(plain, 1, 1) is None
    # the readers, on that table and on the accepted one
    scoped = scopered.reduce_scopes(_planes(), runs=1, epochs=1)
    monkeypatch.setitem(scopered_att._memo, "table", red)
    monkeypatch.setitem(scopered._memo, "table", scoped)
    cfg = manifest.read_json(os.path.join(
        BENCH, "configs", CONFIG + ".json"))
    run = {"trace": {"epochs": 1, "busy_s": 900e-6}, "config": cfg,
           "nnz": 1000, "chips": 1, "device_kind": "TPU v5 lite"}
    assert _reader("att_score_s").read(run) == pytest.approx(150e-6)
    assert _reader("att_max_s").read(run) == pytest.approx(50e-6)
    assert _reader("att_norm_s").read(run) == pytest.approx(20e-6)
    # dense: 30 + 150 of 900 busy µs
    assert _reader("att_dense_share").read(run) == pytest.approx(20.0)
    # agg_slots (100+50+300+40) + agg_tail (100+10) = 600 µs; least bytes
    # 2 passes · 1000 nnz · 4 B · (516 + 516 + 192) lanes over 819 GB/s
    least = 2 * 1000 * 4 * 1224 / 819e9
    assert _reader("att_agg_roofline").read(run) \
        == pytest.approx(100 * least / 600e-6)
    for name in READERS:                    # no trace: nothing, no raise
        assert _reader(name).read(dict(run, trace={})) is None
    # a configuration without a model block (the accepted cells')
    assert _reader("att_agg_roofline").read(
        dict(run, config={"widths": [128, 47]})) is None


def test_a_program_without_the_sub_scopes_reads_nothing(monkeypatch):
    monkeypatch.setitem(scopered_att._memo, "table", None)
    monkeypatch.setitem(scopered._memo, "table", None)
    run = {"trace": {"epochs": 1, "busy_s": 1.0}, "config": {}, "nnz": 1,
           "chips": 1, "device_kind": "TPU v5 lite"}
    assert [_reader(n).read(run) for n in READERS] == [None] * 5


def test_attention_cost_model_equals_a_hand_count():
    """Path 0-1-2 with self-loops: 7 nonzeros.  Two layers, 2 heads of 3
    channels then 1 head of 5: a pass gathers 2·3 + 2 = 8 and 5 + 1 = 6
    lanes a nonzero, and there are two passes a layer."""
    model = {"heads": [2, 1], "channels": [3, 5]}
    assert costmodel_att.lanes_per_pass(model) == [8, 6]
    assert costmodel_att.agg_bytes_per_epoch(7, model) == 2 * 7 * 4 * 14
    assert costmodel_att.agg_min_seconds(7, model, "TPU v5 lite") \
        == pytest.approx(784 / 819e9)
    with pytest.raises(KeyError, match="no peaks for device kind"):
        costmodel_att.agg_min_seconds(7, model, "cpu")
    assert "sgcn_tpu" not in open(costmodel_att.__file__).read().split(
        '"""')[2]


# ----------------------------------------------------------------- reference
@pytest.mark.parametrize("k", [1, 4])
def test_attention_reference_matches_the_trainer(k):
    import jax

    from sgcn_tpu.parallel import (build_comm_plan, make_mesh_1d,
                                   shard_stacked)
    from sgcn_tpu.partition import balanced_random_partition
    from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

    ref = manifest.load_module(os.path.join(BENCH, "reference", "gat_ref.py"))
    n, fin, widths = 300, 12, [16, 16, 5]
    model = {"name": "mhgat", "heads": [4, 2, 2], "channels": [4, 8, 5],
             "concat": [True, True, False], "slope": 0.2, "skip": True,
             "bias": True}
    indptr, indices, data = inputs.generate_graph(n, GRAPH)
    ahat = sp.csr_matrix((data, indices, indptr), shape=(n, n))
    feats, labels = inputs.features_and_labels(n, fin, widths[-1], seed=2)
    pv = (np.zeros(n, np.int64) if k == 1
          else balanced_random_partition(n, k, seed=0))
    plan = build_comm_plan(ahat, pv, k)
    mesh = make_mesh_1d(k, devices=jax.devices()[:k])
    args = {a: model[a] for a in ("heads", "concat", "slope", "skip", "bias")}
    tr = FullBatchTrainer(plan, fin=fin, widths=widths, mesh=mesh, seed=2,
                          model="mhgat", model_args=args, activation="elu")
    params0 = jax.tree.map(np.asarray, tr.params)
    d = make_train_data(plan, feats, labels)
    d = TrainData(**shard_stacked(mesh, vars(d)))
    got = [tr.step(d) for _ in range(3)]
    edges = ref.coo_chunks(indptr, indices, data, rows=64)
    assert edges[0].shape[0] == 5 and edges[2].sum() == indptr[-1]  # blocks
    want = ref.training_losses(params0, [(edges, feats, labels)] * 3, 0.01,
                               model)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert want[2] < want[0] and ref.RTOL <= 1e-3
    mine = tr.predict(d)
    theirs = ref.logits(jax.tree.map(np.asarray, tr.params), edges, feats,
                        "highest", model)
    rms = float((theirs.astype("float64") ** 2).mean()) ** 0.5
    assert np.abs(mine - theirs).max() / rms < 1e-4
    # a table held in bfloat16 is another result, and the reference shows it
    narrow = ref.logits(jax.tree.map(np.asarray, tr.params), edges, feats,
                        "highest", model, table_dtype="bfloat16")
    assert float(((narrow - theirs) ** 2).mean()) ** 0.5 / rms > 1e-4


# ----------------------------------------------------------------- rehearsal
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_the_attention_cell_end_to_end(trace):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000005", "--seconds", "1", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[-1].startswith("benchmark rehearsal (cpu, not a result): ")
    said = json.loads(lines[-1].split(": ", 1)[1])
    assert all(said["checks"].values()), said
    want = {0: {"epoch_s", "setup_s"}, 1: {"plan_build_s", "compile_s"}}[trace]
    assert want <= set(said["metrics"])
    # a CPU run names no device metric, the new ones included
    assert not (set(READERS) | {"peak_hbm_gb", "agg_slots_s", "dense_s"}) \
        & set(said["metrics"])
    note = json.loads(next(
        ln for ln in lines if '"setup_s"' in ln)[len("bench: "):])
    assert note["notes"]["trainer"]["model"] == "mhgat"
    assert note["notes"]["trainer"]["params"] == 751574
