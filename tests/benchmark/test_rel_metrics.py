"""The benchmark's relational pieces (PR 33): the configuration and cell are
data, the runner knows types and splits and no model, the generator gives
each relation its count of distinct pairs, the plain reference follows the
trainer and refuses a bfloat16 table, ``costmodel_rel`` and the sub-scope
readers give hand-computed figures, the sub-scope vocabulary is the
program's, every list-less metric resolves in the new cell, and the cell
rehearses end to end.  New entries are asserted by membership and relative
order, never as the manifest's tail (ROADMAP C15).  CPU only.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import costmodel_rel  # noqa: E402
import inputs  # noqa: E402
import manifest  # noqa: E402
import scopered  # noqa: E402
import scopered_rel  # noqa: E402

CELL, CONFIG = "mag-rgcn.fullbatch", "rgcn-mag-2x64"
READERS = ("rel_agg_roofline", "rel_project_s", "rel_table_s",
           "row_update_s", "row_update_roofline", "rel_rows_owned_gb")
ACCEPTED = ("products.fullbatch", "products.fullbatch-gp4",
            "products8-gat.fullbatch", "products8-deepergcn.fullbatch")


def _reader(name):
    return manifest.load_module(os.path.join(BENCH, "layer_metrics",
                                             name + ".py"))


def _config():
    return manifest.read_json(os.path.join(BENCH, "configs",
                                           CONFIG + ".json"))


# ------------------------------------------------------------------ manifest
def test_the_configuration_states_the_published_model_and_its_cuts():
    bench = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    cfg = manifest.read_json(os.path.join(ROOT, entry["file"]))
    # nothing is cut but the graph and dropout: no width, depth, row or chip
    assert entry["reduced"] == ["graph", "training"]
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "snap-stanford/ogb" in cfg["source"] and "mag/rgcn.py" in \
        cfg["source"]
    model = cfg["model"]
    counts = {t["name"]: t["count"] for t in model["types"]}
    assert counts == {"paper": 736389, "author": 1134649,
                      "institution": 8740, "field_of_study": 59965}
    assert sum(counts.values()) == cfg["n"] == 1939743
    assert [t["input"] for t in model["types"]] == ["features"] + \
        ["embedding"] * 3
    assert len(model["relations"]) == 7
    assert len({(s, d) for s, _, d in model["relations"]}) == 7
    assert (model["hidden"], model["layers"], model["label_type"]) \
        == (64, 2, "paper")
    assert (cfg["f_in"], cfg["classes"], cfg["widths"]) == (128, 349,
                                                            [64, 349])
    assert (cfg["lr"], cfg["dropout"], cfg["dtype"]) == (0.01, 0.0, "float32")
    # the leaderboard's parameter count, from these sizes alone
    emb = (counts["author"] + counts["institution"]
           + counts["field_of_study"]) * 128
    assert emb == 154_029_312 == costmodel_rel.row_owned_params(cfg)
    layer = lambda a, b: 7 * a * b + 4 * (a * b + b)        # noqa: E731
    assert (layer(128, 64), layer(64, 349)) == (90_368, 247_092)
    assert emb + layer(128, 64) + layer(64, 349) == cfg["params"] \
        == 154_366_772
    assert cfg["split"] == {"type": "paper", "train_first": 629571}
    published = {(s, d): m for s, d, m in cfg["graph"]["relations"]}
    assert published == {("author", "paper"): 7145660,
                         ("paper", "paper"): 5416271,
                         ("paper", "field_of_study"): 7505078,
                         ("author", "institution"): 1043998}
    assert sum(published.values()) == 21_111_007
    assert cfg["graph"]["types"] == counts
    assert cfg["reference"] == {"file": "rgcn_ref.py", "losses": 2}
    for key in ("source", "assumed", "deployment", "describes"):
        assert cfg[key]
    # the rehearsal: the same four types in proportion, its own count
    small = manifest.merged(cfg, cfg["rehearse"])
    tiny = {t["name"]: t["count"] for t in small["model"]["types"]}
    assert sum(tiny.values()) == small["n"] < 5000
    assert small["graph"]["types"] == tiny
    assert small["params"] == sum(
        c for n, c in tiny.items() if n != "paper") * 128 + 90_368 + 247_092
    for name, count in tiny.items():
        assert abs(count / small["n"] - counts[name] / cfg["n"]) < 2e-3


def test_the_cell_and_its_metrics_are_additions_to_the_manifest():
    bench = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    # after every accepted cell, in their order (membership and relative
    # order, not the tail: a later PR appends after this one)
    assert [c for c in cells if c in ACCEPTED + (CELL,)] \
        == list(ACCEPTED) + [CELL]
    entry = bench["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "fullbatch-typed", 1)
    assert len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = manifest.resolve(CELL)
    assert cell.chips == 1 == cell.traffic["k"]
    assert cell.traffic["kind"] == "fullbatch_typed"
    assert cell.traffic["trace_steps"] == 3
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in READERS]
    assert at == sorted(at) and at[0] > names.index("deep_rows_kept_gb")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert by_name["rel_rows_owned_gb"]["moves"] == "peak_hbm_gb"
    assert by_name["rel_rows_owned_gb"]["source"] == "program_counter"
    assert {by_name[n]["unit"] for n in ("rel_agg_roofline",
                                         "row_update_roofline")} == {"%"}
    # PERF.md's list of layers names the rows the new metrics stand in
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for name in READERS:
        row = next(ln for ln in perf.splitlines()
                   if ln.startswith(f"| `{by_name[name]['layer']}`"))
        assert f"`{name}`" in row, name
    for other in ACCEPTED:
        assert not set(READERS) & {
            n for n, _, _ in manifest.resolve(other).per_layer}


def test_every_list_less_metric_resolves_in_the_new_cell():
    bench = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = manifest.resolve(CELL)
    resolved = {n for n, _, _ in cell.per_layer}
    free = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert free <= resolved and set(READERS) <= resolved
    assert {"plan_build_s", "agg_slots_s", "agg_tail_s", "dense_s",
            "loss_opt_s", "unscoped_share", "step_dispatch_s",
            "agg_useful_share", "gather_roofline", "agg_rows_per_s"} <= free
    # the listed metrics of other cells are not this cell's
    assert not {"agg0_build_s", "att_score_s", "deep_norm_s", "km1"} \
        & resolved
    assert {n for n, _, _ in cell.end_to_end} == {"epoch_s", "peak_hbm_gb",
                                                  "setup_s"}
    # gather_roofline / agg_rows_per_s count by the GCN's rule from widths
    run = {"trace": {"compute_s": 3.0, "epochs": 3}, "config": _config(),
           "nnz": 44_161_757, "chips": 1, "device_kind": "TPU v5 lite"}
    assert _reader("agg_rows_per_s").read(run) == pytest.approx(
        2 * 2 * 44_161_757)
    assert _reader("gather_roofline").read(run) == pytest.approx(
        100 * 2 * 44_161_757 * 4 * (64 + 64) / 819e9)


def test_the_sub_scope_vocabulary_is_the_programs():
    from sgcn_tpu.obs import tracing

    vocab = manifest.read_json(os.path.join(BENCH, "scopes_rel.json"))
    assert tuple(vocab["subscopes"]) == tracing.REL_SUBSCOPES
    assert vocab["prefix"] == tracing.PREFIX == scopered.PREFIX
    assert scopered_rel.SUBSCOPES == tracing.REL_SUBSCOPES
    others = set(scopered.LEAVES)
    for name in ("scopes_att.json", "scopes_deep.json"):
        others |= set(manifest.read_json(os.path.join(BENCH, name))
                      ["subscopes"])
    assert not set(vocab["subscopes"]) & others


def test_the_scopes_file_pin():
    """The yardstick's vocabulary moves only with a PR that says so."""
    vocab = manifest.read_json(os.path.join(BENCH, "scopes_rel.json"))
    assert vocab["subscopes"] == ["rel_project", "rel_table", "row_update"]
    assert set(vocab) == {"prefix", "describes", "subscopes"}


# ------------------------------------------------------------- the generator
def test_the_generator_gives_each_relation_its_count_of_distinct_pairs():
    gen = manifest.load_module(os.path.join(BENCH, "generators",
                                            "typed_dcsbm.py"))
    graph = {"alpha": 2.0, "types": {"a": 50, "b": 70, "c": 3},
             "relations": [["a", "b", 400], ["a", "a", 300], ["b", "c", 150]]}
    src, dst = gen.edges(123, np.random.default_rng(0), graph)
    again = gen.edges(123, np.random.default_rng(0), graph)
    np.testing.assert_array_equal(src, again[0])
    lo = {"a": 0, "b": 50, "c": 120}
    hi = {"a": 50, "b": 120, "c": 123}
    at = 0
    for s, d, m in graph["relations"]:
        a, b = src[at:at + m], dst[at:at + m]
        assert ((a >= lo[s]) & (a < hi[s])).all()
        assert ((b >= lo[d]) & (b < hi[d])).all()
        assert (a != b).all()
        pairs = {(min(x, y), max(x, y)) for x, y in zip(a, b)}
        assert len(pairs) == m                  # distinct, unordered too
        at += m
    assert at == len(src) == 850
    # the hub side is skewed: some c has most of the b's
    assert np.bincount(dst[700:] - 120).max() > 50
    with pytest.raises(ValueError, match="types count"):
        gen.edges(100, np.random.default_rng(0), graph)
    # through the harness's door: one symmetric pattern with a diagonal
    indptr, indices, _ = inputs.generate_graph(
        123, dict(graph, generator="typed_dcsbm", seed=0))
    assert indptr[-1] == 2 * 850 + 123


# ---------------------------------------------------------- the cost model
def test_rel_cost_model_equals_a_hand_count():
    """Two types: ``doc`` (features, labelled) and ``tag`` (embedded); doc
    cites doc (5 pairs, symmetrised: 10 edges), doc has tag (7) and its
    reverse.  Two layers 6 -> 4 -> 3."""
    cfg = {"f_in": 6, "widths": [4, 3],
           "model": {"types": [{"name": "doc", "count": 9,
                                "input": "features"},
                               {"name": "tag", "count": 4,
                                "input": "embedding"}],
                     "relations": [["doc", "cites", "doc"],
                                   ["doc", "has", "tag"],
                                   ["tag", "of", "doc"]],
                     "label_type": "doc", "layers": 2},
           "graph": {"relations": [["doc", "doc", 5], ["doc", "tag", 7]]}}
    assert costmodel_rel.relation_edges(cfg) == {"cites": 10, "has": 7,
                                                 "of": 7}
    assert costmodel_rel.needed_types(cfg["model"]) == [{"doc", "tag"},
                                                        {"doc"}]
    passes = costmodel_rel.agg_passes(cfg)
    assert [(p["layer"], p["direction"], p["relations"], p["edges"],
             p["lanes"]) for p in passes] == [
        (0, "forward", ["cites", "has", "of"], 24, 4),
        # backward of layer 0: out of the embedded type only
        (0, "backward", ["of"], 7, 4),
        (1, "forward", ["cites", "of"], 17, 3),
        (1, "backward", ["cites", "of"], 17, 3)]
    assert costmodel_rel.agg_bytes_per_epoch(cfg) \
        == 4 * (24 * 4 + 7 * 4 + 17 * 3 + 17 * 3)
    assert costmodel_rel.agg_min_seconds(cfg, "TPU v5 lite") \
        == pytest.approx(904 / 819e9)
    assert costmodel_rel.row_owned_params(cfg) == 4 * 6
    assert costmodel_rel.row_update_min_seconds(cfg, "TPU v5 lite") \
        == pytest.approx(24 * 4 * 6 / 819e9)
    with pytest.raises(KeyError, match="no peaks for device kind"):
        costmodel_rel.agg_min_seconds(cfg, "cpu")
    for mod in (costmodel_rel, scopered_rel):       # the yardstick's own
        assert "import sgcn_tpu" not in open(mod.__file__).read()


def test_the_cells_least_count_is_the_issues():
    """ISSUE 33: ~108 M edge passes of 64 lanes = 27.6 GB an epoch."""
    cfg = _config()
    edges = costmodel_rel.relation_edges(cfg)
    assert sum(edges.values()) == 42_222_014
    assert edges["cites"] == 2 * 5416271
    assert edges["writes"] == edges["rev_writes"] == 7145660
    passes = costmodel_rel.agg_passes(cfg)
    assert [p["edges"] for p in passes] == [41_178_016, 15_694_736,
                                            25_483_280, 25_483_280]
    assert "affiliated_with" not in passes[0]["relations"]
    assert passes[1]["relations"] == ["writes", "rev_has_topic",
                                      "rev_affiliated_with"]
    assert {p["lanes"] for p in passes} == {64}
    assert costmodel_rel.agg_bytes_per_epoch(cfg) == pytest.approx(27.6e9,
                                                                   rel=2e-3)


# ------------------------------------------------------------- the readers
P = "jit(per_chip)/shard_map/"
PROJ_F = P + "jvp(sgcn.layer0)/sgcn.dense/sgcn.rel_project/dot_general:"
PROJ_B = P + "transpose(jvp(sgcn.layer1))/sgcn.dense/sgcn.rel_project/" \
    "dot_general:"
TABLE_F = P + "jvp(sgcn.layer0)/sgcn.dense/sgcn.rel_table/concatenate:"
TABLE_B = P + "transpose(jvp(sgcn.layer0))/sgcn.dense/sgcn.rel_table/" \
    "concatenate:"
FEATS = P + "sgcn.dense/sgcn.rel_table/gather:"
SLOTS_F = P + "jvp(sgcn.layer0)/sgcn.agg_slots/add:"
SLOTS_B = P + "transpose(jvp(sgcn.layer0))/sgcn.agg_slots/add:"
TAIL_B = P + "transpose(jvp(sgcn.layer1))/sgcn.agg_tail/scatter-add:"
ROWS = P + "sgcn.optimizer/sgcn.row_update/sqrt:"
SHARED = P + "sgcn.optimizer/mul:"
LOSS = P + "jvp(sgcn.loss)/reduce_sum:"
PSUM = P + "transpose(jvp(sgcn.layer0))/sgcn.dense/sgcn.rel_project/" \
    "psum_invariant:"


def test_sub_scope_of_an_op_is_its_last_sub_scope_token():
    assert scopered_rel.sub_of(PROJ_B) == "rel_project"
    assert scopered_rel.sub_of(TABLE_F) == "rel_table"
    assert scopered_rel.sub_of(ROWS) == "row_update"
    assert scopered_rel.sub_of(SHARED) is None
    assert scopered_rel.sub_of(SLOTS_B) is None
    assert scopered_rel.sub_of("") is None
    # and the accepted reduction books the same ops to their leaf scope
    assert scopered.scope_of(PROJ_B) == ("layer1", "dense", "bwd")
    assert scopered.scope_of(FEATS) == ("-", "dense", "fwd")
    assert scopered.scope_of(ROWS) == ("-", "optimizer", "fwd")
    assert scopered.scope_of(SLOTS_B) == ("layer0", "agg_slots", "bwd")


def _planes():
    """One chip, two runs of program P (0–1000 µs, 1000–2000 µs); the first
    holds the ops below, back to back (900 µs busy)."""
    us = 1e3
    spec = [(PROJ_F, 20), (PROJ_B, 40), (TABLE_F, 15), (TABLE_B, 25),
            (FEATS, 10), (SLOTS_F, 300), (SLOTS_B, 200), (TAIL_B, 100),
            (ROWS, 120), (SHARED, 5), (LOSS, 60), (PSUM, 5)]
    ops, t = [], 0.0
    for i, (tf_op, dur) in enumerate(spec):
        name = f"all-reduce.{i}" if tf_op == PSUM else f"fusion.{i}"
        ops.append([name, t * us, dur * us, {"tf_op": tf_op}])
        t += dur
    return [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": [["P(1)", 0.0, 1000 * us, {}],
                        ["P(1)", 1000 * us, 1000 * us, {}]],
        "XLA Ops": ops}}]


def test_sub_scope_seconds_and_rooflines_on_a_hand_built_trace(monkeypatch):
    red = scopered_rel.reduce_rel(_planes(), runs=1, epochs=1)
    assert red == {"rel_project": pytest.approx(60e-6),
                   "rel_project:collective": pytest.approx(5e-6),
                   "rel_table": pytest.approx(50e-6),
                   "row_update": pytest.approx(120e-6)}
    assert scopered_rel.reduce_rel([], 1, 1) is None
    plain = _planes()
    for ev in plain[0]["lines"]["XLA Ops"]:        # a parent's program
        for sub in scopered_rel.SUBSCOPES:
            ev[3]["tf_op"] = ev[3]["tf_op"].replace(f"sgcn.{sub}/", "")
    assert scopered_rel.reduce_rel(plain, 1, 1) is None
    scoped = scopered.reduce_scopes(_planes(), runs=1, epochs=1)
    monkeypatch.setitem(scopered_rel._memo, "table", red)
    monkeypatch.setitem(scopered._memo, "table", scoped)
    cfg = _config()
    run = {"trace": {"epochs": 1, "busy_s": 900e-6}, "config": cfg,
           "nnz": 1000, "chips": 1, "device_kind": "TPU v5 lite"}
    assert _reader("rel_project_s").read(run) == pytest.approx(60e-6)
    assert _reader("rel_table_s").read(run) == pytest.approx(50e-6)
    assert _reader("row_update_s").read(run) == pytest.approx(120e-6)
    # dense_s reads all of the layer's row-wise seconds, the two split it;
    # loss_opt_s reads the whole optimiser, row_update_s its owned part
    assert scopered.scope_seconds(run, "dense") == pytest.approx(110e-6)
    assert _reader("loss_opt_s").read(run) == pytest.approx(185e-6)
    # agg_slots 500 + agg_tail 100 = 600 µs against the configuration's
    # least bytes; 154,029,312 parameters · 4 B · 6 against 120 µs
    least = costmodel_rel.agg_bytes_per_epoch(cfg) / 819e9
    assert _reader("rel_agg_roofline").read(run) \
        == pytest.approx(100 * least / 600e-6)
    assert _reader("row_update_roofline").read(run) \
        == pytest.approx(100 * (154_029_312 * 24 / 819e9) / 120e-6)
    for name in READERS[:-1]:               # no trace: nothing, no raise
        assert _reader(name).read(dict(run, trace={})) is None
    # configurations without this model block (the accepted cells')
    for other in ({"widths": [128, 47]},
                  {"model": {"name": "deepergcn", "hidden": 8, "layers": 2}}):
        assert _reader("rel_agg_roofline").read(
            dict(run, config=other)) is None
        assert _reader("row_update_roofline").read(
            dict(run, config=other)) is None


def test_a_program_without_the_sub_scopes_or_the_counter_reads_nothing(
        monkeypatch):
    from sgcn_tpu.obs import tracing

    monkeypatch.setitem(scopered_rel._memo, "table", None)
    monkeypatch.setitem(scopered._memo, "table", None)
    monkeypatch.setattr(tracing, "_counters", {})
    run = {"trace": {"epochs": 1, "busy_s": 1.0}, "config": {}, "nnz": 1,
           "chips": 1, "device_kind": "TPU v5 lite"}
    assert [_reader(n).read(run) for n in READERS] == [None] * 6
    tracing.set_counter("rel.work", {"row_owned_bytes": {
        "parameters": 616_117_248, "optimizer_state": 1_232_234_496,
        "gradient": 616_117_248}})
    assert _reader("rel_rows_owned_gb").read(run) \
        == pytest.approx(1.848351744)


def test_the_runner_knows_types_and_splits_and_no_model():
    src = open(os.path.join(BENCH, "runners", "fullbatch_typed.py")).read()
    code = "".join(src.split('"""')[2::2])     # all but the docstrings
    for word in ("rgcn", "deepergcn", "mhgat", "relations", "hidden",
                 "layers", "emb"):
        assert word not in code, word
    assert "program.MODELS" in code and 'cfg["params"]' in code
    assert "train_mask=" in code and 'cfg["split"]' in code
    runner = manifest.load_module(os.path.join(BENCH, "runners",
                                               "fullbatch_typed.py"))
    cfg = _config()
    rows = runner.labelled_rows(cfg)
    assert (rows.start, rows.stop) == (0, 736389)
    mask = runner.train_mask(cfg)
    assert mask.sum() == 629571 and mask[:629571].all()
    other = dict(cfg, split={"type": "author", "train_first": 10})
    assert runner.labelled_rows(other).start == 736389
    assert runner.train_mask(other)[736389:736399].all()


# ------------------------------------------------- the reference, by itself
def test_the_reference_follows_the_trainer_and_refuses_a_bf16_table():
    """At rehearsal size, through the runner's own calls: two losses and the
    labelled logits against the program's, and the bfloat16-table reading
    past the limit."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from sgcn_tpu.parallel import (build_comm_plan, make_mesh_1d,
                                   shard_stacked)
    from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

    ref = manifest.load_module(os.path.join(BENCH, "reference",
                                            "rgcn_ref.py"))
    assert "sgcn_tpu" not in open(ref.__file__).read().split('"""')[2]
    runner = manifest.load_module(os.path.join(BENCH, "runners",
                                               "fullbatch_typed.py"))
    cfg = manifest.merged(_config(), _config()["rehearse"])
    ahat, _ = inputs.load_graph(cfg["n"], cfg["graph"])
    feats, labels = inputs.features_and_labels(cfg["n"], cfg["f_in"],
                                               cfg["classes"], seed=5)
    model = dict(cfg["model"])
    name = model.pop("name")
    plan = build_comm_plan(ahat, np.zeros(cfg["n"], np.int64), 1)
    tr = FullBatchTrainer(plan, fin=cfg["f_in"], widths=cfg["widths"],
                          mesh=make_mesh_1d(1), lr=cfg["lr"], seed=5,
                          model=name, model_args=model)
    mask = runner.train_mask(cfg)
    data = make_train_data(plan, feats, labels, train_mask=mask)
    data = TrainData(**shard_stacked(tr.mesh, vars(data)))
    params0 = tr.host_state()[0]
    got = [tr.step(data), tr.step(data)]
    edges = ref.coo_chunks(ahat.indptr, ahat.indices, ahat.data, rows=256,
                           model=cfg["model"])
    assert len(edges) == 4 and edges[0][0].shape[0] == -(-1139 // 256)
    want = ref.training_losses(params0, [(edges, feats, labels, mask)] * 2,
                               cfg["lr"], cfg["model"], cfg["activation"])
    gaps = [abs(g / w - 1) for g, w in zip(got, want)]
    assert max(gaps) < ref.RTOL / 20, gaps
    rows = runner.labelled_rows(cfg)
    mine = tr.predict(data)[rows]
    params = tr.host_state()[0]
    theirs = ref.logits(params, edges, feats, "highest", cfg["model"],
                        cfg["activation"])
    narrow = ref.logits(params, edges, feats, "highest", cfg["model"],
                        cfg["activation"], table_dtype="bfloat16")
    assert mine.shape == theirs.shape == (1139, 349)

    def gap(a, b, norm):
        diff = (a - b).astype("float64")
        rms = float((b.astype("float64") ** 2).mean()) ** 0.5
        return (float(abs(diff).max()) if norm == "max"
                else float((diff ** 2).mean()) ** 0.5) / rms

    for precision, norm, limit in ref.LOGITS_CHECKS:
        assert precision == "highest"
        assert gap(mine, theirs, norm) < limit / 3, (norm, limit)
        # a bfloat16 table is refused, by each limit, at least twice over
        assert gap(mine, narrow, norm) > 2 * limit, (norm, limit)


# ----------------------------------------------------------------- rehearsal
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_the_typed_cell_end_to_end(trace):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000007", "--seconds", "1", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 3, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[-1].startswith("benchmark rehearsal (cpu, not a result): ")
    said = json.loads(lines[-1].split(": ", 1)[1])
    assert all(said["checks"].values()), said
    want = {0: {"epoch_s", "setup_s"},
            1: {"plan_build_s", "compile_s", "rel_rows_owned_gb",
                "agg_useful_share"}}[trace]
    assert want <= set(said["metrics"])
    # a CPU run names no device metric, the new ones included
    assert not (set(READERS[:-1]) | {"peak_hbm_gb", "agg_slots_s", "dense_s"}
                ) & set(said["metrics"])
    note = json.loads(next(
        ln for ln in lines if '"setup_s"' in ln)[len("bench: "):])
    assert note["notes"]["trainer"]["model"] == "rgcn"
    assert note["notes"]["trainer"]["params"] == 575796
    assert note["notes"]["trainer"]["memory_estimate"]["row_owned"] > 0
    # every run reads what the logits limit must refuse
    narrow = json.loads(next(
        ln for ln in lines if "bf16_table_reference" in ln)[len("bench: "):])
    assert narrow["bf16_table_reference"]["refused_by"] > 2
