"""The benchmark's deep-stack pieces (PR 31): the configuration and cell are
data, the runner knows no model, the sub-scope and recomputation readers and
``costmodel_deep`` give hand-computed figures, the sub-scope vocabulary is
the program's, and the new cell rehearses end to end.  (The plain reference
``reference/deepergcn_ref.py`` is held against the trainer, loss, logits and
every gradient leaf, in ``tests/test_deepergcn.py``.)  CPU only; nothing here
describes a topology.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import costmodel_deep  # noqa: E402
import manifest  # noqa: E402
import scopered  # noqa: E402
import scopered_deep  # noqa: E402

CELL, CONFIG = "products8-deepergcn.fullbatch", "deepergcn-products-14x128"
READERS = ("deep_agg_roofline", "deep_recompute_share", "deep_norm_s",
           "deep_softmax_s", "deep_rows_kept_gb")
ACCEPTED = ("products.fullbatch", "products.fullbatch-gp4",
            "products8-gat.fullbatch")


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
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "deep_gcns_torch" in cfg["source"]
    model = cfg["model"]
    # every published width and setting, and the count they give
    assert (cfg["f_in"], cfg["classes"], cfg["params"]) == (100, 47, 253743)
    assert (model["layers"], model["hidden"], model["t"]) == (14, 128, 0.1)
    assert (model["aggr"], model["norm"], model["block"],
            model["mlp_layers"]) == ("softmax_sg", "batch", "res+", 1)
    assert model["eps"] == 1e-7 and model["keep"] == "aggregate"
    assert cfg["widths"] == [128] * 14 + [47] and cfg["lr"] == 0.01
    assert cfg["dtype"] == "float32" and cfg["dropout"] == 0.0
    assert cfg["activation"] == "relu"
    h, layers = model["hidden"], model["layers"]
    count = (cfg["f_in"] * h + h + layers * (h * h + h) + layers * 2 * h
             + h * cfg["classes"] + cfg["classes"])
    assert count == cfg["params"]
    assert cfg["n"] == -(-2449029 // 8)
    for key in ("source", "assumed", "deployment", "describes"):
        assert cfg[key]
    # the attention configuration's graph, to the key: one cache entry
    gat = manifest.read_json(os.path.join(
        BENCH, "configs", "gat-products-3x4x128.json"))
    assert (cfg["n"], cfg["graph"]) == (gat["n"], gat["graph"])
    assert cfg["reference"] == {"file": "deepergcn_ref.py", "losses": 2}


def test_the_cell_and_its_metrics_are_additions_to_the_manifest():
    bench = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [w["name"] for w in bench["workloads"]] == list(ACCEPTED) + [CELL]
    assert bench["configs"][-1]["name"] == CONFIG
    cell = manifest.resolve(CELL)
    assert cell.chips == 1 == cell.traffic["k"]
    assert cell.traffic["kind"] == "fullbatch_model"
    assert cell.traffic["trace_steps"] == 2
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-5:] == list(READERS)
    assert names[-6] == "att_dense_share"       # appended after it
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert {by_name[n]["layer"] for n in READERS} == {"device_compute",
                                                      "step_program"}
    assert by_name["deep_rows_kept_gb"]["moves"] == "peak_hbm_gb"
    assert by_name["deep_rows_kept_gb"]["source"] == "program_counter"
    assert by_name["deep_agg_roofline"]["unit"] == "%"
    # PERF.md's list of layers names the rows the new metrics stand in
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for name in READERS:
        row = next(ln for ln in perf.splitlines()
                   if ln.startswith(f"| `{by_name[name]['layer']}`"))
        assert f"`{name}`" in row, name
    resolved = {n for n, _, _ in cell.per_layer}
    assert set(READERS) <= resolved
    for other in ACCEPTED:
        assert not set(READERS) & {
            n for n, _, _ in manifest.resolve(other).per_layer}
    # every metric without a list is the new cell's too; the listed ones of
    # other cells are not
    assert {n for n, m in by_name.items() if "workloads" not in m} <= resolved
    assert not {"agg0_build_s", "att_score_s", "km1"} & resolved


def test_the_sub_scope_vocabulary_is_the_programs():
    from sgcn_tpu.obs import tracing

    vocab = manifest.read_json(os.path.join(BENCH, "scopes_deep.json"))
    assert tuple(vocab["subscopes"]) == tracing.DEEP_SUBSCOPES
    assert vocab["prefix"] == tracing.PREFIX == scopered.PREFIX
    assert scopered_deep.SUBSCOPES == tracing.DEEP_SUBSCOPES
    # a sub-scope is no scope of the accepted vocabularies
    att = manifest.read_json(os.path.join(BENCH, "scopes_att.json"))
    assert not set(vocab["subscopes"]) & (set(scopered.LEAVES)
                                          | set(att["subscopes"]))
    assert tuple(att["subscopes"]) == tracing.SUBSCOPES     # left as it was


# ------------------------------------------------------------- the readers
# tf_op strings as the step compiled for a v5e prints them (PR 31, sandbox)
P = "jit(per_chip)/shard_map/"
BODY = "transpose(jvp(sgcn.layer1))/while/body/checkpoint/"
NORM_F = P + "jvp(sgcn.layer1)/while/body/checkpoint/sgcn.dense/sgcn.norm/mul:"
NORM_R = P + BODY + "rematted_computation/sgcn.dense/sgcn.norm/rsqrt:"
NORM_B = P + BODY + "sgcn.dense/sgcn.norm/reduce_sum:"
NORM_PSUM = P + BODY + "sgcn.dense/sgcn.norm/psum_invariant:"
TABLE_F0 = P + "jvp(sgcn.layer0)/checkpoint/sgcn.dense/sgcn.softmax_table/exp:"
TABLE_R = (P + BODY + "rematted_computation/sgcn.dense/sgcn.softmax_table/"
           "concatenate:")
PMAX = P + "jvp(sgcn.layer0)/checkpoint/sgcn.dense/sgcn.softmax_table/pmax:"
DOT_R = P + BODY + "rematted_computation/sgcn.dense/dot_general:"
SLOTS_F = P + "jvp(sgcn.layer1)/while/body/checkpoint/sgcn.agg_slots/add:"
SLOTS_B = P + BODY + "sgcn.agg_slots/add:"
TAIL_B = P + BODY + "sgcn.agg_tail/scatter-add:"
HEAD = P + "jvp(sgcn.dense)/sgcn.norm/sub:"
LOSS = P + "jvp(sgcn.loss)/reduce_sum:"


def test_sub_scope_of_an_op_is_its_last_sub_scope_token():
    assert scopered_deep.sub_of(NORM_R) == "norm"
    assert scopered_deep.sub_of(TABLE_F0) == "softmax_table"
    assert scopered_deep.sub_of(HEAD) == "norm"
    assert scopered_deep.sub_of(DOT_R) is None
    assert scopered_deep.sub_of(SLOTS_B) is None
    assert scopered_deep.sub_of("") is None
    # and the accepted reduction books the same ops to their leaf scope: the
    # scanned body is one layer token, the head has none
    assert scopered.scope_of(NORM_R) == ("layer1", "dense", "bwd")
    assert scopered.scope_of(TABLE_F0) == ("layer0", "dense", "fwd")
    assert scopered.scope_of(SLOTS_B) == ("layer1", "agg_slots", "bwd")
    assert scopered.scope_of(HEAD) == ("-", "dense", "fwd")


def _planes():
    """One chip, two runs of program P (0–1000 µs, 1000–2000 µs); the first
    holds the ops below, back to back (900 µs busy)."""
    us = 1e3
    spec = [(NORM_F, 40), (NORM_R, 30), (NORM_B, 50), (NORM_PSUM, 5),
            (TABLE_F0, 60), (TABLE_R, 45), (PMAX, 5), (DOT_R, 25),
            (SLOTS_F, 300), (SLOTS_B, 180), (TAIL_B, 120), (HEAD, 10),
            (LOSS, 30)]
    ops, t = [], 0.0
    for i, (tf_op, dur) in enumerate(spec):
        name = (f"all-reduce.{i}" if tf_op in (NORM_PSUM, PMAX)
                else f"fusion.{i}")
        ops.append([name, t * us, dur * us, {"tf_op": tf_op}])
        t += dur
    return [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": [["P(1)", 0.0, 1000 * us, {}],
                        ["P(1)", 1000 * us, 1000 * us, {}]],
        "XLA Ops": ops}}]


def test_sub_scope_and_recomputed_seconds_on_a_hand_built_trace(monkeypatch):
    red = scopered_deep.reduce_deep(_planes(), runs=1, epochs=1)
    assert red == {"busy": pytest.approx(900e-6),
                   "norm": pytest.approx(130e-6),
                   "norm:collective": pytest.approx(5e-6),
                   "softmax_table": pytest.approx(105e-6),
                   "softmax_table:collective": pytest.approx(5e-6),
                   "recomputed": pytest.approx(100e-6)}
    assert scopered_deep.reduce_deep([], 1, 1) is None
    plain = _planes()
    for ev in plain[0]["lines"]["XLA Ops"]:        # a parent's program
        for sub in scopered_deep.SUBSCOPES:
            ev[3]["tf_op"] = ev[3]["tf_op"].replace(f"sgcn.{sub}/", "")
    assert scopered_deep.reduce_deep(plain, 1, 1) is None
    # the readers, on that table and on the accepted one
    scoped = scopered.reduce_scopes(_planes(), runs=1, epochs=1)
    monkeypatch.setitem(scopered_deep._memo, "table", red)
    monkeypatch.setitem(scopered._memo, "table", scoped)
    cfg = manifest.read_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    run = {"trace": {"epochs": 1, "busy_s": 900e-6}, "config": cfg,
           "nnz": 1000, "chips": 1, "device_kind": "TPU v5 lite"}
    assert _reader("deep_norm_s").read(run) == pytest.approx(130e-6)
    assert _reader("deep_softmax_s").read(run) == pytest.approx(105e-6)
    assert _reader("deep_recompute_share").read(run) \
        == pytest.approx(100 * 100 / 900)
    # dense_s reads ALL of the layer's row-wise seconds, the two split it
    # (the collectives are booked dense:collective by the accepted reader)
    assert scopered.scope_seconds(run, "dense") == pytest.approx(
        (130 + 105 + 25) * 1e-6)
    # agg_slots 480 + agg_tail 120 = 600 µs; least bytes 14 layers · 1000
    # nnz · 4 B · (256 + 128) lanes over 819 GB/s
    least = 14 * 1000 * 4 * 384 / 819e9
    assert _reader("deep_agg_roofline").read(run) \
        == pytest.approx(100 * least / 600e-6)
    for name in READERS[:-1]:               # no trace: nothing, no raise
        assert _reader(name).read(dict(run, trace={})) is None
    # configurations without this model block (the accepted cells')
    for other in ({"widths": [128, 47]},
                  {"model": {"name": "mhgat", "heads": [4], "channels": [8]}}):
        assert _reader("deep_agg_roofline").read(
            dict(run, config=other)) is None


def test_a_program_without_the_sub_scopes_or_the_counter_reads_nothing(
        monkeypatch):
    from sgcn_tpu.obs import tracing

    monkeypatch.setitem(scopered_deep._memo, "table", None)
    monkeypatch.setitem(scopered._memo, "table", None)
    monkeypatch.setattr(tracing, "_counters", {})
    run = {"trace": {"epochs": 1, "busy_s": 1.0}, "config": {}, "nnz": 1,
           "chips": 1, "device_kind": "TPU v5 lite"}
    assert [_reader(n).read(run) for n in READERS] == [None] * 5
    tracing.set_counter("deep.work", {"rows_kept_bytes": 6_896_474_112})
    assert _reader("deep_rows_kept_gb").read(run) == pytest.approx(6.896474112)


def test_deep_cost_model_equals_a_hand_count():
    """Path 0-1-2 with self-loops: 7 nonzeros.  Three layers of 5 channels:
    a layer's two passes gather 2·5 + 5 = 15 lanes a nonzero."""
    model = {"layers": 3, "hidden": 5}
    assert costmodel_deep.lanes_per_layer(model) == 15
    assert costmodel_deep.agg_bytes_per_epoch(7, model) == 3 * 7 * 4 * 15
    assert costmodel_deep.agg_min_seconds(7, model, "TPU v5 lite") \
        == pytest.approx(1260 / 819e9)
    with pytest.raises(KeyError, match="no peaks for device kind"):
        costmodel_deep.agg_min_seconds(7, model, "cpu")
    for mod in (costmodel_deep, scopered_deep):     # the yardstick's own
        assert "import sgcn_tpu" not in open(mod.__file__).read()


def test_the_runner_knows_no_model():
    src = open(os.path.join(BENCH, "runners", "fullbatch_model.py")).read()
    code = src.split('"""')[2]
    for word in ("deepergcn", "mhgat", "heads", "channels", "layers",
                 "hidden"):
        assert word not in code, word
    assert "program.MODELS" in code and 'cfg["params"]' in code


# ----------------------------------------------------------------- rehearsal
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_the_deep_cell_end_to_end(trace):
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
            1: {"plan_build_s", "compile_s", "deep_rows_kept_gb"}}[trace]
    assert want <= set(said["metrics"])
    # a CPU run names no device metric, the new ones included
    assert not (set(READERS[:-1]) | {"peak_hbm_gb", "agg_slots_s", "dense_s"}
                ) & set(said["metrics"])
    note = json.loads(next(
        ln for ln in lines if '"setup_s"' in ln)[len("bench: "):])
    assert note["notes"]["trainer"]["model"] == "deepergcn"
    assert note["notes"]["trainer"]["params"] == 253743
    # every run reads what the logits limit must refuse
    narrow = json.loads(next(
        ln for ln in lines if "bf16_table_reference" in ln)[len("bench: "):])
    assert narrow["bf16_table_reference"]["rms"] > 1e-4
