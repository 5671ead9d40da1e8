"""The benchmark's typed attention pieces (PR 39): the configuration and
cell are data over the typed runner and generator as they are, the four
metrics are additions asserted by membership (never as the manifest's
tail, ROADMAP C15), every reader reads nothing where its scope or counter is
absent, the sub-scope vocabulary is the program's, ``costmodel_ratt``
equals a hand count on the rehearsal graph, and the cell rehearses end to
end with the reference agreeing.  CPU only.
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

import costmodel_ratt  # noqa: E402
import inputs  # noqa: E402
import manifest  # noqa: E402
import scopered  # noqa: E402
import scopered_ratt  # noqa: E402

CELL, CONFIG = "mag240m-rgat.fullbatch", "rgat-mag240m-2x4x256"
READERS = ("ratt_agg_roofline", "ratt_project_s", "ratt_norm_s",
           "ratt_tail_share")
ACCEPTED = ("products.fullbatch", "products.fullbatch-gp4",
            "products8-gat.fullbatch", "products8-deepergcn.fullbatch",
            "mag-rgcn.fullbatch")


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
    cfg = _config()
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # no width, head, relation or layer is cut
    assert entry["reduced"] == ["graph", "n", "training", "sampling"]
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert entry["source"] == cfg["source"] and "lsc/mag240m/rgnn.py" in \
        cfg["source"]
    model = cfg["model"]
    assert model["name"] == "rgat"
    counts = {t["name"]: t["count"] for t in model["types"]}
    assert counts == {"paper": 59449, "author": 59757, "institution": 13}
    assert sum(counts.values()) == cfg["n"] == 119_219
    assert {t["input"] for t in model["types"]} == {"features"}
    assert [r[1] for r in model["relations"]] == [
        "writes", "rev_writes", "affiliated_with", "rev_affiliated_with",
        "cites"]
    assert (model["hidden"], model["layers"], model["heads"],
            model["label_type"]) == (1024, 2, 4, "paper")
    assert model["head"] == {"hidden": 1024, "norm": "batch",
                             "activation": "relu"}
    assert (cfg["f_in"], cfg["classes"], cfg["widths"]) == (768, 153,
                                                            [1024, 1024, 153])
    assert (cfg["lr"], cfg["dropout"], cfg["activation"]) == (0.001, 0.0,
                                                             "elu")
    # the baseline's parameter count, from these sizes alone
    def layer(a):
        return 5 * (a * 1024 + 3 * 1024) + a * 1024 + 1024 + 2048
    assert (layer(768), layer(1024)) == (4_737_024, 6_309_888)
    head = 1024 * 1024 + 1024 + 2048 + 1024 * 153 + 153
    assert layer(768) + layer(1024) + head == cfg["params"] == 12_255_385
    # one chip's 1/2,048 share of the published counts
    whole = {"paper": 121_751_666, "author": 122_383_112,
             "institution": 25_721}
    assert counts == {n: round(c / 2048) for n, c in whole.items()}
    published = {(s, d): m for s, d, m in cfg["graph"]["relations"]}
    whole = {("author", "paper"): 386_022_720,
             ("author", "institution"): 44_592_586,
             ("paper", "paper"): 1_297_748_926}
    assert published == {("author", "paper"): 188_488,
                         ("author", "institution"): 21_774,
                         ("paper", "paper"): 633_667}
    assert all(abs(published[p] - m / 2048) < 1 for p, m in whole.items())
    assert cfg["split"] == {"type": "paper",
                            "train_first": 1_112_392 // 2048}
    assert cfg["graph"]["generator"] == "typed_dcsbm"
    assert cfg["reference"] == {"file": "rgat_ref.py", "losses": 2}
    for key in ("describes", "deployment", "assumed"):
        assert cfg[key]
    assert "2,048" in cfg["deployment"]
    small = manifest.merged(cfg, cfg["rehearse"])
    tiny = {t["name"]: t["count"] for t in small["model"]["types"]}
    assert sum(tiny.values()) == small["n"] < 5000
    assert small["graph"]["types"] == tiny
    assert small["widths"] == cfg["widths"]     # the widths are not cut


def test_the_cell_and_its_metrics_are_additions_to_the_manifest():
    bench = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    assert [c for c in cells if c in ACCEPTED + (CELL,)] \
        == list(ACCEPTED) + [CELL]
    entry = bench["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "fullbatch-typed", 1)
    assert 0 < len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index(CONFIG) > configs.index("rgcn-mag-2x64")
    cell = manifest.resolve(CELL)
    assert cell.chips == 1 == cell.traffic["k"]
    assert cell.traffic["kind"] == "fullbatch_typed"
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in READERS]
    assert at == sorted(at) and at[0] > names.index("rel_rows_owned_gb")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "epoch_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert by_name["ratt_agg_roofline"]["unit"] == "%"
    assert by_name["ratt_tail_share"]["source"] == "program_counter"
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
    resolved = {n for n, _, _ in manifest.resolve(CELL).per_layer}
    free = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert free <= resolved and set(READERS) <= resolved
    assert not {"att_score_s", "rel_agg_roofline", "deep_norm_s"} & resolved


def test_the_sub_scope_vocabulary_is_the_programs():
    from sgcn_tpu.obs import tracing

    vocab = manifest.read_json(os.path.join(BENCH, "scopes_ratt.json"))
    assert tuple(vocab["subscopes"]) == tracing.RATT_SUBSCOPES
    assert vocab["prefix"] == tracing.PREFIX == scopered.PREFIX
    assert scopered_ratt.SUBSCOPES == tracing.RATT_SUBSCOPES
    assert not set(tracing.RATT_SUBSCOPES) & set(tracing.SCOPES)


# ---------------------------------------------------------------- the count
def test_the_cost_model_equals_a_hand_count_on_the_rehearsal_graph():
    cfg = manifest.merged(_config(), _config()["rehearse"])
    a = inputs.generate_graph(cfg["n"], cfg["graph"])
    indptr, indices = a[0], a[1]
    rows = np.repeat(np.arange(cfg["n"]), np.diff(indptr))
    starts = np.cumsum([0] + [t["count"] for t in cfg["model"]["types"]])
    typ = np.searchsorted(starts, np.arange(cfg["n"]), "right") - 1
    names = [t["name"] for t in cfg["model"]["types"]]
    off = rows != indices

    def edges(s, d):            # directed edges of the pair, s -> d
        return int(((typ[indices] == names.index(s))
                    & (typ[rows] == names.index(d)) & off).sum())

    # layer 1 computes papers and authors, layer 2 papers
    hand = [edges("author", "paper") + edges("paper", "author")
            + edges("institution", "author") + edges("paper", "paper"),
            edges("author", "paper") + edges("paper", "paper")]
    passes = costmodel_ratt.agg_passes(cfg)
    assert [p["edges"] for p in passes] == hand
    assert passes[0]["relations"] == ["writes", "rev_writes",
                                      "rev_affiliated_with", "cites"]
    assert passes[1]["relations"] == ["writes", "cites"]
    assert {p["lanes"] for p in passes} == {1024 + 4}
    assert costmodel_ratt.agg_bytes_per_epoch(cfg) == 2 * sum(hand) * 1028 * 4


def test_the_cells_least_count():
    """3,121,906 live edge visits an epoch, two passes of 1,028 lanes."""
    passes = costmodel_ratt.agg_passes(_config())
    assert [p["edges"] for p in passes] == [1_666_084, 1_455_822]
    assert costmodel_ratt.agg_bytes_per_epoch(_config()) \
        == 2 * 3_121_906 * 1028 * 4
    assert costmodel_ratt.agg_min_seconds(_config(), "TPU v5 lite") \
        == pytest.approx(2 * 3_121_906 * 1028 * 4 / 819e9)


# ------------------------------------------------------------- the readers
P = "jit(per_chip)/shard_map/"
PROJ_F = P + "jvp(sgcn.layer0)/sgcn.dense/sgcn.ratt_project/dot_general:"
PROJ_B = P + "transpose(jvp(sgcn.layer1))/sgcn.dense/sgcn.ratt_project/" \
    "dot_general:"
NORM_F = P + "jvp(sgcn.dense)/sgcn.ratt_norm/reduce_sum:"
NORM_B = P + "transpose(jvp(sgcn.layer0))/sgcn.dense/sgcn.ratt_norm/mul:"
SLOTS = P + "jvp(sgcn.layer0)/sgcn.agg_slots/sgcn.att_score/exp:"
TAIL = P + "transpose(jvp(sgcn.layer1))/sgcn.agg_tail/sgcn.fold_rows/" \
    "scatter-add:"
PSUM = P + "jvp(sgcn.dense)/sgcn.ratt_norm/psum:"


def _planes():
    """One chip, two runs of program P; the first holds the ops below back
    to back."""
    us = 1e3
    spec = [(PROJ_F, 20), (PROJ_B, 40), (NORM_F, 15), (NORM_B, 25),
            (SLOTS, 300), (TAIL, 100), (PSUM, 5)]
    ops, t = [], 0.0
    for i, (tf_op, dur) in enumerate(spec):
        name = f"all-reduce.{i}" if tf_op == PSUM else f"fusion.{i}"
        ops.append([name, t * us, dur * us, {"tf_op": tf_op}])
        t += dur
    return [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": [["P(1)", 0.0, 1000 * us, {}],
                        ["P(1)", 1000 * us, 1000 * us, {}]],
        "XLA Ops": ops}}]


def test_sub_scope_seconds_and_the_roofline_on_a_hand_built_trace(
        monkeypatch):
    assert scopered_ratt.sub_of(PROJ_B) == "ratt_project"
    assert scopered_ratt.sub_of(NORM_F) == "ratt_norm"
    assert scopered_ratt.sub_of(SLOTS) is None
    red = scopered_ratt.reduce_ratt(_planes(), runs=1, epochs=1)
    assert red == {"ratt_project": pytest.approx(60e-6),
                   "ratt_norm": pytest.approx(40e-6),
                   "ratt_norm:collective": pytest.approx(5e-6)}
    monkeypatch.setitem(scopered_ratt._memo, "table", red)
    monkeypatch.setitem(scopered._memo, "table", scopered.reduce_scopes(
        _planes(), runs=1, epochs=1))
    cfg = _config()
    run = {"trace": {"epochs": 1, "busy_s": 505e-6}, "config": cfg,
           "nnz": 1000, "chips": 1, "device_kind": "TPU v5 lite"}
    assert _reader("ratt_project_s").read(run) == pytest.approx(60e-6)
    assert _reader("ratt_norm_s").read(run) == pytest.approx(40e-6)
    least = costmodel_ratt.agg_bytes_per_epoch(cfg) / 819e9
    assert _reader("ratt_agg_roofline").read(run) \
        == pytest.approx(100 * least / 400e-6)
    # the configurations of the accepted cells have no such model block
    for other in ({"widths": [128, 47]}, manifest.read_json(os.path.join(
            BENCH, "configs", "rgcn-mag-2x64.json"))):
        assert _reader("ratt_agg_roofline").read(
            dict(run, config=other)) is None


def test_a_program_without_the_sub_scopes_or_the_counter_reads_nothing(
        monkeypatch):
    from sgcn_tpu.obs import tracing

    monkeypatch.setitem(scopered_ratt._memo, "table", None)
    monkeypatch.setitem(scopered._memo, "table", None)
    monkeypatch.setattr(tracing, "_counters", {})
    run = {"trace": {"epochs": 1, "busy_s": 1.0}, "config": _config(),
           "nnz": 1, "chips": 1, "device_kind": "TPU v5 lite"}
    assert [_reader(n).read(run) for n in READERS] == [None] * 4
    # a parent's trace: no op carries a sub-scope token
    plain = _planes()
    for ev in plain[0]["lines"]["XLA Ops"]:
        for sub in scopered_ratt.SUBSCOPES:
            ev[3]["tf_op"] = ev[3]["tf_op"].replace(f"sgcn.{sub}/", "")
    assert scopered_ratt.reduce_ratt(plain, 1, 1) is None
    assert scopered_ratt.reduce_ratt([], 1, 1) is None
    # no trace at all
    assert _reader("ratt_project_s").read(dict(run, trace={})) is None
    tracing.set_counter("ratt.work", {"per_step": {
        "executed_slots": 400, "virtual_row_slots": 100}})
    assert _reader("ratt_tail_share").read(run) == pytest.approx(25.0)


# ----------------------------------------------------------- end to end
def test_rehearsal_runs_the_cell_end_to_end_with_the_reference_agreeing():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 3, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    said = json.loads(lines[-1].split(": ", 1)[1])
    assert all(said["checks"].values()), said
    # a counter reads on the CPU; no device metric does
    assert {"plan_build_s", "compile_s", "ratt_tail_share"} \
        <= set(said["metrics"])
    assert not {"ratt_agg_roofline", "ratt_project_s", "ratt_norm_s",
                "peak_hbm_gb"} & set(said["metrics"])
    note = json.loads(next(
        ln for ln in lines if '"setup_s"' in ln)[len("bench: "):])
    assert note["notes"]["trainer"]["model"] == "rgat"
    assert note["notes"]["trainer"]["params"] == 12_255_385
    ref = json.loads(next(
        ln for ln in lines if '"reference"' in ln)[len("bench: "):])
    assert ref["reference"]["ok"]
    narrow = json.loads(next(
        ln for ln in lines if "bf16_table_reference" in ln)[len("bench: "):])
    assert narrow["bf16_table_reference"]["refused_by"] > 2
