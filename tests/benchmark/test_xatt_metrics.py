"""The benchmark's graph-transformer pieces: the configuration states the
published model and its cuts, the cell and its five metrics are additions to
the manifest (asserted by membership, never as its tail), every reader reads
nothing where its scope or counter is absent, the vocabulary of sub-scopes
and passes is the program's, ``costmodel_xatt`` gives the cell's 164.6 GB from the
configuration and equals a hand count on the rehearsal graph, and the runner
hands the reference the run's labels.  CPU only, nothing compiled.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import costmodel_xatt  # noqa: E402
import inputs  # noqa: E402
import manifest  # noqa: E402
import scopered  # noqa: E402
import scopered_xatt  # noqa: E402

CELL, CONFIG = "products8-unimp.fullbatch", "unimp-products-3x4x32"
READERS = ("xatt_agg_roofline", "xatt_project_s", "xatt_norm_s",
           "xatt_lanes_per_edge", "xatt_max_s")
ACCEPTED = ("products.fullbatch", "products.fullbatch-gp4",
            "products8-gat.fullbatch", "products8-deepergcn.fullbatch",
            "mag-rgcn.fullbatch", "mag240m-rgat.fullbatch")
NNZ, N = 15_546_379, 306_129        # the products-eighth graph's Â


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
    # no width, head or layer is cut
    assert entry["reduced"] == ["chips", "graph", "n", "training", "labels"]
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert entry["source"] == cfg["source"] == \
        "https://arxiv.org/abs/2009.03509"
    assert cfg["model"] == {"name": "unimp", "layers": 3, "hidden": 128,
                            "heads": 4, "label_rate": 0.625, "label_seed": 0}
    assert (cfg["f_in"], cfg["classes"], cfg["widths"]) == (100, 47,
                                                            [128, 128, 47])
    assert (cfg["lr"], cfg["dropout"], cfg["activation"]) == (0.001, 0.0,
                                                             "relu")
    # the count, from these widths alone: label embedding, three layers of
    # q, k, v, skip and gate, two LayerNorms
    def layer(f, kc, out):
        return 3 * (f * kc + kc) + f * out + out + 3 * out
    assert (layer(100, 128, 128), layer(128, 128, 128),
            layer(128, 188, 47)) == (52_096, 66_432, 78_960)
    assert 47 * 100 + 52_096 + 66_432 + 78_960 + 2 * 256 \
        == cfg["params"] == 202_700
    # one chip's share of the 8-chip deployment, the attention cells' graph
    assert cfg["n"] == -(-2_449_029 // 8)
    gat = manifest.read_json(os.path.join(BENCH, "configs",
                                          "gat-products-3x4x128.json"))
    assert (cfg["graph"], cfg["n"]) == (gat["graph"], gat["n"])
    assert cfg["reference"] == {"file": "unimp_ref.py", "losses": 2}
    for key in ("describes", "deployment", "assumed"):
        assert cfg[key]
    small = manifest.merged(cfg, cfg["rehearse"])
    assert small["n"] < 5000 and small["widths"] == cfg["widths"]


def test_the_cell_and_its_metrics_are_additions_to_the_manifest():
    bench = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    assert [c for c in cells if c in ACCEPTED + (CELL,)] \
        == list(ACCEPTED) + [CELL]
    entry = bench["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "fullbatch-label", 1)
    assert 0 < len(entry["why"]) <= 200
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index(CONFIG) > configs.index("rgat-mag240m-2x4x256")
    cell = manifest.resolve(CELL)
    assert cell.chips == 1 == cell.traffic["k"]
    assert cell.traffic["kind"] == "fullbatch_label"
    deep = manifest.resolve("products8-deepergcn.fullbatch").traffic
    # the deep-stack cell's mix, with the runner that hands labels on
    assert {k: v for k, v in cell.traffic.items()
            if k not in ("kind", "describes")} \
        == {k: v for k, v in deep.items() if k not in ("kind", "describes")}
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in READERS]
    assert at == sorted(at) and at[0] > names.index("ratt_tail_share")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "epoch_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert by_name["xatt_agg_roofline"]["unit"] == "%"
    assert by_name["xatt_lanes_per_edge"]["source"] == "program_counter"
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
    assert not {"att_score_s", "ratt_agg_roofline", "deep_norm_s"} & resolved


def test_the_sub_scope_vocabulary_is_the_programs():
    from sgcn_tpu.obs import tracing

    vocab = manifest.read_json(os.path.join(BENCH, "scopes_xatt.json"))
    assert tuple(vocab["subscopes"]) == tracing.XATT_SUBSCOPES
    assert tuple(vocab["passes"]) == tracing.XATT_PASSES
    assert vocab["prefix"] == tracing.PREFIX == scopered.PREFIX
    assert scopered_xatt.SUBSCOPES \
        == tracing.XATT_SUBSCOPES + tracing.XATT_PASSES
    # the max pass is the graph-transformer's own, not mhgat's
    assert not set(tracing.XATT_PASSES) & set(tracing.SUBSCOPES)
    # the typed attention's reader keeps its own vocabulary
    import scopered_ratt
    assert scopered_ratt.SUBSCOPES == tracing.RATT_SUBSCOPES
    assert not set(tracing.XATT_SUBSCOPES + tracing.XATT_PASSES) & set(
        tracing.SCOPES)


# ---------------------------------------------------------------- the count
def test_the_cells_least_count_is_164_6_gb_an_epoch():
    cfg = _config()
    assert costmodel_xatt.heads_and_channels(cfg) == [(4, 32), (4, 32),
                                                      (4, 47)]
    assert costmodel_xatt.lanes_per_edge(cfg) == 780 + 780 + 1_140 == 2_700
    assert costmodel_xatt.attention_edges(NNZ, cfg) == 15_240_250
    assert costmodel_xatt.agg_bytes_per_epoch(NNZ, cfg) \
        == 15_240_250 * 2_700 * 4 == pytest.approx(164.6e9, rel=1e-3)
    assert costmodel_xatt.agg_min_seconds(NNZ, cfg, "TPU v5 lite") \
        == pytest.approx(15_240_250 * 2_700 * 4 / 819e9)


def test_the_attention_edges_are_a_hand_count_on_the_rehearsal_graph():
    cfg = manifest.merged(_config(), _config()["rehearse"])
    indptr, indices, _ = inputs.generate_graph(cfg["n"], cfg["graph"])
    rows = np.repeat(np.arange(cfg["n"]), np.diff(indptr))
    off = int((rows != indices).sum())
    assert costmodel_xatt.attention_edges(len(indices), cfg) == off > 0


# ------------------------------------------------------------- the readers
P = "jit(per_chip)/shard_map/"
PROJ_F = P + "jvp(sgcn.layer0)/sgcn.dense/sgcn.xatt_project/dot_general:"
PROJ_B = P + "transpose(jvp(sgcn.layer1))/sgcn.dense/sgcn.xatt_project/" \
    "dot_general:"
NORM_F = P + "jvp(sgcn.layer0)/sgcn.dense/sgcn.xatt_norm/reduce_sum:"
NORM_B = P + "transpose(jvp(sgcn.layer1))/sgcn.dense/sgcn.xatt_norm/mul:"
SLOTS = P + "jvp(sgcn.layer0)/sgcn.agg_slots/sgcn.xatt_fwd/" \
    "sgcn.bkt_8x3_u/exp:"
TAIL = P + "transpose(jvp(sgcn.layer1))/sgcn.agg_tail/sgcn.xatt_src/" \
    "sgcn.fold_rows/scatter-add:"
MAX = P + "jvp(sgcn.layer2)/sgcn.agg_slots/sgcn.bkt_8x3_u/sgcn.xatt_max/" \
    "reduce_max:"


def _planes():
    """One chip, two runs of program P; the first holds the ops below back
    to back."""
    us = 1e3
    spec = [(PROJ_F, 20), (PROJ_B, 40), (NORM_F, 15), (NORM_B, 25),
            (SLOTS, 300), (TAIL, 100), (MAX, 70)]
    ops, t = [], 0.0
    for i, (tf_op, dur) in enumerate(spec):
        ops.append([f"fusion.{i}", t * us, dur * us, {"tf_op": tf_op}])
        t += dur
    return [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": [["P(1)", 0.0, 1000 * us, {}],
                        ["P(1)", 1000 * us, 1000 * us, {}]],
        "XLA Ops": ops}}]


def test_sub_scope_seconds_and_the_roofline_on_a_hand_built_trace(
        monkeypatch):
    assert scopered_xatt.sub_of(PROJ_B) == "xatt_project"
    assert scopered_xatt.sub_of(NORM_F) == "xatt_norm"
    assert scopered_xatt.sub_of(SLOTS) == "xatt_fwd"
    assert scopered_xatt.sub_of(TAIL) == "xatt_src"
    assert scopered_xatt.sub_of(MAX) == "xatt_max"
    assert scopered_xatt.sub_of(P + "sgcn.agg_slots/sgcn.att_max/max:") \
        is None
    red = scopered_xatt.reduce_xatt(_planes(), runs=1, epochs=1)
    assert red == {"xatt_project": pytest.approx(60e-6),
                   "xatt_norm": pytest.approx(40e-6),
                   "xatt_fwd": pytest.approx(300e-6),
                   "xatt_src": pytest.approx(100e-6),
                   "xatt_max": pytest.approx(70e-6)}
    monkeypatch.setitem(scopered_xatt._memo, "table", red)
    monkeypatch.setitem(scopered._memo, "table", scopered.reduce_scopes(
        _planes(), runs=1, epochs=1))
    cfg = _config()
    run = {"trace": {"epochs": 1, "busy_s": 500e-6}, "config": cfg,
           "nnz": NNZ, "chips": 1, "device_kind": "TPU v5 lite"}
    assert _reader("xatt_project_s").read(run) == pytest.approx(60e-6)
    assert _reader("xatt_norm_s").read(run) == pytest.approx(40e-6)
    assert _reader("xatt_max_s").read(run) == pytest.approx(70e-6)
    least = costmodel_xatt.agg_bytes_per_epoch(NNZ, cfg) / 819e9
    assert _reader("xatt_agg_roofline").read(run) \
        == pytest.approx(100 * least / 470e-6)
    # another model's configuration reads nothing
    gat = manifest.read_json(os.path.join(BENCH, "configs",
                                          "gat-products-3x4x128.json"))
    for other in ({"widths": [128, 47]}, gat):
        assert _reader("xatt_agg_roofline").read(
            dict(run, config=other)) is None


def test_a_program_without_the_sub_scopes_or_the_counter_reads_nothing(
        monkeypatch):
    from sgcn_tpu.obs import tracing

    monkeypatch.setitem(scopered_xatt._memo, "table", None)
    monkeypatch.setitem(scopered._memo, "table", None)
    monkeypatch.setattr(tracing, "_counters", {})
    run = {"trace": {"epochs": 1, "busy_s": 1.0}, "config": _config(),
           "nnz": NNZ, "chips": 1, "device_kind": "TPU v5 lite"}
    assert [_reader(n).read(run) for n in READERS] == [None] * len(READERS)
    # a parent's trace: no op carries a sub-scope token
    plain = _planes()
    for ev in plain[0]["lines"]["XLA Ops"]:
        for sub in scopered_xatt.SUBSCOPES:
            ev[3]["tf_op"] = ev[3]["tf_op"].replace(f"sgcn.{sub}/", "")
    assert scopered_xatt.reduce_xatt(plain, 1, 1) is None
    assert scopered_xatt.reduce_xatt([], 1, 1) is None
    assert _reader("xatt_project_s").read(dict(run, trace={})) is None
    # the counter alone: every pass's lanes over the true edges
    tracing.set_counter("xatt.work", {"true_edges_per_pass": [10, 30],
                                      "lanes_per_epoch": [31_440, 94_320]})
    assert _reader("xatt_lanes_per_edge").read(run) == pytest.approx(3_144)


def test_the_counter_of_the_program_gives_the_lanes_the_cost_model_least():
    """The program's own ``xatt.work`` at the configuration's widths: the
    least count of ``costmodel_xatt`` plus the max pass's K·C a layer."""
    from sgcn_tpu.models import unimp

    lanes = [unimp.pass_lanes(k, c) for k, c in
             costmodel_xatt.heads_and_channels(_config())]
    assert sum(sum(x.values()) for x in lanes) \
        == costmodel_xatt.lanes_per_edge(_config()) + 128 + 128 + 188


# ----------------------------------------------------------- the runner
class _Ref:
    """The reference's surface, recording what the runner hands it."""
    LOGITS_CHECKS = (("highest", "max", 2e-4), ("highest", "rms", 2e-5))

    def __init__(self):
        self.calls = []

    def coo_chunks(self, indptr, indices, data):
        return np.zeros(1, np.int32)

    def logits(self, params, edges, h0, precision, model, activation,
               table_dtype=None, labels=None):
        self.calls.append((precision, table_dtype, labels))
        out = np.ones((3, 2), np.float32)
        return out * 1.01 if table_dtype else out


def test_the_runner_hands_the_reference_the_runs_labels(capsys):
    import jax
    import scipy.sparse as sp

    runner = manifest.load_module(os.path.join(BENCH, "runners",
                                               "fullbatch_label.py"))
    for fn in ("build", "warm", "sample", "traced", "release",
               "first_updates", "reference_losses", "logits_pair"):
        assert callable(getattr(runner, fn)), fn

    class Ctx:
        cell = manifest.resolve(CELL)
        ahat = sp.eye(3, format="csr")
        feats = np.zeros((3, 2), np.float32)
        labels = np.arange(3, dtype=np.int32)
        devices = jax.devices("cpu")[:1]

    state = manifest.load_module(os.path.join(BENCH, "runlib.py")).State()
    state.extra["final"] = ({}, np.ones((3, 2), np.float32))
    ref = _Ref()
    got, theirs = runner.logits_pair(state, Ctx(), ref,
                                     ["highest", "highest"])
    assert list(theirs) == ["highest"]
    assert [c[:2] for c in ref.calls] == [("highest", None),
                                          ("highest", "bfloat16")]
    for _, _, labels in ref.calls:
        assert np.array_equal(np.asarray(labels), Ctx.labels)
    assert '"bf16_table_reference"' in capsys.readouterr().out
