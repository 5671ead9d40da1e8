"""The benchmark's slot prices (PR 35): ``scopered_slots`` groups a traced
window's seconds under the aggregation scopes by the bucket, fold-row and
pair tokens the program names itself by, joins each group to the program
counter ``slots.work`` and gives seconds, executed slots and ns each; five
per-layer metrics read it.  The vocabulary is the program's; the metrics are
additions to the manifest (membership and relative order, never the tail:
ROADMAP C15); a hand-built trace gives hand-computed figures; a program
without the tokens or the counter, and a run without a trace, read nothing.
CPU only.
"""

import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import manifest  # noqa: E402
import scopered  # noqa: E402
import scopered_slots  # noqa: E402

READERS = ("ell_slot_ns", "fold_slot_ns", "fold_row_ns",
           "scanned_slot_share", "agg_unbucketed_share")
CELLS = ("products.fullbatch", "products.fullbatch-gp4",
         "products8-gat.fullbatch", "products8-deepergcn.fullbatch",
         "mag-rgcn.fullbatch")
P = "jit(per_chip)/shard_map/"
ELL_F = P + "jvp(sgcn.layer1)/sgcn.agg_slots/sgcn.bkt_100x8_s4/while/body/add:"
ELL_B = (P + "transpose(jvp(sgcn.layer1))/sgcn.agg_slots/sgcn.bkt_100x8_s4/"
         "while/body/jit(_take)/gather:")
ELL_U = P + "jvp(sgcn.layer1)/sgcn.agg_slots/sgcn.bkt_40x2_u/add:"
CONCAT = P + "jvp(sgcn.layer1)/sgcn.agg_slots/concatenate:"
TAIL_F = P + "jvp(sgcn.layer1)/sgcn.agg_tail/sgcn.bkt_10x4_s2/while/body/add:"
ROWS_F = P + "jvp(sgcn.layer1)/sgcn.agg_tail/sgcn.fold_rows/scatter-add:"
HALO_F = (P + "jvp(sgcn.layer1)/sgcn.agg_halo_fold/sgcn.bkt_10x4_s2/while/"
          "body/add:")
MAX_F = (P + "jvp(sgcn.layer0)/sgcn.agg_slots/sgcn.att_max/"
         "sgcn.bkt_100x8_s4/while/body/max:")
SCORE_F = (P + "jvp(sgcn.layer0)/sgcn.agg_slots/sgcn.bkt_100x8_s4/while/"
           "body/sgcn.att_score/exp:")
PAIR_B = (P + "transpose(jvp(sgcn.layer0))/sgcn.pair_2_0/sgcn.agg_tail/"
          "sgcn.bkt_10x4_s2/while/body/add:")
DENSE = P + "jvp(sgcn.layer1)/sgcn.dense/dot_general:"
PSUM = P + "jvp(sgcn.layer1)/sgcn.agg_slots/sgcn.bkt_100x8_s4/psum:"


def _reader(name):
    return manifest.load_module(os.path.join(BENCH, "layer_metrics",
                                             name + ".py"))


def _pass(layer, way, tags=(), times=1, ell=(), tail=(), halo=()):
    entry = lambda b: {"rows": b[0], "width": b[1], "form": b[2]}  # noqa: E731
    return {"layer": layer, "way": way, "tags": list(tags), "lanes": 128,
            "times_per_epoch": times, "true_edges": [1],
            "stores": {"ell": [entry(b) for b in ell],
                       "tail": [entry(b) for b in tail],
                       "halo": [entry(b) for b in halo]}}


# two equal-shaped scanned buckets and an unrolled one in layer 1's ELL
ELL = ((100, 8, "s4"), (100, 8, "s4"), (40, 2, "u"))
WORK = {"passes": [_pass(1, "fwd", ell=ELL, tail=[(10, 4, "s2")],
                         halo=[(10, 4, "s2")]),
                   _pass(1, "bwd", ell=ELL, tail=[(10, 4, "s2")],
                         halo=[(10, 4, "s2")])],
        "per_epoch": {"ell_slots": 2 * 1680, "fold_slots": 2 * 80,
                      "virtual_rows": 2 * 20, "scanned_slots": 2 * 1680,
                      "true_edges": [2]}}


def _planes(chips=1, strip=False):
    """``chips`` devices, two runs of program P (0–1000 µs, 1000–2000 µs);
    the first holds the ops below back to back, chip c's each c + 1 times
    as long."""
    us = 1e3
    spec = [(ELL_F, 160), (ELL_B, 240), (ELL_U, 8), (CONCAT, 12),
            (TAIL_F, 4), (ROWS_F, 3), (HALO_F, 6), (DENSE, 50), (PSUM, 5)]
    out = []
    for c in range(chips):
        ops, t = [], 0.0
        for i, (tf_op, dur) in enumerate(spec):
            if strip:                           # a parent's program
                tf_op = re.sub(r"sgcn\.(bkt_\w+|fold_rows|pair_\w+)/", "",
                               tf_op)
            name = f"all-reduce.{i}" if tf_op.endswith("psum:") \
                else f"fusion.{i}"
            ops.append([name, t * us, dur * us / chips * (c + 1),
                        {"tf_op": tf_op}])
            t += dur
        out.append({"name": f"/device:TPU:{c}", "lines": {
            "XLA Modules": [["P(1)", 0.0, 1000 * us, {}],
                            ["P(1)", 1000 * us, 1000 * us, {}]],
            "XLA Ops": ops}})
    return out


# ------------------------------------------------------------------ manifest
def test_the_five_metrics_are_additions_to_the_manifest():
    bench = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in READERS]
    # in this order, after every metric of the accepted benchmark
    assert at == sorted(at) and at[0] > names.index("rel_rows_owned_gb")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}                  # no list: every cell
        assert (m["layer"], m["moves"], m["better"]) \
            == ("device_compute", "epoch_s", "lower")
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert [by_name[n]["unit"] for n in READERS] \
        == ["ns", "ns", "ns", "%", "%"]
    assert [by_name[n]["source"] for n in READERS] == [
        "device_trace"] * 3 + ["program_counter", "device_trace"]
    assert set(CELLS) <= {w["name"] for w in bench["workloads"]}
    for cell in CELLS:
        assert set(READERS) <= {n for n, _, _ in
                                manifest.resolve(cell).per_layer}
    # PERF.md's list of layers names the row the new metrics stand in
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    row = next(ln for ln in perf.splitlines()
               if ln.startswith("| `device_compute`"))
    for word in READERS + ("slots.work", "sgcn.bkt_", "sgcn.fold_rows",
                           "sgcn.pair_"):
        assert word in row, word


def test_the_token_patterns_are_the_programs():
    from sgcn_tpu.obs import tracing

    vocab = manifest.read_json(os.path.join(BENCH, "scopes_slots.json"))
    assert set(vocab) == {"prefix", "describes", "bucket", "fold_rows",
                          "pair", "tags", "stores"}
    assert vocab["prefix"] == tracing.PREFIX == scopered.PREFIX
    assert vocab["bucket"] == tracing.BUCKET_TOKEN
    assert vocab["pair"] == tracing.PAIR_TOKEN
    assert (vocab["fold_rows"],) == tracing.SLOT_SUBSCOPES
    assert set(vocab["tags"]) <= set(tracing.SUBSCOPES)
    assert set(vocab["stores"]) == {"agg_slots", "agg_tail",
                                    "agg_halo_fold"} < set(tracing.SCOPES)
    # the yardstick's vocabulary moves only with a PR that says so
    assert vocab["bucket"] == r"bkt_(\d+)x(\d+)_(u|s\d+)"
    assert vocab["pair"] == r"pair_(\d+)_(\d+)"
    assert vocab["tags"] == ["att_max"]
    # every token is one of scopered's, and none is in its vocabulary: an
    # op still books to its leaf
    for token in ("bkt_991392x16_s1", "fold_rows", "pair_0_3"):
        assert scopered.TOKEN.fullmatch(scopered.PREFIX + token)
        assert token not in scopered.LEAVES
    assert tracing.parse_bucket_token("bkt_991392x16_s1") \
        == (991392, 16, "s1")
    assert "import sgcn_tpu" not in open(scopered_slots.__file__).read()


def test_the_group_of_an_op():
    key = scopered_slots.key_of
    assert key(ELL_F) == ("layer1", "agg_slots", "fwd", (), "bkt_100x8_s4")
    assert key(ELL_B) == ("layer1", "agg_slots", "bwd", (), "bkt_100x8_s4")
    assert key(CONCAT) == ("layer1", "agg_slots", "fwd", (), None)
    assert key(ROWS_F) == ("layer1", "agg_tail", "fwd", (), "fold_rows")
    assert key(HALO_F)[1::3] == ("agg_halo_fold", "bkt_10x4_s2")
    # the max pass is told from the aggregation of its layer by its tag; a
    # sub-scope that is no tag (att_score) is not
    assert key(MAX_F) == ("layer0", "agg_slots", "fwd", ("att_max",),
                          "bkt_100x8_s4")
    assert key(SCORE_F) == ("layer0", "agg_slots", "fwd", (),
                            "bkt_100x8_s4")
    assert key(PAIR_B) == ("layer0", "agg_tail", "bwd", ("pair_2_0",),
                           "bkt_10x4_s2")
    assert key(DENSE) is None and key("") is None
    # and the accepted reduction books the same ops as before
    assert scopered.scope_of(ELL_B) == ("layer1", "agg_slots", "bwd")
    assert scopered.scope_of(PAIR_B) == ("layer0", "agg_tail", "bwd")
    assert scopered.scope_of(ROWS_F) == ("layer1", "agg_tail", "fwd")


# ------------------------------------------------------ the hand-built trace
def test_grouping_join_and_prices_on_a_hand_built_trace():
    red = scopered_slots.reduce_slots(_planes(), runs=1, epochs=1)
    assert red["chips"] == 1
    # the psum under a bucket is a collective: booked apart, no slot work
    assert sum(red["mean"].values()) == pytest.approx(433e-6)
    tab = scopered_slots.prices(red, WORK)
    rows = {tuple(r[1:3]) + (r[4],): dict(zip(tab["columns"], r))
            for r in tab["rows"]}
    # two equal-shaped buckets of one pass are one group, and one count
    got = rows["agg_slots", "fwd", "bkt_100x8_s4"]
    assert got["count"] == 1600 and got["seconds"] == pytest.approx(160e-6)
    assert got["ns"] == pytest.approx(100.0)
    assert rows["agg_slots", "bwd", "bkt_100x8_s4"]["ns"] \
        == pytest.approx(150.0)
    assert rows["agg_slots", "fwd", "bkt_40x2_u"]["ns"] \
        == pytest.approx(100.0)
    assert rows["agg_tail", "fwd", "bkt_10x4_s2"]["ns"] \
        == pytest.approx(100.0)
    assert rows["agg_halo_fold", "fwd", "bkt_10x4_s2"]["ns"] \
        == pytest.approx(150.0)
    # fold rows are priced per virtual row of their store
    assert rows["agg_tail", "fwd", "fold_rows"]["count"] == 10
    assert rows["agg_tail", "fwd", "fold_rows"]["ns"] == pytest.approx(300.0)
    unb = rows["agg_slots", "fwd", None]
    assert (unb["count"], unb["ns"]) == (None, None)
    assert unb["seconds"] == pytest.approx(12e-6)
    sums = tab["sums"]
    assert sums["ell_s"] == pytest.approx(408e-6)
    assert sums["fold_s"] == pytest.approx(10e-6)
    assert sums["rows_s"] == pytest.approx(3e-6)
    assert sums["unbucketed_s"] == pytest.approx(12e-6)
    # every group found its count: the prices give the scopes' seconds back
    assert sums["priced_s"] == pytest.approx(sums["agg_s"]) \
        == pytest.approx(433e-6)
    # the backward's tail and halo store left no op here: joined < counted
    assert tab["joined"] == {"ell_slots": 1600 + 1600 + 80,
                             "fold_slots": 80, "virtual_rows": 10}
    # a bucket no pass lists keeps its seconds and gets no price
    odd = scopered_slots.prices(red, dict(WORK, passes=WORK["passes"][1:]))
    assert odd["sums"]["priced_s"] == pytest.approx((240 + 12) * 1e-6)
    assert [r[5] for r in odd["rows"] if r[2] == "fwd"] == [None] * 6


def test_one_token_over_several_runs_and_tagged_passes_join():
    """The deep stack's scanned body (one token, 13 layers) and a pass under
    ``keep="input"`` that shares layer, direction and bucket with another;
    a typed pass joins by its pair."""
    work = {"passes": [_pass(1, "bwd", times=13, ell=ELL[:1]),
                       _pass(1, "bwd", times=13, ell=ELL[:1]),
                       _pass(0, "bwd", tags=["pair_2_0"],
                             tail=[(10, 4, "s2")]),
                       _pass(0, "bwd", tags=["pair_1_0"],
                             tail=[(10, 4, "s2")])]}
    ex = scopered_slots.executed
    assert ex(work, scopered_slots.key_of(ELL_B)) == 2 * 13 * 800
    assert ex(work, scopered_slots.key_of(PAIR_B)) == 40
    assert ex(work, scopered_slots.key_of(PAIR_B)[:4] + ("fold_rows",)) == 10
    assert ex(work, scopered_slots.key_of(ELL_F)) is None       # no fwd pass
    assert ex(work, scopered_slots.key_of(MAX_F)) is None       # no such tag


def test_per_chip_columns_and_the_mean_on_two_chips():
    red = scopered_slots.reduce_slots(_planes(chips=2), runs=1, epochs=1)
    assert red["chips"] == 2
    key = scopered_slots.key_of(ELL_F)
    assert [chip[key] for chip in red["per_chip"]] \
        == [pytest.approx(80e-6), pytest.approx(160e-6)]
    assert red["mean"][key] == pytest.approx(120e-6)
    tab = scopered_slots.prices(red, WORK)
    row = next(r for r in tab["rows"] if r[1:3] + [r[4]] == [
        "agg_slots", "fwd", "bkt_100x8_s4"])
    assert row[-1] == [pytest.approx(80e-6), pytest.approx(160e-6)]
    assert row[7] == pytest.approx(75.0)
    # more than one traced epoch: seconds per epoch
    half = scopered_slots.reduce_slots(_planes(), runs=1, epochs=2)
    assert half["mean"][key] == pytest.approx(80e-6)


def test_a_token_free_plane_gives_nothing():
    assert scopered_slots.reduce_slots(_planes(strip=True), 1, 1) is None
    assert scopered_slots.reduce_slots([], 1, 1) is None
    # ... while the accepted reduction reads what it read
    want = scopered.reduce_scopes(_planes(), 1, 1)["mean"]
    assert scopered.reduce_scopes(_planes(strip=True), 1, 1)["mean"] == want
    assert scopered.seconds(want, "agg_slots") == pytest.approx(420e-6)


# ---------------------------------------------------------------- the readers
def test_the_five_readers_on_the_hand_built_trace(monkeypatch):
    from sgcn_tpu.obs import tracing

    red = scopered_slots.reduce_slots(_planes(), runs=1, epochs=1)
    monkeypatch.setitem(scopered_slots._memo, "table",
                        scopered_slots.prices(red, WORK))
    monkeypatch.setattr(tracing, "_counters", {"slots.work": WORK})
    run = {"trace": {"epochs": 1, "busy_s": 488e-6}, "config": {}, "nnz": 1,
           "chips": 1, "device_kind": "TPU v5 lite"}
    got = {n: _reader(n).read(run) for n in READERS}
    assert got["ell_slot_ns"] == pytest.approx(408e-6 * 1e9 / 3360)
    assert got["fold_slot_ns"] == pytest.approx(10e-6 * 1e9 / 160)
    assert got["fold_row_ns"] == pytest.approx(3e-6 * 1e9 / 40)
    assert got["scanned_slot_share"] == pytest.approx(100 * 3360 / 3520)
    assert got["agg_unbucketed_share"] == pytest.approx(100 * 12 / 433)
    # an untraced run reads nothing, and does not raise
    assert [_reader(n).read(dict(run, trace={})) for n in READERS] \
        == [None] * 5


def test_a_program_without_the_tokens_or_the_counter_reads_nothing(
        monkeypatch, tmp_path):
    from sgcn_tpu.obs import tracing

    run = {"trace": {"epochs": 1, "busy_s": 1.0}, "config": {}, "nnz": 1,
           "chips": 1, "device_kind": "TPU v5 lite"}
    # a parent commit: no counter (its trace is not even opened) ...
    monkeypatch.setattr(tracing, "_counters", {})
    monkeypatch.delitem(scopered_slots._memo, "table", raising=False)
    monkeypatch.setattr(scopered, "newest_trace", lambda: 1 / 0)
    assert [_reader(n).read(run) for n in READERS] == [None] * 5
    # ... a trainer that left ``None`` (a program with no pass list) ...
    monkeypatch.delitem(scopered_slots._memo, "table", raising=False)
    monkeypatch.setattr(tracing, "_counters", {"slots.work": None})
    assert [_reader(n).read(run) for n in READERS] == [None] * 5
    # ... and the counter without a trace file: only the count reads
    monkeypatch.delitem(scopered_slots._memo, "table", raising=False)
    monkeypatch.setattr(tracing, "_counters", {"slots.work": WORK})
    monkeypatch.setattr(scopered, "newest_trace", lambda: None)
    got = [_reader(n).read(run) for n in READERS]
    assert got[:3] == [None] * 3 and got[4] is None
    assert got[3] == pytest.approx(100 * 3360 / 3520)
