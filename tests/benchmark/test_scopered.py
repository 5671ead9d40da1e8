"""The reduction of a trace by the program's own scopes, and the layer metrics
that read it (``benchmark/scopered.py``, PR 25's readers): the vocabulary is
the program's, a hand-built two-chip trace gives hand-computed seconds, the
recorded scoped steps are pinned, and every new manifest entry keeps the
rules the old ones are held to.  CPU only; nothing here describes a topology.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import manifest  # noqa: E402
import scopered  # noqa: E402
import tracered  # noqa: E402

FIXTURES = os.path.join(BENCH, "fixtures")
DEVICE = ("agg_slots_s", "agg_tail_s", "agg_halo_fold_s", "dense_s",
          "loss_opt_s", "unscoped_share", "xchg_pack_s", "xchg_unpack_s")
PLAN = tuple(f"plan_{p}_s" for p in ("relabel", "halo", "edges", "ell",
                                     "symmetric"))
NEW = DEVICE + ("step_dispatch_s",) + PLAN + ("agg_useful_share",)
GP4_ONLY = {"agg_halo_fold_s", "xchg_pack_s", "xchg_unpack_s"}


def _reader(name):
    return manifest.load_module(os.path.join(BENCH, "layer_metrics",
                                             name + ".py"))


def test_the_benchmarks_vocabulary_is_the_programs():
    from sgcn_tpu.obs import tracing

    vocab = manifest.read_json(os.path.join(BENCH, "scopes.json"))
    assert tuple(vocab["scopes"]) == tracing.SCOPES
    assert vocab["prefix"] == tracing.PREFIX
    assert scopered.LAYER == "layer" and "layer" not in scopered.LEAVES
    assert set(scopered.COLLECTIVE_SCOPES) <= set(scopered.LEAVES)


# tf_op strings as the step compiled for v5e:2x2 prints them (PR 25, sandbox)
P = "jit(per_chip)/shard_map/"
SLOTS_F0 = P + "jvp(sgcn.layer0)/sgcn.agg_slots/jit(_take)/gather:"
SLOTS_B1 = P + "transpose(jvp(sgcn.layer1))/sgcn.agg_slots/jit(_take)/gather:"
TAIL_F1 = P + "jvp(sgcn.layer1)/sgcn.agg_tail/scatter-add:"
FOLD_B1 = P + "transpose(jvp(sgcn.layer1))/sgcn.agg_halo_fold/scatter-add:"
DENSE_B0 = P + "transpose(jvp(sgcn.layer0))/sgcn.dense/dot_general:"
DENSE_PSUM = P + "transpose(jvp(sgcn.layer0))/sgcn.dense/psum_invariant:"
PACK_F1 = P + "jvp(sgcn.layer1)/sgcn.xchg_pack/jit(_take)/gather:"
A2A_F1 = P + "jvp(sgcn.layer1)/sgcn.xchg_a2a/all_to_all:"
UNPACK_F1 = P + "jvp(sgcn.layer1)/sgcn.xchg_unpack/jit(_take)/gather:"
LOSS_B = P + "transpose(jvp(sgcn.loss))/jit(log_softmax)/div:"
GRAD_PSUM = P + "sgcn.grad_psum/psum_invariant:"
ADAM = P + "sgcn.optimizer/add:"
RELU = P + "jvp(sgcn.layer0)/max:"           # a layer, no leaf scope
BARE = P + "broadcast.18:"                   # neither


def test_scope_of_reads_tokens_wherever_the_transforms_put_them():
    assert scopered.scope_of(SLOTS_F0) == ("layer0", "agg_slots", "fwd")
    assert scopered.scope_of(SLOTS_B1) == ("layer1", "agg_slots", "bwd")
    assert scopered.scope_of(LOSS_B) == ("-", "loss", "bwd")
    assert scopered.scope_of(GRAD_PSUM) == ("-", "grad_psum", "fwd")
    assert scopered.scope_of(RELU) == ("layer0", "unscoped", "fwd")
    assert scopered.scope_of(BARE) == ("-", "unscoped", "fwd")
    assert scopered.scope_of("") == ("-", "unscoped", "fwd")
    # the LAST leaf wins; a token outside the vocabulary is no scope
    assert scopered.scope_of(P + "sgcn.agg_slots/sgcn.agg_tail/add:")[1] \
        == "agg_tail"
    assert scopered.scope_of(P + "sgcn.step.dispatch/add:")[1] == "unscoped"
    # layer10 is a layer, xchg_a2a is not mistaken for one
    assert scopered.scope_of(P + "jvp(sgcn.layer10)/sgcn.xchg_a2a/x:")[:2] \
        == ("layer10", "xchg_a2a")


def _two_chip_planes():
    """Two chips, two runs of program P (0–100 µs, 200–300 µs), window =
    one period.  Chip 0: a while (0–40) over two slot gathers (0–10 forward
    layer 0, 10–40 backward layer 1), tail 40–45, fold 45–50, dense 50–52, the
    psum the transpose put under dense 52–60, pack / a2a / unpack 60–62 / 62–70
    / 70–73, loss 73–74, grad psum 74–75, Adam 75–77, a relu 77–78, a bare
    broadcast 78–80.  Chip 1 is the same but its backward slot gather is 10 µs
    shorter and its all_to_all as much longer (it waits)."""
    us = 1e3

    def chip(name, slots_b, a2a):
        t, ops = 0.0, [["while.1", 0.0, 40 * us, {"tf_op": SLOTS_F0}]]
        for op, tf, dur in (
                ("fusion.1", SLOTS_F0, 10), ("fusion.2", SLOTS_B1, slots_b),
                ("fusion.3", TAIL_F1, 5), ("fusion.4", FOLD_B1, 5),
                ("convolution.5", DENSE_B0, 2),
                ("all-reduce.6", DENSE_PSUM, 8), ("fusion.7", PACK_F1, 2),
                ("all_to_all.8", A2A_F1, a2a), ("fusion.9", UNPACK_F1, 3),
                ("fusion.10", LOSS_B, 1), ("psum_invariant.11", GRAD_PSUM, 1),
                ("fusion.12", ADAM, 2), ("fusion.13", RELU, 1),
                ("fusion.14", BARE, 2)):
            ops.append([op, t * us, dur * us, {"tf_op": tf}])
            t += dur
        ops.append(["fusion.1", 200 * us, 10 * us, {"tf_op": SLOTS_F0}])
        return {"name": name, "lines": {
            "XLA Modules": [["P(1)", 0.0, 100 * us, {}],
                            ["P(1)", 200 * us, 100 * us, {}]],
            "XLA Ops": ops}}

    return [chip("/device:TPU:0", 30, 8), chip("/device:TPU:1", 20, 18),
            {"name": "/host:CPU", "lines": {"python": [
                ["bench.step.dispatch", 0.0, 8 * us, {}]]}}]


def test_reduction_by_scope_gives_hand_computed_seconds():
    red = scopered.reduce_scopes(_two_chip_planes(), runs=1, epochs=1)
    assert red["chips"] == 2
    c0, c1 = red["per_chip"]
    us = pytest.approx
    assert c0[("layer0", "agg_slots", "fwd")] == us(10e-6)    # not the while
    assert c0[("layer1", "agg_slots", "bwd")] == us(30e-6)
    assert c1[("layer1", "agg_slots", "bwd")] == us(20e-6)
    assert c0[("layer1", "agg_tail", "fwd")] == us(5e-6)
    assert c0[("layer1", "agg_halo_fold", "bwd")] == us(5e-6)
    assert c0[("layer0", "dense", "bwd")] == us(2e-6)
    assert c0[("layer0", "dense:collective", "bwd")] == us(8e-6)
    assert c0[("layer1", "xchg_a2a", "fwd")] == us(8e-6)
    assert c1[("layer1", "xchg_a2a", "fwd")] == us(18e-6)
    assert c0[("-", "grad_psum", "fwd")] == us(1e-6)
    assert c0[("layer0", "unscoped", "fwd")] == us(1e-6)
    assert c0[("-", "unscoped", "fwd")] == us(2e-6)
    assert len(c0) == 14
    # all rows together are the device's busy seconds, chip by chip
    busy = tracered.reduce_trace(_two_chip_planes(), 1)["per_chip"]
    assert sum(c0.values()) == us(busy[0]["busy_s"]) == us(80e-6)
    assert sum(c1.values()) == us(busy[1]["busy_s"])
    mean = red["mean"]
    assert mean[("layer1", "agg_slots", "bwd")] == us(25e-6)
    assert scopered.seconds(mean, "agg_slots") == us(35e-6)
    assert scopered.seconds(mean, "loss", "optimizer") == us(3e-6)
    assert scopered.seconds(mean, "dense") == us(2e-6)    # without the psum
    assert scopered.by_scope(c0)["layer1/agg_slots/bwd"] == 30e-6
    # two epochs in the window halve every figure
    planes = _two_chip_planes()
    two = scopered.reduce_scopes(planes, runs=2, epochs=2)
    assert two["per_chip"][0][("layer0", "agg_slots", "fwd")] == us(10e-6)
    assert two["per_chip"][0][("layer1", "agg_tail", "fwd")] == us(2.5e-6)


def test_a_program_without_scopes_or_a_run_without_a_trace_reads_nothing(
        monkeypatch):
    planes = _two_chip_planes()
    for p in planes[:2]:
        for ev in p["lines"]["XLA Ops"]:
            ev[3] = {"tf_op": "jit(per_chip)/shard_map/jvp(jit(_take))/gather:"}
    assert scopered.reduce_scopes(planes, 1, 1) is None       # a parent commit
    assert scopered.reduce_scopes(planes[2:], 1, 1) is None    # no device
    monkeypatch.setattr(scopered, "_memo", {})
    monkeypatch.setattr(scopered, "newest_trace", lambda: pytest.fail(
        "a run without a trace must not look for one"))
    for name in DEVICE + ("step_dispatch_s",):
        assert _reader(name).read({"trace": {}, "spans": {}}) is None, name


def _pretend(monkeypatch, planes, epochs=1):
    """A run whose process 'just wrote' ``planes``."""
    monkeypatch.setattr(scopered, "_memo", {})
    monkeypatch.setattr(scopered, "newest_trace", lambda: "trace.xplane.pb")
    monkeypatch.setattr(tracered, "load_xplane", lambda path: planes)
    monkeypatch.setattr(scopered, "program_spans", lambda path: [
        ["sgcn.train_step", 0.0, 190e3], ["sgcn.step.readback", 90e3, 190e3]])
    return {"trace": {"epochs": epochs}, "spans": {}}


def test_readers_and_bench_lines_on_the_hand_built_trace(monkeypatch, capsys):
    run = _pretend(monkeypatch, _two_chip_planes())
    want = {"agg_slots_s": 35e-6, "agg_tail_s": 5e-6, "agg_halo_fold_s": 5e-6,
            "dense_s": 2e-6, "loss_opt_s": 3e-6, "xchg_pack_s": 2e-6,
            "xchg_unpack_s": 3e-6,
            # a relu and a broadcast: 3 µs of 80
            "unscoped_share": 100 * 3 / 80}
    for name, value in want.items():
        assert _reader(name).read(run) == pytest.approx(value), name
    said = [json.loads(ln[len("bench: "):])
            for ln in capsys.readouterr().out.splitlines()]
    assert len(said) == 2                       # reduced once, memoised
    chips = said[0]["scopes_per_chip"]
    assert len(chips) == 2 and chips[1]["layer1/xchg_a2a/fwd"] == 18e-6
    # the gap 80–200 µs of chip 0 goes to the innermost program span over it
    assert said[1]["idle_gaps_by_program_span"] \
        == {"sgcn.step.readback": pytest.approx(120e-6)}


def test_program_span_and_counter_readers(monkeypatch):
    from sgcn_tpu.obs import tracing

    tracing.reset_spans()
    monkeypatch.setattr(tracing, "_counters", {})
    for name in PLAN + ("agg_useful_share", "step_dispatch_s"):
        assert _reader(name).read({"trace": {"epochs": 1}}) is None, name
    row = {"count": 3, "total_s": 9.0, "parent": None}
    monkeypatch.setattr(tracing, "_spans", {
        "step.dispatch": dict(row, durations=[7.0, 0.5, 1.5]),
        "plan.halo": dict(row, durations=[4.0, 5.0])})
    assert _reader("step_dispatch_s").read({"trace": {"epochs": 1}}) == 1.5
    assert _reader("step_dispatch_s").read({"trace": {}}) is None
    assert _reader("plan_halo_s").read({"trace": {}}) == 4.0   # first build
    assert _reader("plan_ell_s").read({"trace": {}}) is None
    tracing.set_counter("plan.work_counts", {
        "true": {"slot_edges": [60, 30], "tail_edges": [10, 0],
                 "halo_edges": [10, 30], "halo_rows": [5, 5],
                 "rows_sent": [5, 5]},
        "executed": {"slot_edges": 70, "tail_edges": 10, "halo_edges": 20,
                     "halo_rows": 5, "rows_sent": 10}})
    # (80 + 60) true edges over 2 × 100 executed slots
    assert _reader("agg_useful_share").read({"trace": {}}) \
        == pytest.approx(70.0)
    # a program without the tables (a parent commit) reads nothing
    monkeypatch.delattr(tracing, "span_totals")
    monkeypatch.delattr(tracing, "counters")
    for name in PLAN + ("agg_useful_share", "step_dispatch_s"):
        assert _reader(name).read({"trace": {"epochs": 1}}) is None, name


def test_the_trace_must_be_this_processs_own(tmp_path, monkeypatch):
    monkeypatch.setattr(scopered.inputs, "CACHE_DIR", str(tmp_path))
    assert scopered.newest_trace() is None
    d = tmp_path / "trace" / "cell" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    old, new = d / "old.xplane.pb", d / "new.xplane.pb"
    old.write_bytes(b""), new.write_bytes(b"")
    os.utime(old, (scopered.LOADED_AT - 100,) * 2)
    assert scopered.newest_trace() == str(new)
    os.utime(new, (scopered.LOADED_AT - 50,) * 2)
    assert scopered.newest_trace() is None


# --------------------------------------------------------- recorded fixtures
# One step of each cell as the scoped program ran it on the chip (PR 25, cut
# with ``benchmark/record_fixture.py``); seconds per epoch, mean over chips.
RECORDED = {
    "products.fullbatch": {
        "chips": 1, "busy_s": 3.74118678,
        "agg_slots_s": 2.9058087, "agg_tail_s": 0.738317276,
        "dense_s": 0.018118877, "loss_opt_s": 0.036539975,
        "unscoped_share": 1.13324754,
        "rows": {"layer0/agg_slots/fwd": 0.972319,
                 "layer1/agg_slots/fwd": 0.96671,
                 "layer1/agg_slots/bwd": 0.966779,
                 "layer1/agg_tail/bwd": 0.24603}},
    "products.fullbatch-gp4": {
        "chips": 4, "busy_s": 1.54592770,
        "agg_slots_s": 0.651402876, "agg_tail_s": 0.125454618,
        "agg_halo_fold_s": 0.397102093, "dense_s": 0.004206589,
        "loss_opt_s": 0.0093870205, "xchg_pack_s": 0.076424402,
        "xchg_unpack_s": 0.0597430105, "unscoped_share": 3.29567711,
        "rows": {"layer0/agg_halo_fold/fwd": 0.133066,
                 "layer1/xchg_a2a/bwd": 0.059019,
                 "layer0/dense:collective/bwd": 0.004989,
                 "-/loss:collective/fwd": 0.004911}},
}
# what the per-chip table is for: chip 2, the one the other three wait for,
# is the slow one in the slot passes (and the fast one in the halo-edge fold)
GP4_SLOTS_BY_CHIP = [0.6121, 0.6150, 0.7664, 0.6122]


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_readers_on_the_recorded_scoped_step(cell, monkeypatch, capsys):
    want = dict(RECORDED[cell])
    planes = tracered.load_fixture(os.path.join(
        FIXTURES, cell + ".scoped.step1.events.json.gz"))
    run = _pretend(monkeypatch, planes)
    red = scopered.table(run)
    assert red["chips"] == want.pop("chips")
    for key, value in want.pop("rows").items():
        assert scopered.by_scope(red["mean"])[key] == pytest.approx(
            value, abs=1e-6), key
    # scopes and ``unscoped`` together are the device's busy seconds, on
    # every chip (the issue asks for 1 %; the two are the same leaf ops)
    busy = [c["busy_s"] for c in tracered.reduce_trace(planes, 1)["per_chip"]]
    assert [sum(rows.values()) for rows in red["per_chip"]] \
        == pytest.approx(busy, rel=1e-9)
    assert sum(busy) / len(busy) == pytest.approx(want.pop("busy_s"))
    reported = {m for m in DEVICE
                if m not in GP4_ONLY or red["chips"] == 4}
    assert set(want) == reported
    for name, value in want.items():
        assert _reader(name).read(run) == pytest.approx(value, rel=1e-6), name
    assert len(capsys.readouterr().out.splitlines()) == 2
    if cell == "products.fullbatch-gp4":
        assert [scopered.seconds(rows, "agg_slots")
                for rows in red["per_chip"]] == pytest.approx(
            GP4_SLOTS_BY_CHIP, abs=1e-4)


# ------------------------------------------------------------------ manifest
def test_every_new_metric_keeps_the_manifests_rules():
    b = manifest.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in b["per_layer"]}
    assert [m["name"] for m in b["per_layer"]][-len(NEW):] == list(NEW)
    with open(os.path.join(ROOT, "PERF.md")) as fh:
        perf = fh.read()
    e2e = {m["name"] for m in b["end_to_end"]}
    for name in NEW:
        m = entries[name]
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert f"| `{m['layer']}` " in perf and m["moves"] in e2e
        assert f"`{name}`" in perf, f"PERF.md §3 does not name {name}"
        assert m.get("workloads") == (["products.fullbatch-gp4"]
                                      if name in GP4_ONLY else None)
        assert callable(_reader(name).read)
    assert {entries[n]["source"] for n in DEVICE} == {"device_trace"}
    assert {entries[n]["source"] for n in PLAN + ("step_dispatch_s",)} \
        == {"program_span"}
    assert entries["agg_useful_share"]["source"] == "program_counter"
    for w in b["workloads"]:
        cell = manifest.resolve(w["name"])
        got = {n for n, _, _ in cell.per_layer}
        assert set(NEW) - GP4_ONLY <= got
        assert (GP4_ONLY <= got) == (w["chips"] == 4)
