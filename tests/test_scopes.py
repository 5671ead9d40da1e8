"""The program names its own work: ``jax.named_scope``s in the compiled step
(``obs.tracing.scope`` over the fixed vocabulary ``SCOPES``) and host spans on
the profiler's clock (``obs.tracing.span``).

  * the step both benchmark cells run, compiled for ``v5e:2x2`` without a
    chip: every gather / scatter-add / all_to_all fusion carries a leaf scope;
  * the lowered one-chip and four-virtual-device step names every scope;
  * ``span`` nests, fills ``span_totals()``, needs no active trace, and the
    module imports without jax; ``SpanTimer.span`` keeps its recorder event;
  * ``build_comm_plan``'s five spans cover the call, and it leaves
    ``CommPlan.work_counts()`` in ``counters()``;
  * scopes change no arithmetic: losses with the scopes nulled (the program
    as it was before them) are bit-identical.

The topology is described inside a fixture and nowhere else (libtpu admits
one process; see tests/test_pallas_tpu_aot.py).
"""

import contextlib
import importlib
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from sgcn_tpu.io.datasets import dcsbm_graph
from sgcn_tpu.obs import tracing
from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d, shard_stacked
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer, TrainData, make_train_data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, FIN, WIDTHS = 3000, 100, [128, 47]         # the products widths, small n
LEAVES = tuple(s for s in tracing.SCOPES if s != "layer")
TOKEN = re.compile(r"sgcn\.([A-Za-z0-9_]+)")


@pytest.fixture(scope="module")
def ahat():
    # hubs past the ELL width cap (a tail) and, split four ways, halo edges
    return normalize_adjacency(dcsbm_graph(N, ncomm=8, avg_deg=50, seed=0))


def _trainer(ahat, k, monkeypatch=None, scan=False):
    if scan:       # the lax.scan slot passes, as at the products shape
        monkeypatch.setattr(importlib.import_module("sgcn_tpu.ops.pspmm"),
                            "_CONCURRENT_TEMP_LIMIT", 0)
    pv = (np.zeros(N, np.int64) if k == 1
          else balanced_random_partition(N, k, seed=0))
    plan = build_comm_plan(ahat, pv, k)
    assert plan.symmetric and plan.ltail_nnz.sum() > 0
    tr = FullBatchTrainer(plan, fin=FIN, widths=WIDTHS, mesh=make_mesh_1d(k),
                          seed=3)
    assert tr.comm_schedule == "a2a" and "pallas_tb" not in tr._fwd_static
    return plan, tr


def _leaf(op_name: str):
    leaves = [t for t in TOKEN.findall(op_name) if t in LEAVES]
    return leaves[-1] if leaves else None


# ------------------------------------------------- (a) the v5e-compiled step
@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"v5e topology AOT unavailable: {e!r}")
    return topo.devices


OP = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"', re.M)


def test_v5e_compiled_step_scopes_every_gather_scatter_and_exchange(
        ahat, v5e_devices, monkeypatch):
    from jax.sharding import Mesh

    _, tr = _trainer(ahat, 4, monkeypatch, scan=True)
    mesh = Mesh(np.array(v5e_devices[:4]), ("v",))
    text = tr.lower_step(mesh).compile().as_text()
    hot = [(ins, op) for ins, op in OP.findall(text)
           if op.rstrip(":").rsplit("/", 1)[-1]
           in ("gather", "scatter-add", "all_to_all")]
    fusions = [(ins, op) for ins, op in hot if "fusion" in ins]
    assert len(fusions) > 20 and any(ins.startswith("all_to_all")
                                     or ins.startswith("all-to-all")
                                     for ins, _ in hot)
    bare = [(ins, op) for ins, op in hot if _leaf(op) is None]
    assert not bare, bare[:5]
    # the slot passes run as while loops here, and their bodies are named
    assert any(_leaf(op) == "agg_slots" and ins.startswith("while")
               for ins, op in OP.findall(text))
    found = {_leaf(op) for _, op in OP.findall(text)} - {None}
    assert found == set(LEAVES), set(LEAVES) - found
    # forward and backward are told apart by token
    assert any("transpose(" in op and "sgcn.layer1" in op
               and _leaf(op) == "agg_slots" for _, op in hot)
    assert any("transpose(" not in op and "sgcn.layer1" in op
               and _leaf(op) == "agg_tail" for _, op in hot)
    # layer 0's aggregation is hoisted out of the step (PR 26): the step
    # keeps its dense product and none of its gathers, folds or exchanges
    assert tr.hoist is not None
    assert not [op for _, op in hot if "sgcn.layer0" in op]
    assert any("sgcn.layer0" in op and _leaf(op) == "dense"
               for _, op in OP.findall(text))


# ------------------------------------------------------ (b) the lowered step
@pytest.mark.parametrize("k", [1, 4])
def test_lowered_step_names_every_scope(ahat, k):
    _, tr = _trainer(ahat, k)
    text = tr.lower_step().as_text(debug_info=True)
    tokens = set(TOKEN.findall(text))
    # one chip has no halo-source edge: since PR 30 a store without edges has
    # no pass, and nothing is sent for a table nobody reads
    halo = {"agg_halo_fold", "xchg_pack", "xchg_a2a", "xchg_unpack"}
    want = set(LEAVES) - (halo if k == 1 else set())
    assert want <= tokens, want - tokens
    assert k > 1 or not halo & tokens
    assert {"layer0", "layer1"} <= tokens
    assert tracing.SCOPES[0] == "layer"
    with pytest.raises(ValueError, match="unknown scope"):
        tracing.scope("aggregate")


# ----------------------------------------------------------- (c) span()
def test_span_nests_and_fills_the_table():
    tracing.reset_spans()
    assert tracing.span_totals() == {}
    for _ in range(3):
        with tracing.span("outer"):
            time.sleep(0.002)
            with tracing.span("inner"):
                time.sleep(0.004)
    with tracing.span("inner"):          # parent is the FIRST one seen
        pass
    tot = tracing.span_totals()
    assert tot["outer"]["count"] == 3 and tot["inner"]["count"] == 4
    assert tot["outer"]["parent"] is None and tot["inner"]["parent"] == "outer"
    assert tot["outer"]["total_s"] >= tot["inner"]["total_s"] >= 0.012
    assert len(tot["inner"]["durations"]) == 4
    assert sum(tot["outer"]["durations"]) == pytest.approx(
        tot["outer"]["total_s"])
    for _ in range(tracing.SPAN_KEEP + 10):
        with tracing.span("many"):
            pass
    many = tracing.span_totals()["many"]
    assert many["count"] == tracing.SPAN_KEEP + 10
    assert len(many["durations"]) == tracing.SPAN_KEEP
    # a raising body still closes its span
    with pytest.raises(RuntimeError):
        with tracing.span("boom"):
            raise RuntimeError
    with tracing.span("after"):
        pass
    assert tracing.span_totals()["after"]["parent"] is None
    tracing.reset_spans()
    assert tracing.span_totals() == {}


def test_tracing_module_imports_without_jax():
    code = ("import sys, importlib.util as u\n"
            "spec = u.spec_from_file_location('t', sys.argv[1])\n"
            "m = u.module_from_spec(spec); sys.modules['t'] = m\n"
            "spec.loader.exec_module(m)\n"
            "assert 'jax' not in sys.modules, 'import pulled jax in'\n"
            "m.set_counter('c', 3); assert m.counters() == {'c': 3}\n"
            "assert m.SCOPES[0] == 'layer' and m.span_totals() == {}\n")
    proc = subprocess.run(
        [sys.executable, "-c", code,
         os.path.join(REPO, "sgcn_tpu", "obs", "tracing.py")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


# ------------------------------------------------- (d) SpanTimer's contract
def test_span_timer_keeps_its_event_and_enters_the_primitive(tmp_path):
    from sgcn_tpu.obs import RunRecorder, load_run

    tracing.reset_spans()
    d = str(tmp_path / "run")
    with RunRecorder(d, config={}) as rec:
        st = tracing.SpanTimer(recorder=rec)
        with st.span("train_step", step=2):
            with st.span("step", step=2, phase="p"):
                pass
    spans = [e for e in load_run(d).events if e["kind"] == "span"]
    assert [(s["name"], s.get("parent"), s["depth"]) for s in spans] \
        == [("step", "train_step", 1), ("train_step", None, 0)]
    assert spans[0]["step"] == 2 and spans[0]["phase"] == "p"
    assert st.timer.counts["step"] == st.timer.counts["train_step"] == 1
    tot = tracing.span_totals()
    assert tot["step"]["parent"] == "train_step" and tot["step"]["count"] == 1


# ------------------------------------- the spans on the profiler's own clock
def test_profile_shows_the_program_spans_on_the_host_plane(ahat, tmp_path):
    """What ``python -m sgcn_tpu.train --profile DIR`` does: a
    ``jax.profiler.trace`` around ``fit()``."""
    import jax
    from jax.profiler import ProfileData

    plan, tr = _trainer(ahat, 4)
    rng = np.random.default_rng(0)
    data = make_train_data(plan, rng.normal(size=(N, FIN)).astype(np.float32),
                           rng.integers(0, WIDTHS[-1], N).astype(np.int32))
    tr.step(TrainData(**shard_stacked(tr.mesh, vars(data))))    # compile
    with jax.profiler.trace(str(tmp_path)):
        tr.fit(data, epochs=2, warmup=0, verbose=False)
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("sgcn.")}
    assert {"sgcn.train_step", "sgcn.step.dispatch",
            "sgcn.step.readback"} <= names, names


# ------------------------------------------- (e) build_comm_plan's five spans
PLAN_SPANS = ("plan.relabel", "plan.halo", "plan.edges", "plan.ell",
              "plan.symmetric")


def test_plan_spans_cover_build_comm_plan_and_it_leaves_its_counts():
    n = 40000
    a = normalize_adjacency(dcsbm_graph(n, ncomm=8, avg_deg=30, seed=1))
    pv = balanced_random_partition(n, 4, seed=0)
    build_comm_plan(a, pv, 4)                 # imports and caches, untimed
    tracing.reset_spans()
    with tracing.span("whole"):
        plan = build_comm_plan(a, pv, 4)
    tot = tracing.span_totals()
    assert all(tot[s]["count"] == 1 and tot[s]["parent"] == "whole"
               for s in PLAN_SPANS)
    parts = sum(tot[s]["total_s"] for s in PLAN_SPANS)
    assert parts == pytest.approx(tot["whole"]["total_s"], rel=0.02)
    work = tracing.counters()["plan.work_counts"]
    assert work == plan.work_counts()
    true, run = work["true"], work["executed"]
    assert len(true["slot_edges"]) == 4
    for p in range(4):
        assert true["slot_edges"][p] + true["tail_edges"][p] \
            + true["halo_edges"][p] == plan.nnz[p]
        assert true["rows_sent"][p] == plan.send_counts[p].sum()
    assert sum(true["halo_rows"]) == sum(true["rows_sent"])
    assert run == {"slot_edges": plan.ell_idx.shape[1],
                   "tail_edges": plan.ltail_dst.shape[1],
                   "halo_edges": plan.hedge_dst.shape[1],
                   "halo_rows": plan.halo_src.shape[1],
                   "rows_sent": plan.send_idx[0].size}
    assert all(max(true[key]) <= run[key] for key in run)


# ------------------------------------ (f) scopes change metadata, not numbers
@pytest.mark.parametrize("k", [1, 4])
def test_losses_are_bit_identical_without_the_scopes(ahat, k, monkeypatch):
    rng = np.random.default_rng(1)
    feats = rng.normal(size=(N, FIN)).astype(np.float32)
    labels = rng.integers(0, WIDTHS[-1], N).astype(np.int32)

    def losses():
        plan, tr = _trainer(ahat, k)
        data = make_train_data(plan, feats, labels)
        data = TrainData(**shard_stacked(tr.mesh, vars(data)))
        text = tr.lower_step().as_text(debug_info=True)
        return [tr.step(data) for _ in range(4)], "sgcn." in text

    scoped, named = losses()
    assert named
    null = lambda name, index=None: contextlib.nullcontext()    # noqa: E731
    for mod in ("sgcn_tpu.ops.pspmm", "sgcn_tpu.models.gcn",
                "sgcn_tpu.train.fullbatch"):
        monkeypatch.setattr(importlib.import_module(mod), "scope", null)
    bare, named = losses()
    assert not named                 # the program as it was before the scopes
    assert scoped == bare and scoped[-1] < scoped[0]
