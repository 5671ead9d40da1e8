"""The slot passes name themselves and count themselves (PR 35).

Every bucket, width class and relation of the slot passes
(``ops/pspmm.py::bucketed_slot_reduce``, ``fold_slots``, ``_typed_pass``,
``models/mhgat.py::_all_stores``) opens a named scope of one of three token
families (``obs/tracing.py``: ``sgcn.bkt_<rows>x<width>_<form>``,
``sgcn.fold_rows``, ``sgcn.pair_<s>_<d>``), and ONE program counter,
``slots.work``, lists the same buckets, forms and pairs per pass
(``models/setup.py::slot_work``):

  * (a) for every model at k = 1 and k = 4 the tokens of the lowered step are
    exactly the buckets, forms, fold rows and pairs the counter lists, by
    layer, direction and store;
  * (b) the counter's sums equal the older counters' slot figures, with and
    without the ``agg0`` hoist;
  * (c) a token parses back to what made it, ``bucket_scope`` outside a leaf
    scope raises, and the scopes are metadata: the lowered step without
    locations is the same text with them patched out.

CPU, tiny graphs, one to four virtual devices; nothing is compiled.
"""

import contextlib
import hashlib
import importlib
import re

import numpy as np
import pytest
import scipy.sparse as sp

from sgcn_tpu.io.datasets import dcsbm_graph
from sgcn_tpu.models import setup as model_setup
from sgcn_tpu.models.gcn import gcn_slot_passes
from sgcn_tpu.obs import tracing
from sgcn_tpu.parallel import build_comm_plan, make_mesh_1d
from sgcn_tpu.partition import balanced_random_partition
from sgcn_tpu.prep import normalize_adjacency
from sgcn_tpu.train import FullBatchTrainer

pspmm = importlib.import_module("sgcn_tpu.ops.pspmm")   # the module, not
#                                       the function ``ops`` exports under it
N, FIN = 643, 6
COUNTS = {"paper": 300, "author": 341, "inst": 7, "fos": 5}
RELS = [("author", "writes", "paper"), ("paper", "cites", "paper"),
        ("paper", "has_topic", "fos"), ("author", "affiliated_with", "inst"),
        ("paper", "rev_writes", "author"), ("fos", "rev_has_topic", "paper"),
        ("inst", "rev_affiliated_with", "author")]
MODELS = {
    "gcn": {"widths": [8, 5]},
    # layer 0 projects first (300 -> 8 lanes): its backward pass runs
    "gcn-project-first": {"widths": [8, 5], "fin": 300},
    "mhgat": {"widths": [8, 5], "activation": "elu",
              "model_args": {"heads": (4, 2), "concat": (True, False)}},
    "deepergcn": {"widths": [8] * 4 + [5],
                  "model_args": {"layers": 4, "hidden": 8}},
    "deepergcn-keep-input": {"widths": [8] * 3 + [5],
                             "model_args": {"layers": 3, "hidden": 8,
                                            "keep": "input"}},
    "rgcn": {"widths": [5, 4], "model_args": {
        "types": [{"name": n, "count": c,
                   "input": "features" if n == "paper" else "embedding"}
                  for n, c in COUNTS.items()],
        "relations": RELS, "label_type": "paper", "hidden": 5, "layers": 2}},
    # dot-product attention, a gated residual, a label input; the hidden
    # layers' K·C is one lane tile, so their backward walks once and the
    # last layer's twice
    "unimp": {"widths": [128, 128, 5],
              "model_args": {"heads": 2, "label_rate": 0.5}},
    # attention inside the typed layouts: every type brings features
    "rgat": {"widths": [8, 8, 5], "activation": "elu", "model_args": {
        "types": [{"name": n, "count": c, "input": "features"}
                  for n, c in COUNTS.items()],
        "relations": RELS, "label_type": "paper", "hidden": 8, "layers": 2,
        "heads": 2}},
}
TYPED = ("rgcn", "rgat")
STORE_OF = {"agg_slots": "ell", "agg_tail": "tail", "agg_halo_fold": "halo"}
NAME = re.compile(r'"([^"]*sgcn\.[^"]*)"')
TOKEN = re.compile(r"sgcn\.([A-Za-z0-9_]+)")
TAG = re.compile("|".join(("att_max",) + tracing.XATT_PASSES
                          + (tracing.PAIR_TOKEN,)))


@pytest.fixture(scope="module")
def plans():
    """A homogeneous graph with a hub past the ELL width cap, and a typed
    one (``tests/test_rgcn.py``'s shape: one field of study is every
    paper's topic), each whole and split four ways."""
    a = sp.lil_matrix(dcsbm_graph(N, ncomm=4, avg_deg=5, seed=0))
    a[3, :] = 1.0
    a[:, 3] = 1.0
    rng = np.random.default_rng(0)
    start = dict(zip(COUNTS, np.concatenate(
        [[0], np.cumsum(list(COUNTS.values()))[:-1]])))

    def pairs(s, d, m):
        return (start[s] + rng.integers(0, COUNTS[s], m),
                start[d] + rng.integers(0, COUNTS[d], m))

    everyone = (start["paper"] + np.arange(COUNTS["paper"]),
                np.full(COUNTS["paper"], start["fos"]))
    src, dst = (np.concatenate(x) for x in zip(
        pairs("author", "paper", 900), pairs("paper", "paper", 700),
        pairs("paper", "fos", 900), pairs("author", "inst", 330), everyone))
    keep = src != dst
    n = sum(COUNTS.values())
    t = sp.coo_matrix((np.ones(keep.sum()), (src[keep], dst[keep])),
                      shape=(n, n)).tocsr()
    out = {}
    for kind, adj in (("plain", sp.csr_matrix(a)),
                      ("typed", ((t + t.T) > 0).astype(np.float32))):
        ahat = normalize_adjacency(sp.csr_matrix(adj))
        for k in (1, 4):
            pv = (np.zeros(ahat.shape[0], np.int64) if k == 1 else
                  balanced_random_partition(ahat.shape[0], k, seed=1))
            out[kind, k] = build_comm_plan(ahat, pv, k)
    return out


def _trainer(plans, model, k, **kw):
    spec = dict(MODELS[model])
    plan = plans["typed" if model in TYPED else "plain", k]
    tr = FullBatchTrainer(plan, fin=spec.pop("fin", FIN), seed=3,
                          model=model.split("-")[0], mesh=make_mesh_1d(k),
                          **spec, **kw)
    return tr, tracing.counters()["slots.work"]


def named_buckets(text: str) -> set:
    """``{(layer, way, store, tags, bucket token | "fold_rows")}`` over the
    op names of a lowered step with debug info.  The body of a checkpoint
    or a scan is a function of its own there, named from its own root (the
    layer and the direction are its caller's: the compiled step joins
    them): such a name gives ``(None, None, store, tags, token)``."""
    found = set()
    for name in NAME.findall(text):
        tokens = TOKEN.findall(name)
        leaf = [t for t in tokens if t in STORE_OF]
        what = [t for t in tokens if tracing.parse_bucket_token(t)
                or t in tracing.SLOT_SUBSCOPES]
        if not what:
            continue
        assert leaf, name               # never outside a leaf scope
        layer = [int(t[5:]) for t in tokens if re.fullmatch(r"layer\d+", t)]
        tags = tuple(sorted(t for t in tokens if TAG.fullmatch(t)))
        where = ((layer[-1], "bwd" if "transpose(" in name else "fwd")
                 if layer else (None, None))
        found.add((*where, STORE_OF[leaf[-1]], tags, what[-1]))
    return found


def same_buckets(found: set, want: set) -> bool:
    """Every name that carries its layer agrees with the counter on layer
    and direction too; all names together are the counter's buckets."""
    whole = {f for f in found if f[0] is not None}
    return whole <= want and {f[2:] for f in found} == {w[2:] for w in want}


def counted_buckets(work: dict) -> set:
    want = set()
    for p in work["passes"]:
        key = (p["layer"], p["way"])
        for store, entries in p["stores"].items():
            for e in entries:
                want.add((*key, store, tuple(sorted(p["tags"])),
                          f"bkt_{e['rows']}x{e['width']}_{e['form']}"))
            if store != "ell" and entries:
                want.add((*key, store, tuple(sorted(p["tags"])),
                          "fold_rows"))
    return want


# ------------------------------------------- (a) tokens == the counter's list
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("model", list(MODELS))
def test_the_lowered_steps_tokens_are_the_counters_buckets(plans, model, k):
    tr, work = _trainer(plans, model, k)
    found = named_buckets(tr.lower_step().as_text(debug_info=True))
    want = counted_buckets(work)
    assert same_buckets(found, want), sorted(
        {f[2:] for f in found} ^ {w[2:] for w in want})[:8]
    # only a checkpointed body's names lack their layer
    assert model.startswith("deepergcn") or found == want
    ways = {(p["layer"], p["way"]) for p in work["passes"]}
    # forward and backward are both listed, and told apart by the op names
    assert {w for _, w in ways} == {"fwd", "bwd"}
    if k == 1:          # a store without edges has no pass, no token
        assert not any(p["stores"]["halo"] for p in work["passes"])
    else:
        assert all(p["stores"]["halo"] for p in work["passes"])
    if model in TYPED:
        pairs = {t for p in work["passes"] for t in p["tags"]} - {"att_max"}
        assert pairs == set(work["relations"]) and len(pairs) >= 6
        # a backward pass walks the REVERSE pair's layout: writes
        # (author -> paper) backward fills authors from papers
        assert {"pair_1_0", "pair_0_1"} <= pairs
        assert work["relations"]["pair_1_0"] == "writes"
    else:
        assert "relations" not in work


def test_a_scanned_ell_bucket_is_named_by_its_unroll(plans, monkeypatch):
    """The products-scale form on a tiny plan: every bucket wider than two
    slots scans, and token, form and counter move together."""
    monkeypatch.setattr(pspmm, "_CONCURRENT_TEMP_LIMIT", 0)
    tr, work = _trainer(plans, "gcn", 4)
    assert named_buckets(tr.lower_step().as_text(debug_info=True)) \
        == counted_buckets(work)
    every = [e for p in work["passes"] for st in p["stores"].values()
             for e in st]
    assert {e["form"] for e in every if e["width"] > 2} == {"s4"}
    assert {e["form"] for e in every if e["width"] <= 2} <= {"u"}
    per = work["per_epoch"]
    assert per["scanned_slots"] == sum(
        e["rows"] * e["width"] for e in every if e["width"] > 2) \
        > 0.9 * (per["ell_slots"] + per["fold_slots"])


# --------------------------------- (b) the sums equal the older counters'
def _three(plan):
    ex = plan.work_counts()["executed"]
    return ex["slot_edges"], ex["tail_edges"] + ex["halo_edges"]


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("model,hoist", [
    ("gcn", True), ("gcn", False), ("gcn-project-first", False),
    ("mhgat", None), ("deepergcn", None), ("deepergcn-keep-input", None),
    ("rgcn", None), ("unimp", None)])
def test_the_counters_sums_are_the_older_counters(plans, model, hoist, k):
    tr, work = _trainer(plans, model, k)
    if hoist is False and model == "gcn":
        # the hoist switched off before a trace (evaluate_fullgraph's)
        tr.hoist = None
        work = model_setup.slot_work(gcn_slot_passes(
            tr.plan, FIN, MODELS[model]["widths"],
            tr._fwd_static["fold_classes"], hoisted=False))
        assert named_buckets(tr.lower_step().as_text(debug_info=True)) \
            == counted_buckets(work)
    per, c = work["per_epoch"], tracing.counters()
    ell, fold = _three(tr.plan)
    slots = per["ell_slots"] + per["fold_slots"]
    if model.startswith("gcn"):
        assert (tr.hoist is not None) is bool(hoist)
        # 2·L passes, less layer 0's forward where hoisted and its backward
        # where it aggregates first (its input is data)
        npass = {"gcn": 2 if hoist else 3, "gcn-project-first": 4}[model]
        assert len(work["passes"]) == npass
        assert (per["ell_slots"], per["fold_slots"]) \
            == (npass * ell, npass * fold)
        folded = c["fold"]
        assert per["fold_slots"] == npass * sum(
            folded[s]["executed_slots"] for s in ("tail", "halo"))
        assert per["virtual_rows"] == npass * sum(
            folded[s]["virtual_rows"] for s in ("tail", "halo"))
    elif model == "mhgat":
        att = c["att.work"]
        npass = sum(att["passes_per_step"].values())
        assert len(work["passes"]) == npass == 6
        assert slots == npass * att["executed_slots_per_pass"]
        assert per["true_edges"] == [npass * x
                                     for x in att["true_edges_per_pass"]]
    elif model == "unimp":
        xatt = c["xatt.work"]
        npass = sum(xatt["passes_per_step"].values())
        # the backward walks the slots once in a fused layer, twice in the
        # last
        assert xatt["fused_backward"] == [True, True, False]
        assert len(work["passes"]) == npass == 3 + 3 + (1 + 1 + 2)
        assert slots == npass * xatt["executed_slots_per_pass"]
        assert per["true_edges"] == [npass * x
                                     for x in xatt["true_edges_per_pass"]]
        assert {(p["layer"], p["tags"][0]) for p in work["passes"]
                if p["way"] == "bwd"} == {
            (0, "xatt_src"), (1, "xatt_src"), (2, "xatt_dst"),
            (2, "xatt_src")}
        assert {tuple(p["tags"]) for p in work["passes"]} == {
            ("xatt_max",), ("xatt_fwd",), ("xatt_dst",), ("xatt_src",)}
        # Σ lanes a true edge: the counter's, and the two-pass backward's
        # (destination 2·K·C, source 2·K·C + 3K) to the lane
        lanes = sum(p["lanes"] for p in work["passes"])
        assert lanes == xatt["lanes_per_edge"] == sum(
            k * c + 2 * k * c + 2 * k * c + (2 * k * c + 3 * k)
            for k, c in zip(xatt["heads"], xatt["channels"]))
    elif model.startswith("deepergcn"):
        deep = c["deep.work"]
        npass = sum(deep["agg_passes_per_step"].values())
        assert sum(p["times_per_epoch"] for p in work["passes"]) == npass
        assert (per["ell_slots"], per["fold_slots"]) \
            == (npass * ell, npass * fold)
        lanes = {(p["way"], p["lanes"]) for p in work["passes"]}
        assert lanes == {("fwd", 16), ("bwd", 8)} | (
            {("bwd", 16)} if deep["keep"] == "input" else set())
    else:
        rel = c["rel.work"]
        assert slots == rel["executed_slots_per_step"]
        assert per["virtual_rows"] == sum(
            r["rows"] for p in rel["passes"] for r in p["run"])
        assert len(work["passes"]) == sum(len(p["run"])
                                          for p in rel["passes"])
        assert max(per["true_edges"]) <= rel["live_edges_per_step"]
    # scanned: every class of virtual rows wider than two slots, and no
    # ELL bucket of these sizes
    assert per["scanned_slots"] == sum(
        e["rows"] * e["width"] * p["times_per_epoch"]
        for p in work["passes"] for s in ("tail", "halo")
        for e in p["stores"][s] if e["form"] != "u")
    assert per["scanned_slots"] <= per["fold_slots"]
    assert len(per["true_edges"]) == k


def test_a_trainer_without_a_pass_list_leaves_no_stale_counter(plans):
    _trainer(plans, "gcn", 1)
    assert tracing.counters()["slots.work"]
    FullBatchTrainer(plans["plain", 1], fin=FIN, widths=[8, 5], seed=3,
                     mesh=make_mesh_1d(1), shared_envelope=True)
    assert tracing.counters()["slots.work"] is None


# ------------------------------------------ (c) tokens, legality, metadata
@pytest.mark.parametrize("nb,wb,unroll,token", [
    (534, 64, None, "bkt_534x64_u"), (991392, 16, 1, "bkt_991392x16_s1"),
    (8, 3, 4, "bkt_8x3_s4")])
def test_a_bucket_token_round_trips(nb, wb, unroll, token):
    assert tracing.bucket_token(nb, wb, unroll) == token
    assert tracing.parse_bucket_token(token) \
        == tracing.parse_bucket_token(tracing.PREFIX + token) \
        == (nb, wb, tracing.form_token(unroll))
    assert TOKEN.fullmatch(tracing.PREFIX + token)       # scopered.TOKEN
    assert token not in tracing.SCOPES


def test_what_is_no_bucket_token_parses_to_nothing():
    for bad in ("bkt_8x3", "bkt_8x3_s", "bkt_x3_u", "agg_slots",
                "pair_0_1", "fold_rows", "bkt_8x3_u4"):
        assert tracing.parse_bucket_token(bad) is None
    assert re.fullmatch(tracing.PAIR_TOKEN, "pair_0_12").groups() \
        == ("0", "12")


def test_bucket_and_fold_scopes_are_legal_inside_a_leaf_scope_only():
    assert not tracing.in_leaf_scope()
    with pytest.raises(ValueError, match="outside a leaf scope"):
        tracing.bucket_scope(8, 3, None)
    with pytest.raises(ValueError, match="outside a leaf scope"):
        tracing.subscope("fold_rows")
    with tracing.pair_scope(0, 1):          # like a layer: no leaf
        assert not tracing.in_leaf_scope()
        with tracing.scope("agg_tail"):
            assert tracing.in_leaf_scope()
            with tracing.bucket_scope(8, 3, 2), tracing.subscope("fold_rows"):
                pass
    assert not tracing.in_leaf_scope()
    # a caller outside every leaf scope (the factorised GAT, the
    # micro-benchmarks) runs unnamed, and does not raise
    import jax.numpy as jnp

    (out,) = pspmm.bucketed_slot_reduce(
        jnp.arange(6) % 3, jnp.ones(6), ((3, 2),),
        contrib=lambda i, w: jnp.eye(3)[i] * w[:, None],
        init=lambda nb: jnp.zeros((nb, 3)), slot_bytes=lambda nb: nb)
    assert out.shape == (3, 3)


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("model", ["deepergcn", "rgcn"])
def test_the_new_scopes_are_metadata(plans, model, k, monkeypatch):
    """The two models without a pinned sha (``tests/test_plan_padding.py``
    pins ``gcn`` and ``mhgat``): the lowered step without locations is the
    same text with the new scopes patched to ``nullcontext``."""
    def sha():
        tr, _ = _trainer(plans, model, k)
        return (hashlib.sha256(tr.lower_step().as_text().encode())
                .hexdigest(), tr.lower_step().as_text(debug_info=True))

    named, text = sha()
    assert "sgcn.bkt_" in text and "sgcn.fold_rows" in text
    assert ("sgcn.pair_" in text) == (model == "rgcn")
    null = lambda *a, **kw: contextlib.nullcontext()        # noqa: E731
    for name in ("bucket_scope", "fold_rows_scope", "pair_scope"):
        monkeypatch.setattr(pspmm, name, null)
    bare, text = sha()
    assert "sgcn.bkt_" not in text and "sgcn.fold_rows" not in text \
        and "sgcn.pair_" not in text and "sgcn.agg_slots" in text
    assert named == bare
