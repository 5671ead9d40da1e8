"""R-GCN on the partitioned full-batch path: typed rows, one relation per
ordered pair of node types, and per-node embeddings owned with the rows.

Schlichtkrull et al., "Modeling Relational Data with Graph Convolutional
Networks" (arXiv:1703.06103), in the full-batch configuration the OGB
repository publishes for the ``ogbn-mag`` leaderboard
(``examples/nodeproppred/mag/rgcn.py``).  Node ids are contiguous per type,
in the order of the type table; a type brings its input rows as features
(the trainer's ``h0``) or as a trainable embedding table.  For a row i of
type d and a layer's input x::

    h_i = W_root[d] x_i + b[d] + sum_{r = (s -> d)} W_r mean_{j in N_r(i)} x_j

an empty neighbourhood giving 0; ReLU between the layers; the logits are the
last layer's rows of the labelled type.  A relation is a function of the
ordered pair (type of source, type of destination) — at most one per pair,
a second is refused — so with the published reverse relations the union of
all relations is ONE undirected simple graph: the plan's.  ``N_r(i)`` are
i's neighbours of type s in that graph (a self-loop is no edge; Â's values
are not read).

**Typed order.**  Every array of the model lives in the plan's row order
restricted to one type: the rows of that type in plan order, padded to the
fullest chip's count.  In that order a type's rows are a static slice, so a
weight per TYPE is a product on a slice.

**One slot layout per relation.**  The unit of the aggregation is the
ordered pair of types (s -> d): the rows of d, their slots that pair's edges
only (``build_typed_layout``), every directed edge of the graph in exactly
one layout, once.  A layout is what the plan's own builders make of the
pair's edges: ELL buckets over d's rows in typed order (``_build_ell``, at
the widths ``_relation_buckets`` chooses by count), what lies past a bucket's
width as virtual rows (``_build_virtual_rows``: the typed order follows a
row's TOTAL degree, a relation's own degree may not), the halo-source edges
as virtual rows too.  A slot names its source's row in the SOURCE type's own
table and carries the mean's weight ``1 / deg_r(i)`` and the transposed
weight ``1 / deg_r'(j)`` of the same slot read from the other side: the
forward of s -> d and the backward of d -> s walk the same layout
(``ops.pspmm.typed_aggregate``), which is why the pattern must be symmetric.

**Every layer aggregates first**: per relation into a destination type the
mean over its sources (``d_in`` lanes gathered, one ``d_in``-lane array
accumulated), then per type the products ``x_d W_root + sum_s A_ds
W_(s->d)``.  The backward runs, per relation whose source needs a gradient —
at layer 0 the embedded types only (features are data) — the reverse pair's
layout over the cotangent of the block that relation fills; a relation dead
in a pass contributes no slot to it, the blocks nobody reads are never
formed, and the weights' gradients are dense products on what the forward
kept (``typed_passes`` lists what runs; the counter ``rel.work`` reports it).
The v5e gathers rows at one rate
from 64 to 128 lanes, so projecting layer 0 first would buy nothing per
slot and cost a pass into every source row for the weights' gradient.

**Pruned**: a layer computes the types that can reach a labelled row —
``D_L`` = the labelled type, ``D_(l-1)`` = ``D_l`` and the sources of
relations into it.  The weights of what is left out keep a zero gradient, as
they do in the published model, and the tree still counts every parameter.

**Row-owned parameters.**  ``params["emb"][type]`` is ``(height, f)`` per
chip in typed order, sharded with the rows (``ModelSetup.row_owned`` maps
each per-chip row to the table's global row): its gradient is not
``psum``med, Adam's moments are sharded alike.  At k > 1 a halo copy of an
embedding row returns its gradient to the owner through layer 0's backward
exchange.

Per-chip code, inside ``shard_map`` over the 1D vertex mesh.  Refused,
loudly: an asymmetric plan, ``comm_schedule='ragged'``, stale / replica
modes, ``halo_dtype``, ``compute_dtype``, the Pallas aggregator, mini-batch,
serving.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs.tracing import scope, subscope
from ..ops.pspmm import (_FOLD_SCAN_LIVE, _TYPED_SCAN_LIVE, pass_store_forms,
                         typed_aggregate)
from ..parallel.mesh import AXIS
from ..parallel.plan import (FOLD_ROW_COST, UNSNAPPED, _build_ell,
                             _build_virtual_rows, _choose_buckets,
                             choose_fold_widths, fold_class_shapes,
                             padding_rows, snap_rows)
from .activations import get_activation
from .setup import ModelSetup, slot_pass, slot_work

RGCN_PLAN_FIELDS = ("halo_src",)
INPUTS = ("features", "embedding")
STORES = ("e", "t", "h")    # ELL slots, the runs past them, halo-source edges
# a relation's ELL width cap: none (virtual rows only) up to the plan's own
ELL_CAPS = (0, 1, 2, 4, 8, 16, 32, 64)
PROFILE_POINTS = 64


class TypedSpec(NamedTuple):
    """Statics of one layer's ``typed_aggregate``."""
    heights: tuple      # rows of each type's table, in type order
    sources: tuple      # per type: the types with a relation INTO it
    layouts: tuple      # ((s, d), (ell buckets, tail classes, halo classes))
    dst: tuple          # destination types this layer computes
    grad: tuple         # source types whose table gets a gradient
    exchange: bool      # some chip has a halo-source edge


# ----------------------------------------------------------- configuration
def resolve_args(fin: int, widths, model_args: dict | None) -> dict:
    """The configuration as statics: the type table (name, count, features
    or embedding; ids contiguous in this order), the relation list (source,
    name, destination), the labelled type, ``hidden`` and ``layers``
    (defaulting to what the trainer's ``widths`` say, and checked against
    them)."""
    args = dict(model_args or {})
    widths = [int(w) for w in widths]
    try:
        types = [dict(t) for t in args.pop("types")]
        relations = [tuple(r) for r in args.pop("relations")]
        label = args.pop("label_type")
    except KeyError as e:
        raise ValueError(f"rgcn: model_args needs {e.args[0]!r} (types, "
                         "relations, label_type)") from None
    layers = int(args.pop("layers", len(widths)))
    hidden = int(args.pop("hidden", widths[0] if len(widths) > 1 else fin))
    if args:
        raise ValueError(f"rgcn: unknown model_args {sorted(args)}")
    names = [t["name"] for t in types]
    if len(set(names)) != len(names) or not names:
        raise ValueError(f"rgcn: type names {names} are not distinct")
    for t in types:
        if t.get("input") not in INPUTS or int(t["count"]) < 1:
            raise ValueError(f"rgcn: type {t} needs a count >= 1 and an "
                             f"input of {INPUTS}")
    if label not in names:
        raise ValueError(f"rgcn: label_type {label!r} is not one of {names}")
    pairs = {}
    for rel in relations:
        if len(rel) != 3 or rel[0] not in names or rel[2] not in names:
            raise ValueError(f"rgcn: relation {rel} is not (source type, "
                             f"name, destination type) over {names}")
        pair = (rel[0], rel[2])
        if pair in pairs:
            raise ValueError(
                f"rgcn: relations {pairs[pair]!r} and {rel[1]!r} both run "
                f"{rel[0]} -> {rel[2]}: a relation here is the ordered pair "
                "of its endpoints' types, read off the one adjacency the "
                "plan holds, so two between the same pair cannot be told "
                "apart (ROADMAP B7)")
        pairs[pair] = rel[1]
    if layers < 1 or widths[:-1] != [hidden] * (layers - 1) \
            or len(widths) != layers:
        raise ValueError(f"rgcn: widths {widths} are not {layers} layers of "
                         f"{hidden} ending in the classes")
    return {"types": tuple((t["name"], int(t["count"]), t["input"])
                           for t in types),
            "relations": tuple((names.index(s), name, names.index(d))
                               for s, name, d in relations),
            "label": names.index(label), "hidden": hidden, "layers": layers}


def param_count(fin: int, widths, types, relations) -> int:
    """Embeddings, and per layer a bias-free weight a relation, a biased
    one a type."""
    dims = list(zip([fin] + list(widths[:-1]), widths))
    emb = sum(c for _, c, kind in types if kind == "embedding") * fin
    return emb + sum((len(relations) + len(types)) * a * b + len(types) * b
                     for a, b in dims)


def reachable(ntypes: int, relations, label: int, layers: int) -> list:
    """``[D_0, .., D_L]``: the types layer l's OUTPUT must hold for a
    labelled row's logits (``D_0``: the input types read)."""
    need = [None] * layers + [(label,)]
    for layer in range(layers, 0, -1):
        srcs = {s for s, _, d in relations if d in need[layer]}
        need[layer - 1] = tuple(sorted(srcs | set(need[layer])))
    return need


def _sources(ntypes: int, relations) -> tuple:
    return tuple(tuple(sorted(s for s, _, d in relations if d == t))
                 for t in range(ntypes))


def layer_specs(args: dict, layout: dict) -> tuple:
    """One ``TypedSpec`` a layer, from the configuration and the layout's
    statics."""
    types, rels = args["types"], args["relations"]
    need = reachable(len(types), rels, args["label"], args["layers"])
    embedded = {t for t, (_, _, kind) in enumerate(types)
                if kind == "embedding"}
    sources = _sources(len(types), rels)
    specs = []
    for layer in range(args["layers"]):
        dst = need[layer + 1]
        feeds = {s for d in dst for s in sources[d]}
        # a gradient for a table that depends on a trainable array
        grad = feeds & (embedded if layer == 0 else set(need[layer]))
        specs.append(TypedSpec(
            heights=layout["heights"], sources=sources,
            layouts=layout["layouts"], dst=dst, grad=tuple(sorted(grad)),
            exchange=layout["exchange"]))
    return tuple(specs)


def typed_passes(specs, relations) -> list:
    """What each layer's ``typed_aggregate`` runs, read off its spec as
    ``ops.pspmm`` reads it: per layer the forward and the backward pass,
    each a list of ``(relation, the pair whose layout is walked, weight)``
    — forward every relation into ``spec.dst``; backward those of them
    whose source is in ``spec.grad``, on the REVERSE pair's slots."""
    rel_of = {(s, d): r for r, (s, _, d) in enumerate(relations)}
    return [([(rel_of[s, d], (s, d), "wf")
              for d in spec.dst for s in spec.sources[d]],
             [(rel_of[s, d], (d, s), "wb") for s in spec.grad
              for d in spec.dst if s in spec.sources[d]]) for spec in specs]


def shipped_layouts(layout: dict, specs, relations) -> dict:
    """The relation layouts' arrays a step reads, by the name they ship
    under (``rel_<s>_<d>_<array>``): the pairs some pass walks, and of their
    two weights the ones a pass picks."""
    weights = {}
    for passes in typed_passes(specs, relations):
        for _, pair, weight in passes[0] + passes[1]:
            weights.setdefault(pair, set()).add(weight)
    return {f"rel_{s}_{d}_{name}": x
            for (s, d), picked in sorted(weights.items())
            for name, x in layout["arrays"]["rels"][s, d].items()
            if name[2:] not in {"wf", "wb"} - picked}


def pass_counts(args: dict, layout: dict, specs, lanes) -> list:
    """The ``rel.work`` counter's ``passes``: per layer and direction the
    types whose rows the pass fills, the relations it runs with the edges
    (the fullest chip's), slots, virtual rows and buckets + classes of the
    layout each walks, and the relations it leaves out."""
    names = [name for name, _, _ in args["types"]]
    rels = args["relations"]
    passes = []
    for layer, (spec, a, both) in enumerate(zip(
            specs, lanes, typed_passes(specs, rels))):
        for direction, into, end, run in (("forward", spec.dst, 2, both[0]),
                                          ("backward", spec.grad, 0, both[1])):
            ran = [r for r, _, _ in run]
            counts = [layout["counts"][pair] for _, pair, _ in run]
            passes.append({
                "layer": layer, "direction": direction, "lanes": a,
                # the types whose rows the pass fills: backward, the sources
                "into": [names[t] for t in into],
                "relations": [rels[r][1] for r in ran],
                # what a pass over these types' WHOLE rows would also walk:
                # the relations that end (backward: start) there and are
                # dead in this pass
                "left_out": [rel[1] for r, rel in enumerate(rels)
                             if rel[end] in into and r not in ran],
                "run": [{"relation": rels[r][1], **c}
                        for r, c in zip(ran, counts)],
                "edges": sum(c["edges"] for c in counts),
                "slots": sum(c["slots"] for c in counts)})
    return passes


def slot_passes(args: dict, layout: dict, specs, lanes) -> tuple:
    """``(passes, relations)`` for the counter ``slots.work``
    (``models/setup.py::slot_pass`` / ``slot_work``): one pass a relation run
    — per layer and direction those of ``typed_passes`` — tagged with the
    pair whose layout it walks (``pair_<source type>_<row type>``, the token
    ``ops.pspmm`` names the pass by: backward, the REVERSE pair of the
    relation), and the name of every walked pair: the relation whose forward
    walks it."""
    rels = args["relations"]
    name_of = {(s, d): n for s, n, d in rels}
    layouts = dict(layout["layouts"])
    passes, walked = [], set()
    for layer, (a, both) in enumerate(zip(lanes, typed_passes(specs, rels))):
        for way, run in zip(("fwd", "bwd"), both):
            for _, (s, d), _ in run:
                walked.add((s, d))
                passes.append(slot_pass(
                    layer, way, a,
                    pass_store_forms(*layouts[s, d], a, _TYPED_SCAN_LIVE),
                    tags=(f"pair_{s}_{d}",),
                    true_edges=layout["counts"][s, d]["chip_edges"]))
    return passes, {
        f"pair_{s}_{d}": name_of.get((s, d), f"{name_of.get((d, s))}^T")
        for s, d in sorted(walked)}


# ------------------------------------------------------------------ layout
def _relation_buckets(degs: list, height: int) -> tuple:
    """ELL buckets over a destination type's ``height`` rows for ONE
    relation's local edges (``degs``: per chip, the edges each row has in
    it).  The typed order follows a row's TOTAL degree and a relation's own
    degree may not, so what lies past a bucket's width runs as virtual rows:
    per width cap of ``ELL_CAPS`` the plan's own rules give the buckets
    (``_choose_buckets``) and the classes of the rest
    (``choose_fold_widths``), and the cap with the least ``executed slots +
    FOLD_ROW_COST · virtual rows`` wins — cap 0 is the pure virtual-row
    form, the widest the plan's own ELL with a hub tail.  The profile is the
    maximum over blocks of rows (``_choose_buckets`` walks it in Python), so
    the plan's ``snap_rows`` — buckets of row counts the slot gather runs
    cheaply — is applied to the ROWS, not to the profile.  Returns the
    buckets and what the snap moved in them."""
    block = -(-height // PROFILE_POINTS)
    profile = np.max(degs, axis=0)
    profile = np.pad(profile, (0, -height % block)).reshape(-1, block).max(1)
    best = None
    for cap in ELL_CAPS:
        # a row's width in its bucket; what snap_rows moved
        buckets, width, snapped = (), 0, dict(UNSNAPPED)
        if cap:
            found = _choose_buckets(profile, width_cap=cap)
            rows = [n * block for n, _ in found]
            rows[-1] -= -height % block
            buckets, snapped = snap_rows(
                tuple(zip(rows, (w for _, w in found))), cover=True)
            width = np.repeat([w for _, w in buckets],
                              [n for n, _ in buckets])
        rest = [np.maximum(dg - width, 0) for dg in degs]
        cost = sum(n * w for n, w in buckets) + sum(
            nv * (w + FOLD_ROW_COST)
            for nv, w in fold_class_shapes(rest, choose_fold_widths(rest)))
        if best is None or cost < best[0]:
            best = (cost, buckets, snapped)
    return best[1:]


def _relation_layout(local: list, halo: list, height: int, tables: tuple,
                     ) -> tuple:
    """The slot layout of ONE ordered pair of types from its edges per chip
    (``local`` / ``halo``: ``(destination, source, wf, wb)``, destinations
    ascending typed rows, sources rows of ``tables`` = the source type's
    height / the halo table's): the plan's builders laid over the edge
    NUMBERS — ``_build_ell`` at ``_relation_buckets``' widths, what it spills
    and the halo-source edges through ``_build_virtual_rows`` — then every
    real slot given its edge's source and weights, every padding slot a row
    of ``padding_rows``.  Returns arrays, ``(buckets, tail classes, halo
    classes)`` and counts."""
    k = len(local)
    none = {"idx": np.zeros((k, 0), np.int32), "w": np.zeros((k, 0), np.float32),
            "row": np.zeros((k, 0), np.int32), "classes": (),
            "snapped": dict(UNSNAPPED)}

    def numbered(edges):
        # (dst, edge number, 1 / 0, count): a store as the builders take it
        cnt = np.array([len(e[0]) for e in edges])
        eno = np.arange(cnt.max(), dtype=np.int32)[None].repeat(k, 0)
        dst = np.zeros(eno.shape, np.int32)
        for c, e in enumerate(edges):
            dst[c, :cnt[c]] = e[0]
        return dst, eno, (eno < cnt[:, None]).astype(np.float32), cnt

    def fill(pre, idx, w, edges, table):
        # a built store's slots: edge number -> source row and weights
        out = {f"{pre}_idx": np.empty(idx.shape, np.int32),
               f"{pre}_wf": np.zeros(idx.shape, np.float32),
               f"{pre}_wb": np.zeros(idx.shape, np.float32)}
        for c, (_, src, wf, wb) in enumerate(edges):
            real = w[c] != 0
            out[f"{pre}_idx"][c][~real] = padding_rows(int((~real).sum()),
                                                       table)
            for name, val in (("idx", src), ("wf", wf), ("wb", wb)):
                out[f"{pre}_{name}"][c][real] = val[idx[c][real]]
        return out

    def virtual(pre, stored, edges, table):
        lay = _build_virtual_rows(*stored, height, table) or none
        return ({**fill(pre, lay["idx"], lay["w"], edges, table),
                 f"{pre}_row": lay["row"]}, lay["classes"], lay["snapped"])

    stored = numbered(local)
    buckets, snapped = _relation_buckets(
        [np.bincount(e[0], minlength=height) for e in local], height)
    ell = {"ell_idx": none["idx"], "ell_w": none["w"]}
    if buckets:
        ell = _build_ell(*stored, height, buckets=buckets)
        stored = (ell["ltail_dst"], ell["ltail_src"], ell["ltail_w"],
                  ell["ltail_nnz"])
    tail, tail_classes, tail_snapped = virtual("t", stored, local, tables[0])
    over, halo_classes, halo_snapped = virtual("h", numbered(halo), halo,
                                               tables[1])
    arrays = {**fill("e", ell["ell_idx"], ell["ell_w"], local, tables[0]),
              **tail, **over}
    chip_edges = [len(a[0]) + len(b[0]) for a, b in zip(local, halo)]
    counts = {
        # the fullest chip's edges (and each chip's); what every chip
        # executes
        "edges": max(chip_edges), "chip_edges": chip_edges,
        "slots": sum(arrays[f"{s}_idx"].shape[1] for s in STORES),
        "rows": arrays["t_row"].shape[1] + arrays["h_row"].shape[1],
        "classes": len(buckets) + len(tail_classes) + len(halo_classes),
        # what the plan's snap_rows moved, as ``work_counts()["snapped"]``
        "snapped": {"slot_edges": snapped, "tail_edges": tail_snapped,
                    "halo_edges": halo_snapped}}
    return arrays, (buckets, tail_classes, halo_classes), counts


def build_typed_layout(plan, args: dict) -> dict:
    """Everything the model derives from the plan, per chip: the typed
    order, and per ordered pair of types with a relation (either way round:
    a relation's backward walks the reverse pair's slots) that pair's slot
    layout with both weights (module docstring).  Returns ``arrays``
    (shipped: ``ModelSetup.extra_arrays``), the statics (``heights``,
    ``layouts``, ``exchange``), ``counts`` per pair, ``rows`` per type ``(k,
    height)`` the table row of every typed row in the type's global id order
    (−1 padding) and ``edges`` per relation."""
    types, rels = args["types"], args["relations"]
    nt, k, b = len(types), plan.k, plan.b
    counts = np.array([c for _, c, _ in types], np.int64)
    if counts.sum() != plan.n:
        raise ValueError(f"rgcn: the type table counts {int(counts.sum())} "
                         f"rows, the plan {plan.n}")
    starts = np.concatenate([[0], np.cumsum(counts)])
    is_rel = np.zeros((nt, nt), bool)           # [source, destination]
    for s, _, d in rels:
        is_rel[s, d] = True
    gid = plan.global_row_ids()                             # (k, B), -1 pad
    typ = np.where(gid >= 0, np.searchsorted(starts, gid, "right") - 1, -1)
    hgid = plan.halo_global_rows()                          # (k, R)
    htyp = np.where(hgid >= 0, np.searchsorted(starts, hgid, "right") - 1,
                    -1)

    # the typed order: a type's rows in plan order, padded to the fullest
    # chip's count
    rows = []
    for t in range(nt):
        per = [np.flatnonzero(typ[c] == t) for c in range(k)]
        height = max(len(r) for r in per)
        rows.append(np.stack([np.pad(r, (0, height - len(r)),
                                     constant_values=-1) for r in per]))
    heights = tuple(int(r.shape[1]) for r in rows)
    first = np.concatenate([[0], np.cumsum(heights)])
    pos = np.zeros((k, b), np.int64)            # typed row WITHIN its type
    for r in rows:
        for c in range(k):
            pos[c, r[c][r[c] >= 0]] = np.flatnonzero(r[c] >= 0)

    # every real edge of a chip (a self-loop is none), local and halo-source:
    # destination row, source row / halo rank, their pair of types (int8:
    # these lists are the build's memory traffic)
    stores = [[], []]
    deg = np.zeros((k, b, nt), np.int64)    # a row's neighbours by type
    for c in range(k):
        for halo, (dst, src, cnt, tsrc) in enumerate((
                (plan.ledge_dst, plan.ledge_src, plan.lnnz, typ),
                (plan.hedge_dst, plan.hedge_src, plan.hnnz, htyp))):
            d_, s_ = dst[c, :int(cnt[c])], src[c, :int(cnt[c])]
            ts = tsrc[c].astype(np.int8)[s_]
            ok = ts >= 0
            if not halo:
                ok &= s_ != d_
            d_, s_, ts = d_[ok], s_[ok], ts[ok]
            stores[halo].append((d_, s_, typ[c].astype(np.int8)[d_], ts))
            deg[c] += np.bincount(d_.astype(np.int64) * nt + ts,
                                  minlength=b * nt).reshape(b, nt)
    gdeg = plan.gather_rows(deg)                            # (n, nt)
    edges = {name: int(gdeg[starts[d]:starts[d + 1], s].sum())
             for s, name, d in rels}

    def inv(x):
        return (1.0 / np.maximum(x, 1)).astype(np.float32)

    def pair_edges(c, halo, s, d):
        """Chip c's edges of the pair (s -> d) in one store: typed
        destination row, source (typed row / halo rank), the mean's weight
        of s -> d and the transposed weight of d -> s (0: no such
        relation)."""
        d_, s_, td, ts = stores[halo][c]
        at = np.flatnonzero((td == d) & (ts == s))
        d_, s_ = d_[at], s_[at]
        wf = inv(deg[c][d_, s]) * is_rel[s, d]
        wb = inv(gdeg[(hgid if halo else gid)[c][s_], d]) * is_rel[d, s]
        return pos[c][d_], s_ if halo else pos[c][s_], wf, wb

    arrays = {"rels": {}}
    layouts, slot_counts = {}, {}
    for s, d in np.argwhere(is_rel | is_rel.T).tolist():
        arrays["rels"][s, d], layouts[s, d], slot_counts[s, d] = \
            _relation_layout(*[[pair_edges(c, halo, s, d) for c in range(k)]
                               for halo in (0, 1)], heights[d],
                             (heights[s], plan.r))

    # the exchange's send rows, in the stacked table's order
    send = np.asarray(plan.send_idx)
    arrays["send_rows"] = np.stack([
        (first[np.maximum(typ[c][send[c]], 0)] + pos[c][send[c]])
        for c in range(k)]).astype(np.int32)
    # typed row -> plan row (features are gathered through it), and ->
    # the row of the type's table in global id order
    plan_rows, table_rows = [], []
    for t, r in enumerate(rows):
        filler = np.stack([padding_rows(r.shape[1], b)] * k)
        plan_rows.append(np.where(r >= 0, r, filler).astype(np.int32))
        g = np.stack([gid[c][np.maximum(r[c], 0)] for c in range(k)])
        table_rows.append(np.where(r >= 0, g - starts[t], -1))
    return {"arrays": arrays, "heights": heights,
            "layouts": tuple(sorted(layouts.items())),
            "exchange": bool(np.asarray(plan.hnnz).any()),
            "counts": slot_counts, "plan_rows": plan_rows,
            "table_rows": table_rows, "edges": edges}


# ------------------------------------------------------------------ params
def _uniform(key, shape, bound):
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def init_rgcn_params(rng: jax.Array, dims, types=(), relations=(),
                     table_rows=(), **_static):
    """``emb`` (one table per embedded type, Xavier-uniform over the
    ``(count, f)`` table as torch draws it, laid out per chip in typed order
    with a leading chip axis: row-owned) and ``layers`` (per layer ``rel``
    (relations, d_in, d_out), ``root`` (types, d_in, d_out) and ``bias``
    (types, d_out), as torch's ``Linear`` draws them).  The draw is a
    function of the seed alone, whatever k."""
    fin = int(dims[0][0])
    keys = jax.random.split(rng, len(dims) + 1)
    emb = {}
    for t, ((name, count, kind), key) in enumerate(zip(
            types, jax.random.split(keys[0], len(types)))):
        if kind != "embedding":
            continue
        table = np.asarray(_uniform(key, (count, fin),
                                    np.sqrt(6.0 / (count + fin))))
        rows = table_rows[t]
        emb[name] = np.where((rows >= 0)[..., None],
                             table[np.maximum(rows, 0)], 0.0
                             ).astype(np.float32)
    layers = []
    for key, (a, b) in zip(keys[1:], dims):
        kr, kw, kb = jax.random.split(key, 3)
        bound = 1.0 / np.sqrt(a)
        layers.append({
            "rel": _uniform(kr, (len(relations), a, b), bound),
            "root": _uniform(kw, (len(types), a, b), bound),
            "bias": _uniform(kb, (len(types), b), bound)})
    return {"emb": emb, "layers": layers}


# ----------------------------------------------------------------- forward
def _dot(x, w):
    """float32 proper (``Precision.HIGHEST``): the products are a few
    percent of this model's epoch, and at the TPU's default precision (bf16
    multiplicands) they stand as far from a float32 reference as a bfloat16
    table does (PERF.md §6, PR 31 and PR 33)."""
    return jnp.dot(x, w, precision=lax.Precision.HIGHEST)


def rgcn_forward_local(
    params,
    h,                            # (B, fin) local rows, plan order
    pa,                           # shipped arrays (RGCN_PLAN_FIELDS + rel_*)
    activation: str = "relu",
    final_activation: str = "none",
    symmetric: bool = False,
    types: tuple = (),            # static: (name, count, input) per type
    relations: tuple = (),        # static: (source, name, destination)
    label: int = 0,               # static: the labelled type
    specs: tuple = (),            # static: one TypedSpec a layer
    comm_schedule: str = "a2a",
    axis_name: str = AXIS,
    halo_carry=None,
    **_static,
):
    """Per-chip forward (module docstring); returns the labelled type's
    rows of the last layer, ``(height, classes)`` in typed order — the
    trainer reads labels and masks through ``ModelSetup.out_rows``."""
    if halo_carry is not None:
        raise NotImplementedError(
            "stale-halo pipelining is implemented for the GCN hot path "
            "only; run rgcn with halo_staleness=0")
    if not symmetric:
        raise ValueError(
            "rgcn reads a relation and its reverse off one symmetric "
            "pattern, and its backward walks the same slots; this plan is "
            "asymmetric")
    if comm_schedule != "a2a":
        raise ValueError("rgcn ships its tables over the dense all_to_all "
                         f"only, not comm_schedule={comm_schedule!r}")
    act, last = get_activation(activation), get_activation(final_activation)
    arrays = {"rels": {(s, d): {f"{st}_{n}": pa[f"rel_{s}_{d}_{st}_{n}"]
                                for st in STORES
                                for n in ("idx", "wf", "wb", "row")
                                if f"rel_{s}_{d}_{st}_{n}" in pa}
                       for (s, d), _ in specs[0].layouts},
              "send_rows": pa["rel_send_rows"], "halo_src": pa["halo_src"]}
    x = []
    with scope("dense"), subscope("rel_table"):
        for t, (name, _, kind) in enumerate(types):
            if t not in _read(specs[0]):
                x.append(None)
            elif kind == "embedding":
                x.append(params["emb"][name])
            else:
                x.append(jnp.take(h, pa[f"rel_{t}_rows"], axis=0))
    rel_of = {(s, d): r for r, (s, _, d) in enumerate(relations)}
    for layer, (spec, p) in enumerate(zip(specs, params["layers"])):
        with scope("layer", layer):
            agg = typed_aggregate(tuple(x), arrays, spec, axis_name)
            out = [None] * len(types)
            with scope("dense"), subscope("rel_project"):
                for d, means in zip(spec.dst, agg):
                    y = _dot(x[d], p["root"][d]) + p["bias"][d]
                    for s, mean in zip(spec.sources[d], means):
                        y = y + _dot(mean, p["rel"][rel_of[s, d]])
                    out[d] = (last(y) if layer == len(specs) - 1
                              else act(y))
            x = out
    return x[label]


def _read(spec: TypedSpec) -> set:
    """The types whose input rows a layer reads: its destinations' own rows
    and the sources of relations into them."""
    return set(spec.dst) | {s for d in spec.dst for s in spec.sources[d]}


# ------------------------------------------------------------------- memory
def estimate_rgcn_hbm_bytes(plan, fin: int, widths, args: dict, layout: dict,
                            train: bool = True) -> dict:
    """Per-chip HBM of one fwd+bwd step, itemised (f32):

    * ``row_owned``: the embedding tables with, in training, Adam's two
      moments (12 B a parameter); their gradient (4 B) is a transient;
    * ``rows_kept``: what the forward holds for the backward — per layer the
      featured types' gathered rows, the aggregated blocks ``A_ds`` and the
      layer's output (the last layer's: the labelled rows' logits);
    * ``rows_transient``: the most of — a layer's means being filled
      beside, at k > 1, the stacked table its exchange ships; at the loss,
      the logits' gradient and the
      softmax; in a layer's backward, its output's cotangent, the cotangent
      blocks the gradient types read and the rows gathered into them — and
      the row-owned tables' gradient beside any of these;
    * ``slot_temps``: the slot passes' gathered rows and accumulators,
      bounded by the scan-unroll budgets of the typed passes and the folds
      and by the unrolled buckets' concurrent temporaries;
    * ``plan``: the relation layouts a step reads (``shipped_layouts``):
      per slot an index and the weights its passes pick, a destination per
      virtual row;
    * ``features``: the trainer's ``h0``, labels and masks;
    * ``param_bytes`` (not in the total: it is inside ``row_owned`` and
      ``params``): the parameter tree's bytes on ONE chip, which is what a
      step donates — the replicated leaves whole, the row-owned ones a
      chip's share.

    An estimate of what the arrays need; PERF.md §6 (PR 33) sets it beside
    the chip's ``memory_stats()`` and the compiler's count."""
    specs = layer_specs(args, layout)
    heights = layout["heights"]
    dims = list(zip([fin] + list(widths[:-1]), widths))
    emb_rows = sum(h for h, (_, _, kind) in zip(heights, args["types"])
                   if kind == "embedding")
    owned = emb_rows * fin * (12 if train else 4)
    kept, transient = 0, 2 * heights[args["label"]] * widths[-1] * 4
    for layer, (spec, (a, b)) in enumerate(zip(specs, dims)):
        table = sum(heights[t] for t in _read(spec)) * a * 4 \
            * layout["exchange"]
        agg = sum(heights[d] * len(spec.sources[d]) for d in spec.dst) * a * 4
        out = sum(heights[d] for d in spec.dst) * b * 4
        gathered = sum(heights[t] for t in _read(spec)
                       if args["types"][t][2] == "features") * a * 4 \
            if layer == 0 else 0
        wanted = sum(heights[d] for d in spec.dst for u in spec.sources[d]
                     if u in spec.grad) * a * 4
        into = sum(heights[s] for s in spec.grad) * a * 4
        if train:
            kept += gathered + agg + out
            transient = max(transient, table + agg // 2,
                            out + wanted + into)
        else:
            transient = max(transient, table + agg + out)
    shared = (param_count(fin, widths, args["types"], args["relations"])
              - sum(c for _, c, kd in args["types"] if kd == "embedding")
              * fin)
    # a row of a pass: its gathered lanes and its accumulator
    widest = 2 * max(a for a, _ in dims) * 4
    big = max((n for _, lay in layout["layouts"] for n, _ in lay[0]),
              default=0)
    parts = {"row_owned": owned, "rows_kept": kept,
             "rows_transient": transient
             + (emb_rows * fin * 4 if train else 0),
             "slot_temps": min(_TYPED_SCAN_LIVE + _FOLD_SCAN_LIVE,
                               16 * big * widest),
             "plan": sum(x[0].nbytes for x in shipped_layouts(
                 layout, specs, args["relations"]).values()),
             "features": int(plan.b) * 4 * (fin + 3),
             "params": (16 if train else 4) * shared}
    parts["total"] = sum(parts.values())
    parts["param_bytes"] = 4 * (shared + emb_rows * fin)
    return parts


# -------------------------------------------------------------- the registry
def model_setup(plan, fin: int, widths, model_args: dict | None, *,
                comm_schedule: str, compute_dtype, serve_subgraph: bool
                ) -> ModelSetup:
    """The ``MODELS`` entry's setup hook (``models/setup.py``): validates
    ``model_args``, refuses what the model has no form for, derives the
    typed layout from the plan, and hands the shared code the statics, the
    exchange's lanes per direction, which leaves are row-owned and where
    their rows live, the output rows, the memory estimate and the
    ``rel.work`` counter."""
    if not plan.symmetric:
        raise ValueError(
            "rgcn reads a relation and its reverse off one symmetric "
            "pattern; this plan is asymmetric (models/rgcn.py)")
    if comm_schedule != "a2a" or serve_subgraph:
        raise ValueError(
            "rgcn runs the dense a2a schedule and the full forward only "
            f"(comm_schedule={comm_schedule!r}, "
            f"serve_subgraph={serve_subgraph})")
    if compute_dtype is not None:
        raise ValueError(
            f"rgcn is float32 only (compute_dtype={compute_dtype!r})")
    args = resolve_args(fin, widths, model_args)
    layout = build_typed_layout(plan, args)
    specs = layer_specs(args, layout)
    types, rels = args["types"], args["relations"]
    heights = layout["heights"]
    label = args["label"]
    extra = {"rel_send_rows": layout["arrays"]["send_rows"],
             **shipped_layouts(layout, specs, rels)}
    for t, (_, _, kind) in enumerate(types):
        if kind == "features" and t in _read(specs[0]):
            extra[f"rel_{t}_rows"] = layout["plan_rows"][t]
    out_ok = layout["table_rows"][label] >= 0
    extra["rel_out_rows"] = layout["plan_rows"][label]
    extra["rel_out_valid"] = out_ok.astype(np.float32)
    dims = list(zip([fin] + list(widths[:-1]), widths))
    # lanes a row ships: its input forward; backward its wanted blocks
    lanes_bwd = tuple(
        a * max([sum(u in spec.grad for u in spec.sources[d])
                 for d in spec.dst] + [0])
        for spec, (a, _) in zip(specs, dims))
    estimate = functools.partial(estimate_rgcn_hbm_bytes, plan, fin,
                                 list(widths), args, layout)
    names = [name for name, _, _ in types]

    passes = pass_counts(args, layout, specs, [a for a, _ in dims])
    left_out = [{"layer": layer, "relations": [
        n for s, n, d in rels if d not in spec.dst]}
        for layer, spec in enumerate(specs)]
    est = estimate(train=True)
    counter = {
        "types": {n: {"rows": int(c), "input": kind, "height": int(h)}
                  for (n, c, kind), h in zip(types, heights)},
        "relations": {n: {"source": names[s], "destination": names[d],
                          "edges": layout["edges"][n]} for s, n, d in rels},
        "passes": passes, "left_out": left_out,
        # per chip: the tables, Adam's two moments, the gradient
        "row_owned_bytes": {"parameters": est["row_owned"] // 3,
                            "optimizer_state": 2 * est["row_owned"] // 3,
                            "gradient": est["row_owned"] // 3},
        "executed_slots_per_step": sum(p["slots"] for p in passes),
        "live_edges_per_step": sum(p["edges"] for p in passes),
    }
    statics = {"types": types, "relations": rels, "label": label,
               "specs": specs}
    return ModelSetup(
        fwd_static=statics,
        init_static={"types": types, "relations": rels,
                     "table_rows": tuple(layout["table_rows"])},
        extra_arrays=extra,
        mask_fields=(),
        lane_widths=tuple(a for a, _ in dims),
        lane_widths_bwd=lanes_bwd,
        param_count=param_count(fin, widths, types, rels),
        estimate_memory=estimate,
        counters={"rel.work": counter,
                  "slots.work": slot_work(*slot_passes(
                      args, layout, specs, [a for a, _ in dims]))},
        allow_pallas=False,
        row_owned={"emb": {n: layout["table_rows"][t]
                           for t, (n, _, kind) in enumerate(types)
                           if kind == "embedding"}},
        out_rows=("rel_out_rows", "rel_out_valid"))
