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
restricted to one type: ELL bucket after bucket, within a bucket the rows of
that type in plan order, padded to the fullest chip's count.  In that order
a type's rows are a static slice, so a weight per TYPE is a product on a
slice; the aggregation's slots are the plan's own, regrouped by the type of
their destination (``build_typed_layout``: sub-buckets of ``ell_buckets``,
sub-classes of the tail's and the halo store's virtual rows), and a slot
carries its source's typed row and type in one int32, the mean's weight
``1 / deg_r(i)`` and the transposed weight ``1 / deg_r'(j)`` of the same
slot read from the other side (``ops.pspmm.typed_aggregate``).

**Every layer aggregates first**: per destination type the mean over each
source type into an accumulator of its own (``d_in`` lanes gathered, one
``d_in``-lane array per source type accumulated), then per type the products
``x_d W_root + sum_s A_ds W_(s->d)``.  The backward gathers, into the rows of
each source type that needs a gradient — at layer 0 the embedded types only
(features are data) — the cotangent of the block that type fills; the blocks
nobody reads are never formed, and the weights' gradients are dense products
on what the forward kept.  The v5e gathers rows at one rate
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
from ..ops.pspmm import (_FOLD_SCAN_LIVE, _TYPED_SCAN_LIVE, MAX_NODE_TYPES,
                         TYPE_SHIFT, typed_aggregate)
from ..parallel.mesh import AXIS
from ..parallel.plan import padding_rows
from .activations import get_activation
from .setup import ModelSetup

RGCN_PLAN_FIELDS = ("halo_src",)
INPUTS = ("features", "embedding")
STORES = ("e", "t", "h")            # ELL slots, hub tail, halo-source edges


class TypedSpec(NamedTuple):
    """Statics of one layer's ``typed_aggregate``."""
    heights: tuple      # rows of each type's table, in type order
    sources: tuple      # per type: the types with a relation INTO it
    layouts: tuple      # per type: (ell buckets, tail classes, halo classes)
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
    if len(names) > MAX_NODE_TYPES:
        raise ValueError(f"rgcn: {len(names)} node types; a slot's code "
                         f"holds {MAX_NODE_TYPES}")
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


# ------------------------------------------------------------------ layout
def _sub_layout(buckets, keep):
    """``buckets = ((n, w), ...)`` of a width-major slot layout (slot t of a
    bucket's row v at ``off + t·n + v``) restricted, per chip, to the rows
    ``keep[c]`` (a mask over Σ n rows) marks: the sub-buckets (``n`` the
    fullest chip's count, empty ones dropped), per chip the position each
    slot of the sub-layout had (−1: padding) and the row each of its rows
    was (−1: padding)."""
    k = len(keep)
    sub, takes, rows = [], [[] for _ in range(k)], [[] for _ in range(k)]
    off = r0 = 0
    for n, w in buckets:
        sel = [np.flatnonzero(m[r0:r0 + n]) for m in keep]
        n_sub = max(len(s) for s in sel)
        if n_sub:
            sub.append((n_sub, w))
            for c, s in enumerate(sel):
                pos = np.full((w, n_sub), -1, np.int64)
                pos[:, :len(s)] = off + np.arange(w)[:, None] * n + s[None]
                takes[c].append(pos.ravel())
                row = np.full(n_sub, -1, np.int64)
                row[:len(s)] = r0 + s
                rows[c].append(row)
        off += n * w
        r0 += n
    cat = lambda parts: (np.stack([np.concatenate(p) for p in parts])  # noqa: E731
                         if sub else np.zeros((k, 0), np.int64))
    return tuple(sub), cat(takes), cat(rows)


def build_typed_layout(plan, args: dict) -> dict:
    """Everything the model derives from the plan, per chip: the typed
    order, and per destination type the plan's slots regrouped with their
    codes and both weights (module docstring).  Returns ``arrays`` (shipped:
    ``ModelSetup.extra_arrays``), the statics (``heights``, ``layouts``,
    ``exchange``), ``rows`` per type ``(k, height)`` the table row of every
    typed row in the type's global id order (−1 padding) and ``edges`` per
    relation."""
    types, rels = args["types"], args["relations"]
    nt, k, b = len(types), plan.k, plan.b
    counts = np.array([c for _, c, _ in types], np.int64)
    if counts.sum() != plan.n:
        raise ValueError(f"rgcn: the type table counts {int(counts.sum())} "
                         f"rows, the plan {plan.n}")
    starts = np.concatenate([[0], np.cumsum(counts)])
    rel_of = -np.ones((nt, nt), np.int64)       # [source, destination]
    for r, (s, _, d) in enumerate(rels):
        rel_of[s, d] = r
    plan.ensure_fold_slots()
    gid = plan.global_row_ids()                             # (k, B), -1 pad
    typ = np.where(gid >= 0, np.searchsorted(starts, gid, "right") - 1, -1)
    hgid = plan.halo_global_rows()                          # (k, R)
    htyp = np.where(hgid >= 0, np.searchsorted(starts, hgid, "right") - 1,
                    -1)
    exchange = bool(plan.fold_halo_classes)

    # neighbours of every row by type, a self-loop being no edge
    deg = np.zeros((k, b, nt), np.int64)
    for c in range(k):
        for dst, src, cnt, tsrc, loop in (
                (plan.ledge_dst, plan.ledge_src, plan.lnnz, typ, True),
                (plan.hedge_dst, plan.hedge_src, plan.hnnz, htyp, False)):
            d_, s_ = dst[c, :int(cnt[c])], src[c, :int(cnt[c])]
            ok = (s_ != d_) if loop else np.ones(len(d_), bool)
            ok &= tsrc[c][s_] >= 0
            deg[c] += np.bincount(
                d_[ok].astype(np.int64) * nt + tsrc[c][s_[ok]],
                minlength=b * nt).reshape(b, nt)
    gdeg = plan.gather_rows(deg)                            # (n, nt)
    edges = {name: int(gdeg[starts[d]:starts[d + 1], s].sum())
             for s, name, d in rels}

    # the typed order: per type the ELL's sub-buckets
    ell_rows = sum(n for n, _ in plan.ell_buckets)
    subs = [_sub_layout(plan.ell_buckets,
                        [np.pad(typ[c] == t, (0, ell_rows - b))
                         for c in range(k)]) for t in range(nt)]
    heights = tuple(int(rows.shape[1]) for _, _, rows in subs)
    first = np.concatenate([[0], np.cumsum(heights)])
    pos = np.zeros((k, b), np.int64)            # typed row WITHIN its type
    for t, (_, _, rows) in enumerate(subs):
        for c in range(k):
            ok = rows[c] >= 0
            pos[c, rows[c][ok]] = np.flatnonzero(ok)

    def inv(x):
        return np.where(x > 0, 1.0 / np.maximum(x, 1), 0.0)

    def codes(c, t, dst_row, src, real, halo: bool):
        """Code and both weights of the slots of chip c whose destination
        rows ``dst_row`` have type t; ``src`` a local row or a halo rank."""
        u = np.where(real, (htyp if halo else typ)[c][src], 0)
        real = real & (u >= 0)
        if not halo:
            real = real & (src != dst_row)
        u = np.where(real, u, 0)
        row = src if halo else first[u] + pos[c][src]
        g_src = (hgid if halo else gid)[c][src]
        wf = np.where(real & (rel_of[u, t] >= 0),
                      inv(deg[c][np.where(real, dst_row, 0), u]), 0.0)
        wb = np.where(real & (rel_of[t, u] >= 0),
                      inv(gdeg[np.where(real, g_src, 0), t]), 0.0)
        n_pad = int((~real).sum())
        height = plan.r if halo else int(first[-1])
        code = (row + (u << TYPE_SHIFT)).astype(np.int64)
        code[~real] = padding_rows(n_pad, height)
        return (code.astype(np.int32), wf.astype(np.float32),
                wb.astype(np.float32))

    def store(t, sub, take, dst_rows, idx, w, halo: bool) -> dict:
        """One store's slots regrouped for type t: code and both weights of
        every slot of the sub-layout (``take``: its position in the plan's
        arrays ``idx`` / ``w``; ``dst_rows``: its destination, per chip)."""
        out = []
        for c in range(k):
            ok, at = take[c] >= 0, np.maximum(take[c], 0)
            out.append(codes(c, t, dst_rows[c], np.where(ok, idx[c][at], 0),
                             ok & (w[c][at] != 0), halo))
        return dict(zip(("code", "wf", "wb"), (np.stack(x)
                                               for x in zip(*out))))

    # the two fold stores: which virtual rows hold an edge at all
    folds = []
    for pre, classes, idx, w, vrow, halo in (
            ("t", plan.fold_tail_classes, plan.ft_idx, plan.ft_w,
             plan.ft_row, False),
            ("h", plan.fold_halo_classes, plan.fh_idx, plan.fh_w,
             plan.fh_row, True)):
        slot_row = _slot_rows(classes, np.arange(sum(n for n, _ in classes)))
        real = [np.bincount(slot_row[w[c] != 0], minlength=vrow.shape[1]) > 0
                for c in range(k)]
        folds.append((pre, classes, idx, w, vrow, halo, real))
    arrays = {"types": {}}
    layouts = []
    for t in range(nt):
        buckets, take, rows = subs[t]
        # ELL: a slot's destination is its row of the sub-bucket
        per = {f"e_{name}": x for name, x in store(
            t, buckets, take,
            [np.maximum(_slot_rows(buckets, rows[c]), 0) for c in range(k)],
            plan.ell_idx, plan.ell_w, False).items()}
        lay = [buckets]
        for pre, classes, idx, w, vrow, halo, real in folds:
            sub, vtake, vrows = _sub_layout(
                classes, [real[c] & (typ[c][vrow[c]] == t) for c in range(k)])
            # a virtual row's destination, and its typed row for the fold
            # (padding rows last)
            dest = [vrow[c][np.maximum(vrows[c], 0)] for c in range(k)]
            per.update({f"{pre}_{name}": x for name, x in store(
                t, sub, vtake,
                [_slot_rows(sub, dest[c]) for c in range(k)], idx, w,
                halo).items()})
            per[f"{pre}_row"] = np.stack([
                np.where(vrows[c] >= 0, pos[c][dest[c]],
                         max(heights[t] - 1, 0)) for c in range(k)
            ]).astype(np.int32)
            lay.append(sub)
        arrays["types"][t] = per
        layouts.append(tuple(lay))

    # the exchange's send rows, in the table's order
    send = np.asarray(plan.send_idx)
    arrays["send_rows"] = np.stack([
        (first[np.maximum(typ[c][send[c]], 0)] + pos[c][send[c]])
        for c in range(k)]).astype(np.int32)
    # typed row -> plan row (features are gathered through it), and ->
    # the row of the type's table in global id order
    plan_rows, table_rows = [], []
    for t, (_, _, rows) in enumerate(subs):
        ok = rows >= 0
        filler = np.stack([padding_rows(rows.shape[1], b)] * k)
        plan_rows.append(np.where(ok, rows, filler).astype(np.int32))
        g = np.stack([gid[c][np.maximum(rows[c], 0)] for c in range(k)])
        table_rows.append(np.where(ok, g - starts[t], -1))
    return {"arrays": arrays, "heights": heights, "layouts": tuple(layouts),
            "exchange": exchange, "plan_rows": plan_rows,
            "table_rows": table_rows, "edges": edges}


def _slot_rows(buckets, rows) -> np.ndarray:
    """The entry of ``rows`` (one per row of a width-major layout) every
    slot of the layout belongs to."""
    out, r0 = [], 0
    for n, w in buckets:
        out.append(np.tile(rows[r0:r0 + n], w))
        r0 += n
    return np.concatenate(out) if out else np.zeros(0, np.int64)


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
    arrays = {"types": {t: {f"{s}_{n}": pa[f"rel_{t}_{s}_{n}"]
                            for s in STORES
                            for n in ("code", "wf", "wb", "row")
                            if f"rel_{t}_{s}_{n}" in pa}
                        for t in range(len(types))
                        if f"rel_{t}_e_code" in pa},
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
    * ``rows_transient``: the most of — a layer's gather table beside the
      accumulators being filled; at the loss, the logits' gradient and the
      softmax; in a layer's backward, its output's cotangent, the cotangent
      blocks the gradient types read and the rows gathered into them — and
      the row-owned tables' gradient beside any of these;
    * ``slot_temps``: the slot passes' gathered rows and accumulators,
      bounded by the scan-unroll budgets of the typed passes and the folds
      and by the unrolled buckets' concurrent temporaries;
    * ``plan``: code (int32) and two weights (f32) per slot of every type's
      sub-layout, a destination per virtual row;
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
        table = sum(heights[t] for t in _read(spec)) * a * 4
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
    slots = sum(int(np.prod(x.shape[1:])) for per in
                layout["arrays"]["types"].values()
                for name, x in per.items() if name.endswith("_code"))
    vrows = sum(int(x.shape[1]) for per in layout["arrays"]["types"].values()
                for name, x in per.items() if name.endswith("_row"))
    shared = (param_count(fin, widths, args["types"], args["relations"])
              - sum(c for _, c, kd in args["types"] if kd == "embedding")
              * fin)
    # a row of the widest pass: its gathered lanes and an accumulator per
    # source type
    widest = max((len(spec.sources[d]) + 1) * a * 4
                 for spec, (a, _) in zip(specs, dims) for d in spec.dst)
    big = max((n for lay in layout["layouts"] for n, _ in lay[0]), default=0)
    parts = {"row_owned": owned, "rows_kept": kept,
             "rows_transient": transient
             + (emb_rows * fin * 4 if train else 0),
             "slot_temps": min(_TYPED_SCAN_LIVE + _FOLD_SCAN_LIVE,
                               16 * big * widest),
             "plan": 12 * slots + 4 * vrows,
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
    used = sorted({t for spec in specs for t in spec.dst}
                  | {t for spec in specs for t in spec.grad})
    extra = {"rel_send_rows": layout["arrays"]["send_rows"]}
    for t in used:
        for name, x in layout["arrays"]["types"][t].items():
            extra[f"rel_{t}_{name}"] = x
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

    def slots_of(ts):
        return int(sum(np.prod(layout["arrays"]["types"][t][f"{s}_code"]
                               .shape[1:]) for t in ts for s in STORES))

    passes = []
    for layer, (spec, (a, _)) in enumerate(zip(specs, dims)):
        passes.append({
            "layer": layer, "direction": "forward",
            "into": [names[d] for d in spec.dst],
            "relations": [n for s, n, d in rels if d in spec.dst],
            "slots": slots_of(spec.dst), "lanes": a,
            "table_rows": int(sum(heights[t] for t in _read(spec)))})
        live = list(spec.grad)
        passes.append({
            "layer": layer, "direction": "backward",
            "into": [names[s] for s in live],
            "relations": [n for s, n, d in rels
                          if d in spec.dst and s in spec.grad],
            "slots": slots_of(live), "lanes": a,
            "table_rows": int(sum(
                heights[d] for d in spec.dst for u in spec.sources[d]
                if u in spec.grad))})
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
        counters={"rel.work": counter},
        allow_pallas=False,
        row_owned={"emb": {n: layout["table_rows"][t]
                           for t, (n, _, kind) in enumerate(types)
                           if kind == "embedding"}},
        out_rows=("rel_out_rows", "rel_out_valid"))
