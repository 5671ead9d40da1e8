"""Plain reference training step of R-GCN, independent of ``sgcn_tpu``.

Schlichtkrull et al. (arXiv:1703.06103) as the OGB repository publishes it
for the ``ogbn-mag`` leaderboard, full-batch (``model`` is the
configuration's block: ``types`` with their counts and inputs in id order,
``relations`` as (source type, name, destination type), ``label_type``,
``hidden``, ``layers``).  ``N_r(i)`` are the neighbours of row i whose type
is relation r's source, in the pattern of Â without its diagonal (the union
of every relation and its reverse; Â's values are not read)::

    h_i = W_root[d] x_i + b[d] + sum_{r = (s -> d)} W_r mean_{j in N_r(i)} x_j

for a row i of type d, an empty neighbourhood giving 0; the first layer's
input is a type's features or its embedding table; ReLU between the layers;
the loss is the mean negative log-likelihood of ``log_softmax`` over the
labelled type's training rows, ``optax.adam``.

Straightforward ``jax.numpy`` in float32 on one device, the published
forward whole — every type, every relation, every layer, nothing left out
(what cannot reach the loss is the compiler's to drop, not this file's): per
relation a ``segment_sum`` of the gathered source rows and of ones over each
destination, and a division by the count; gradients by ``jax.grad`` of this
file, no custom gradients.  Â's pattern is cut per destination TYPE into
blocks of ``ROWS`` rows (``deepergcn_ref.py``'s layout, once per type); the
blocks run one after another under ``lax.map`` with the block rematerialised
in the backward pass, so that 42 M gathered rows of 128 lanes never exist at
once.  Every product runs under ``jax.default_matmul_precision("highest")``.
The parameters arrive as ``{"emb": {type: (count, f)}, "layers": [{"rel":
(relations, d_in, d_out), "root": (types, d_in, d_out), "bias": (types,
d_out)}]}``, every table in the type's id order.

Departures from the published code, each on purpose: dropout 0 (published
0.5), so a step is a function of the seed; the training rows are a prefix of
the labelled type's ids (published: the papers up to 2017).  No departure
from the program in precision: the program runs its dense products at
``Precision.HIGHEST`` too (they are a few percent of its epoch, and at the
TPU's default precision they stand as far from this file as a bfloat16
table does: below).

Tolerances, with what was measured on the v5e in PR 33 at the cell's size
(n = 1,939,743, 42,222,014 directed edges; PERF.md §6; eight runs on seven
seeds), every run reading each gap and, beside it, the same gap for this
file with the table its aggregation gathers held in bfloat16
(``runners/fullbatch_typed.py`` prints it on every run).

``RTOL`` bounds ``|loss_trainer / loss_reference - 1|`` over the first K = 2
losses from the same seeded initial weights; it is the accepted cells' 1e-4.
Read: 8.3e-8 to 1.7e-7 (six hundred times of room).  A loss is a mean over
629,571 rows: it checks the training arithmetic (every gradient, Adam on
154 M embedding parameters) and is nearly blind to precision.

``LOGITS_CHECKS`` compare, row by row over the labelled type, the logits of
the program's own ``predict()`` at the trained weights with this file's
forward pass, as ``norm(trainer - reference) / rms(reference)``, both at
``highest``:

* the largest gap may be 2e-5.  Read: 8.1e-7 to 1.7e-6 (twelve times of
  room): the order of the sums, and ``sum · (1 / count)`` against ``sum /
  count``.  With ``table_dtype="bfloat16"`` the trainer stands 5.8e-3 to
  9.9e-3 from this file: refused two hundred and ninety times over.
* the rms gap may be 2e-6.  Read: 5.9e-8 to 7.7e-8 (twenty-six times of
  room; fresh seeds read higher, hence the wider side); with the bfloat16
  table 2.9e-4 to 5.3e-4: refused a hundred and forty-seven times over.
  Either check alone refuses the narrow table.  Two layers amplify little:
  the limits sit a decade under the deep-stack cell's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

RTOL = 1e-4
# (precision of the reference's dense products, norm, limit)
LOGITS_CHECKS = (("highest", "max", 2e-5), ("highest", "rms", 2e-6))
ROWS = 4096              # destination rows per block


def _types(model: dict):
    names = [t["name"] for t in model["types"]]
    counts = [int(t["count"]) for t in model["types"]]
    starts = np.concatenate([[0], np.cumsum(counts)])
    return names, counts, starts


def coo_chunks(indptr, indices, data, rows: int = ROWS, model: dict = None):
    """CSR -> per node type ``(dst, src, valid, row)``, the first three
    ``(nblocks, emax)``: block r of a type holds the edges into its rows
    ``[r·rows, (r+1)·rows)`` (counted within the type), ``dst`` relative to
    the block's first row, ``src`` a global id, padded with invalid edges on
    the block's last row; ``row`` ``(nblocks, rows)`` the rows of each block
    (its shape says how the blocks were cut).  Â's values are not read, and
    its diagonal is dropped: a self-loop is no relation's edge."""
    del data
    indptr, indices = np.asarray(indptr), np.asarray(indices)
    _, counts, starts = _types(model)
    out = []
    for lo, count in zip(starts[:-1], counts):
        size = min(rows, count)
        nblocks = -(-count // size)
        at = lo + np.minimum(np.arange(nblocks + 1) * size, count)
        bounds = indptr[at]
        emax = max(int(np.diff(bounds).max()), 1)
        dst = np.full((nblocks, emax), size - 1, np.int32)
        src = np.zeros((nblocks, emax), np.int32)
        valid = np.zeros((nblocks, emax), bool)
        for r in range(nblocks):
            e0, e1 = int(bounds[r]), int(bounds[r + 1])
            deg = np.diff(indptr[at[r]:at[r + 1] + 1])
            d = np.repeat(np.arange(len(deg), dtype=np.int32), deg)
            j = indices[e0:e1]
            dst[r, :e1 - e0], src[r, :e1 - e0] = d, j
            valid[r, :e1 - e0] = j != at[r] + d
        row = np.arange(nblocks * size, dtype=np.int32).reshape(nblocks,
                                                                size)
        out.append((dst, src, valid, row))
    return tuple(out)


def relation_means(table, chunk, src_types, starts, count: int):
    """Per source type of ``src_types`` the mean of ``table``'s rows over
    the neighbours of that type, for every row of one destination type:
    ``[(count, f), ...]``, 0 where a row has no such neighbour."""
    dst, src, valid, row = chunk
    size = row.shape[1]

    @jax.checkpoint
    def block(args):
        d, j, ok = args
        rows = table[j].astype(jnp.float32)
        means = []
        for s in src_types:
            mine = ok & (j >= starts[s]) & (j < starts[s + 1])
            total = jax.ops.segment_sum(
                jnp.where(mine[:, None], rows, 0.0), d, num_segments=size,
                indices_are_sorted=True)
            n = jax.ops.segment_sum(mine.astype(jnp.float32), d,
                                    num_segments=size,
                                    indices_are_sorted=True)
            means.append(jnp.where(n[:, None] > 0,
                                   total / jnp.maximum(n, 1.0)[:, None], 0.0))
        return tuple(means)

    outs = lax.map(block, (dst, src, valid))
    return [m.reshape(-1, m.shape[-1])[:count] for m in outs]


def forward(params, feats, edges, model, activation, table_dtype=None):
    """Every type's rows after every layer; returns the labelled type's."""
    names, counts, starts = _types(model)
    rels = [(names.index(s), names.index(d)) for s, _, d in
            model["relations"]]
    act = {"relu": jax.nn.relu, "none": lambda v: v}[activation]
    x = [feats[starts[t]:starts[t + 1]] if kind["input"] == "features"
         else params["emb"][kind["name"]]
         for t, kind in enumerate(model["types"])]
    for layer, p in enumerate(params["layers"]):
        table = jnp.concatenate(x)              # global id order
        if table_dtype is not None:
            table = table.astype(table_dtype)
        out = []
        for d in range(len(names)):
            into = [(r, s) for r, (s, dd) in enumerate(rels) if dd == d]
            h = x[d] @ p["root"][d] + p["bias"][d]
            means = relation_means(table, edges[d], [s for _, s in into],
                                   starts, counts[d])
            for (r, _), mean in zip(into, means):
                h = h + mean @ p["rel"][r]
            out.append(act(h) if layer < len(params["layers"]) - 1 else h)
        x = out
    return x[names.index(model["label_type"])]


def loss_fn(params, feats, labels, mask, edges, model, activation):
    logp = jax.nn.log_softmax(
        forward(params, feats, edges, model, activation), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -jnp.sum(picked * mask) / jnp.sum(mask)


def _f32(params):
    return jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), params)


def _labelled(model, labels, mask):
    """Labels and training mask of the labelled type's rows."""
    names, _, starts = _types(model)
    t = names.index(model["label_type"])
    return (labels[starts[t]:starts[t + 1]].astype(jnp.int32),
            mask[starts[t]:starts[t + 1]].astype(jnp.float32))


def logits(params, edges, h0, precision: str = "highest",
           model: dict | None = None, activation: str = "relu",
           table_dtype: str | None = None) -> np.ndarray:
    """One forward pass, the labelled type's rows, on the host when done.
    ``precision="default"`` runs the dense products as the platform does
    when nothing is said (on a TPU: bf16 multiplicands); ``table_dtype``
    holds the table each layer's aggregation gathers in that dtype — the
    calibration reading."""
    fn = jax.jit(functools.partial(forward, model=model,
                                   activation=activation,
                                   table_dtype=table_dtype))
    with jax.default_matmul_precision(precision):
        out = fn(_f32(params), h0, edges)
    return np.asarray(out)


def training_losses(params0, steps, lr: float, model: dict,
                    activation: str = "relu") -> list[float]:
    """The loss before each of ``len(steps)`` Adam updates, starting from
    ``params0``.  ``steps`` yields ``(edges, features, labels, mask)`` per
    update, labels and mask over every row (the same tuple every time for
    full-batch training)."""
    opt = optax.adam(lr)

    @jax.jit
    def step(params, opt_state, edges, h0, labels, mask):
        labels, mask = _labelled(model, labels, mask)
        loss, grads = jax.value_and_grad(loss_fn)(
            params, h0, labels, mask, edges, model, activation)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params = _f32(params0)
    opt_state = opt.init(params)
    losses = []
    with jax.default_matmul_precision("highest"):
        for edges, h0, labels, mask in steps:
            params, opt_state, loss = step(params, opt_state, edges, h0,
                                           labels, mask)
            losses.append(float(loss))
    return losses
