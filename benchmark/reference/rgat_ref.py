"""Plain reference training step of R-GAT, independent of ``sgcn_tpu``.

Busbridge et al.'s relational GAT as the OGB-LSC repository publishes it for
MAG240M (``examples/lsc/mag240m/rgnn.py --model rgat``; ``model`` is the
configuration's block: ``types`` with their counts in id order, every one
with features, ``relations`` as (source type, name, destination type),
``label_type``, ``hidden``, ``layers``, ``heads``).  ``N_r(i)`` are the
neighbours of row i whose type is relation r's source, in the pattern of Â
without its diagonal (Â's values are not read).  Layer l computes the rows
of its target set ``T_l`` — ``T_L`` the labelled type, ``T_(l-1)`` = ``T_l``
and the sources of the relations into it: the sampler's hops — as::

    h_i = W_skip x_i + b_skip + sum_{r in R_l} b_r
          + sum_{r = (s -> type(i))} concat_k sum_{j in N_r(i)} a^r_ijk (W_r x_j)_k
    a^r_ijk = softmax_{j in N_r(i)} LeakyReLU_0.2(a_src^r_k . (W_r x_j)_k
                                                  + a_dst^r_k . (W_r x_i)_k)
    x'_i = ELU(BatchNorm(h)_i)   (statistics over the rows of T_l)

``R_l`` the relations with an edge into ``T_l``, each bias added to every
row of ``T_l`` (PyG's ``GATConv`` bias on all targets of ``out += conv(...)``);
an empty ``N_r(i)`` gives 0.  The head over the labelled rows is ``Linear →
BatchNorm → ReLU → Linear``; the loss the mean negative log-likelihood of
``log_softmax`` over the training rows, ``optax.adam``.

Straightforward ``jax.numpy`` in float32 on one device: per relation the
projected source table ``W_r x`` and the destination rows' own projection
(no reassociation), then per destination type, in blocks of ``ROWS`` rows
under ``lax.map`` (rematerialised in the backward pass), a ``segment_max``
of the scores, a ``segment_sum`` of their exponents and of the weighted
source rows over the block's edges, every head at once — so that 1.7 M
gathered rows of 1,024 lanes never exist at once.  Gradients by
``jax.grad`` of this file, no custom gradients.  Every product runs under
``jax.default_matmul_precision("highest")``, as the program's do.  The
parameters arrive as the program's tree: ``{"layers": [{"w": (relations,
d_in, K·C), "att_src" / "att_dst": (relations, K, C), "b": (relations, K·C),
"skip_w", "skip_b", "bn_g", "bn_b"}], "head": {"w1", "b1", "bn_g", "bn_b",
"w2", "b2"}}``.

Departures from the published code, each on purpose: dropout 0 (published
0.5), so a step is a function of the seed; one full-batch step over every
row in place of sampled mini-batches of 1,024 papers at fan-outs 25 / 15 (the
target sets are the sampler's); the training rows are a prefix of the
labelled type's ids.

Tolerances, with what was measured on the v5e in PR 39 at the cell's size
(n = 119,219, 1,687,858 directed edges; three seeds; PERF.md §2), every run
reading each gap and, beside it, the same gap for this file with the table
its aggregation gathers (``[W_r x ‖ t]``) held in bfloat16
(``runners/fullbatch_typed.py`` prints it on every run).

``RTOL`` bounds ``|loss_trainer / loss_reference - 1|`` over the first K = 2
losses from the same seeded initial weights; it is the accepted cells' 1e-4.
Read: 0 to 1.8e-7.  A loss is a mean over the training papers: it checks the
training arithmetic (every gradient, Adam) and is nearly blind to precision.

``LOGITS_CHECKS`` compare, row by row over the labelled type, the logits of
the program's own ``predict()`` at the trained weights with this file's
forward pass, as ``norm(trainer - reference) / rms(reference)``, both at
``highest``:

* the largest gap may be 2e-4.  Read: 2.3e-6 to 4.9e-6 (forty times of
  room): the order of the sums, the reassociated destination scores, the
  product-spread coefficients.  With ``table_dtype="bfloat16"`` the trainer
  stands 1.5e-2 to 4.1e-2 from this file: refused seventy-five times over.
* the rms gap may be 2e-5.  Read: 1.4e-7 to 1.8e-7 (a hundred and ten
  times of room); with the bfloat16 table 1.0e-3 to 1.1e-3: refused fifty
  times over.  Either check alone refuses the narrow table; the limits sit
  a decade over the typed mean's (``rgcn_ref.py``): a softmax per relation
  and three BatchNorms amplify more than a mean.
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
LOGITS_CHECKS = (("highest", "max", 2e-4), ("highest", "rms", 2e-5))
ROWS = 4096              # destination rows per block
SLOPE = 0.2              # GATConv's negative_slope
BN_EPS = 1e-5            # torch.nn.BatchNorm1d's default


def _types(model: dict):
    names = [t["name"] for t in model["types"]]
    counts = [int(t["count"]) for t in model["types"]]
    starts = np.concatenate([[0], np.cumsum(counts)])
    return names, counts, starts


def targets(model: dict) -> list:
    """``[T_1, .., T_L]``: the type indices each layer computes."""
    names, _, _ = _types(model)
    rels = [(names.index(s), names.index(d))
            for s, _, d in model["relations"]]
    need = [{names.index(model["label_type"])}]
    for _ in range(int(model["layers"]) - 1):
        need.insert(0, need[0] | {s for s, d in rels if d in need[0]})
    return [sorted(t) for t in need]


def coo_chunks(indptr, indices, data, rows: int = ROWS, model: dict = None):
    """CSR -> per node type ``(dst, src, valid, row)``, the first three
    ``(nblocks, emax)``: block r of a type holds the edges into its rows
    ``[r·rows, (r+1)·rows)`` (counted within the type), ``dst`` relative to
    the block's first row, ``src`` a global id, padded with invalid edges on
    the block's last row; ``row`` ``(nblocks, rows)`` the rows of each block
    (clipped to the type's last row).  Â's values are not read, and its
    diagonal is dropped: a self-loop is no relation's edge."""
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
        row = np.minimum(np.arange(nblocks * size, dtype=np.int32),
                         count - 1).reshape(nblocks, size)
        out.append((dst, src, valid, row))
    return tuple(out)


def _leaky(x):
    return jnp.where(x > 0, x, SLOPE * x)


def attention(z, t, sd, chunk, lo: int, hi: int, count: int):
    """One relation's ``concat_k sum_j a_ijk z_jk`` for every row of one
    destination type: ``z`` (sources, K·C) and ``t`` (sources, K) the source
    type's projected rows and scores (ids ``[lo, hi)``), ``sd`` (count, K)
    the destinations' scores; 0 where a row has no such neighbour."""
    dst, src, valid, row = chunk
    size = row.shape[1]
    k = t.shape[1]

    @jax.checkpoint
    def block(args):
        d, j, ok, rows = args
        mine = ok & (j >= lo) & (j < hi)
        jl = jnp.where(mine, j - lo, 0)
        e = jnp.where(mine[:, None], _leaky(t[jl] + sd[rows][d]), -1e30)
        # any shift per row is exact: the softmax is invariant to it
        top = lax.stop_gradient(jax.ops.segment_max(
            e, d, num_segments=size, indices_are_sorted=True))
        ex = jnp.where(mine[:, None], jnp.exp(e - top[d]), 0.0)
        den = jax.ops.segment_sum(ex, d, num_segments=size,
                                  indices_are_sorted=True)
        alpha = ex / jnp.where(den > 0, den, 1.0)[d]
        msg = (z[jl].reshape(len(jl), k, -1) * alpha[..., None])
        return jax.ops.segment_sum(msg.reshape(len(jl), -1), d,
                                   num_segments=size, indices_are_sorted=True)

    out = lax.map(block, (dst, src, valid, row))
    return out.reshape(-1, out.shape[-1])[:count]


def _heads(z, a):
    """Per head ``a_k . z_k``: (rows, K·C) against (K, C) -> (rows, K), as
    a product at the matmul precision in force (a multiply-and-sum would be
    the compiler's to turn into a product at its own)."""
    return jnp.einsum("nkc,kc->nk", z.reshape(len(z), a.shape[0], -1), a)


def _batch_norm(h, g, b):
    mean = h.mean(0)
    var = ((h - mean) ** 2).mean(0)
    return (h - mean) / jnp.sqrt(var + BN_EPS) * g + b


def forward(params, feats, edges, model, activation="elu", table_dtype=None):
    """The labelled type's logits (module docstring)."""
    names, counts, starts = _types(model)
    rels = [(names.index(s), names.index(d))
            for s, _, d in model["relations"]]
    k = int(model["heads"])
    act = {"elu": jax.nn.elu, "relu": jax.nn.relu}[activation]
    x = [feats[starts[t]:starts[t + 1]] for t in range(len(names))]
    for into, p in zip(targets(model), params["layers"]):
        # R_l: the relations with an edge into T_l
        live = [r for r, (s, d) in enumerate(rels) if d in into]
        bias = p["skip_b"]
        for r in live:
            s, d = rels[r]
            _, src, valid, row = edges[d]
            has = jnp.any(valid & (src >= starts[s]) & (src < starts[s + 1]))
            bias = bias + jnp.where(has, p["b"][r], 0.0)
        hs = []
        for d in into:
            h = x[d] @ p["skip_w"] + bias
            for r in live:
                s, dd = rels[r]
                if dd != d:
                    continue
                w = p["w"][r]
                z = x[s] @ w
                t = _heads(z, p["att_src"][r])
                sd = _heads(x[d] @ w, p["att_dst"][r])
                if table_dtype is not None:
                    z = z.astype(table_dtype).astype(jnp.float32)
                    t = t.astype(table_dtype).astype(jnp.float32)
                h = h + attention(z, t, sd, edges[d], int(starts[s]),
                                  int(starts[s + 1]), counts[d])
            hs.append(h)
        y = act(_batch_norm(jnp.concatenate(hs), p["bn_g"], p["bn_b"]))
        x = [None] * len(names)
        at = 0
        for d in into:
            x[d] = y[at:at + counts[d]]
            at += counts[d]
    q = params["head"]
    y = x[names.index(model["label_type"])] @ q["w1"] + q["b1"]
    y = jax.nn.relu(_batch_norm(y, q["bn_g"], q["bn_b"]))
    return y @ q["w2"] + q["b2"]


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
           model: dict | None = None, activation: str = "elu",
           table_dtype: str | None = None) -> np.ndarray:
    """One forward pass, the labelled type's rows, on the host when done.
    ``table_dtype`` holds the table each relation's aggregation gathers in
    that dtype — the calibration reading."""
    fn = jax.jit(functools.partial(forward, model=model,
                                   activation=activation,
                                   table_dtype=table_dtype))
    with jax.default_matmul_precision(precision):
        out = fn(_f32(params), h0, edges)
    return np.asarray(out)


def training_losses(params0, steps, lr: float, model: dict,
                    activation: str = "elu") -> list[float]:
    """The loss before each of ``len(steps)`` Adam updates, starting from
    ``params0``.  ``steps`` yields ``(edges, features, labels, mask)`` per
    update, labels and mask over every row."""
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
