"""Plain reference training step of DeeperGCN, independent of ``sgcn_tpu``.

Li, Xiong, Thabet, Ghanem (arXiv:2006.07739) as its authors configure it for
the OGB ``ogbn-products`` leaderboard (``model`` is the configuration's block:
``layers``, ``hidden``, ``t``, ``eps``; ``aggr`` softmax_sg, ``norm`` batch,
``block`` res+, ``mlp_layers`` 1).  ``N(i)`` are the nonzeros of row i of Â
(A + I, symmetric); Â's values are not read::

    GENConv(x)_i = W (x_i + a_i) + b        m_j = ReLU(x_j) + eps
    w_ij[c] = softmax_{j in N(i)} (t m_j[c])   per destination i and channel c
    a_i[c]  = sum_j stop_gradient(w_ij[c]) m_j[c]
    h0 = GENConv_0(X W_enc + b_enc)
    hl = h(l-1) + GENConv_l(ReLU(BN_(l-1)(h(l-1))))        l = 1 .. L-1
    logits = ReLU(BN_(L-1)(h(L-1))) W_out + b_out
    BN(h) = gamma (h - mean_rows h) / sqrt(var_rows h + 1e-5) + beta

mean softmax cross-entropy over all rows, ``optax.adam``.  Straightforward
``jax.numpy`` in float32 on one device: a Python loop over the layers (no
scan), no factorisation of the softmax — per destination and channel a
``segment_max``, an ``exp`` and a ``segment_sum`` over that row's edges, the
weights under ``stop_gradient`` — BatchNorm by ``mean`` / ``var`` over all
rows, gradients by ``jax.grad`` of this file, no custom gradients.  Â's
pattern is cut into blocks of ``ROWS`` destination rows (``gat_ref.py``'s
layout); the blocks run one after another under ``lax.map`` with the block
rematerialised in the backward pass, and every layer is a ``jax.checkpoint``
of its own, so fourteen layers of 15.5 M edges x 128 channels never exist at
once.  Every product runs under ``jax.default_matmul_precision("highest")``.
The parameters arrive in the program's tree (``enc``, ``conv0``, ``layers``
stacked on a leading axis, ``head``) and are read layer by layer.

Departures from the published code, each on purpose: dropout 0 (published
0.5), so a step is a function of the seed; BatchNorm normalises with the
statistics of the whole graph at evaluation too (published: running mean and
variance, momentum 0.1) — full-batch, the evaluation batch is the training
batch; every row is in the training split; one full-batch step an epoch
(published: ten random clusters).  No departure from the program in
precision: unlike the GCN and the attention model, this model's program runs
its dense products at ``Precision.HIGHEST`` too (at the TPU's default
precision fourteen normalised layers stood as far from this file as a
bfloat16 table does: below).

Tolerances, with what was measured on the v5e in PR 31 at the cell's size
(n = 306,129, 15,546,379 nonzeros; PERF.md §6).  Calibrated on the chip, not
copied from the three-layer attention cell: fourteen normalised layers
amplify rounding differently.  The calibration readings are the builder's
chip calls 1 and 2, every run reading each gap and, beside it, the same gap
for this file with the table its aggregation gathers held in bfloat16
(``runners/fullbatch_model.py`` prints it on every run).

First, what the readings decided about the PROGRAM.  With its dense products
at the TPU's default precision (bf16 multiplicands; call 1, two seeds) the
trainer stood from this file by 7.0e-5 and 2.5e-4 in the second loss, by
2.9e-3 to 3.3e-3 rms (0.031 to 0.032 at most) in the logits, and from this
file computed at the default precision too by 9.4e-4 to 1.0e-3 rms — while a
bfloat16 table read 1.8e-3 to 1.9e-3 rms against it: a factor of 1.9, no
room for a limit with a margin on both sides.  The products are under 3 % of
the epoch, so the program now runs them at ``HIGHEST`` as this file does
(``dense_s`` 0.1019 -> 0.1025 s an epoch), and every reading below is of that
program (calls 2 and 3, eight seeds, one of them with ``keep = input``).

``RTOL`` bounds ``|loss_trainer / loss_reference - 1|`` over the first K = 2
losses from the same seeded initial weights; it is the accepted cells' 1e-4.
Read: at most 2e-7 in the first loss, 1.3e-7 to 1.03e-5 in the second
(ten times of room).  The second loss differs at all because two bias
vectors have a gradient that is zero but for rounding (a bias in front of a
norm), and Adam's first update divides that rounding noise by its own size.
A loss is a mean over every row: it checks the training arithmetic
(gradients through fourteen norms, Adam) and is nearly blind to precision.

``LOGITS_CHECKS`` compare, row by row, the logits of the program's own
``predict()`` at the trained weights with this file's forward pass, as
``norm(trainer - reference) / rms(reference)``, both at ``highest``:

* the largest gap may be 2e-4.  Read: 1.3e-5 to 2.3e-5 (nine times of room):
  the order of the sums, and the factorised softmax (``exp(t (m - M)) / S``
  against ``exp(t m - max) / sum``).  With ``table_dtype="bfloat16"`` the
  trainer stands 2.4e-3 to 3.2e-3 from this file: refused twelve times over.
* the rms gap may be 2e-5.  Read: 3.9e-7 to 4.3e-7 (forty-six times of room;
  fresh seeds read higher, hence the wider side); with the bfloat16 table
  2.2e-4 to 2.4e-4: refused eleven times over.  Either check alone refuses
  the narrow table; a default-precision product (3e-3 rms, above) fails both.
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
BN_EPS = 1e-5
_NEG = -1e30


def coo_chunks(indptr, indices, data, rows: int = ROWS):
    """CSR -> ``(dst, src, valid, row)``: the first three ``(nblocks,
    emax)`` — block r holds the edges of rows ``[r·rows, (r+1)·rows)``,
    ``dst`` relative to the block's first row, padded with invalid edges on
    the block's last row — and ``row`` ``(nblocks, rows)``, the rows of each
    block (its shape says how the blocks were cut).  Â's values are not
    read: every edge weighs 1."""
    del data
    n = len(indptr) - 1
    rows = min(rows, n)
    nblocks = -(-n // rows)
    bounds = np.asarray(indptr)[np.minimum(np.arange(nblocks + 1) * rows, n)]
    emax = int(np.diff(bounds).max())
    dst = np.full((nblocks, emax), rows - 1, np.int32)
    src = np.zeros((nblocks, emax), np.int32)
    valid = np.zeros((nblocks, emax), bool)
    deg = np.diff(indptr)
    for r in range(nblocks):
        lo, hi = int(bounds[r]), int(bounds[r + 1])
        d = deg[r * rows:(r + 1) * rows]
        dst[r, :hi - lo] = np.repeat(np.arange(len(d), dtype=np.int32), d)
        src[r, :hi - lo] = indices[lo:hi]
        valid[r, :hi - lo] = True
    row = np.arange(nblocks * rows, dtype=np.int32).reshape(nblocks, rows)
    return dst, src, valid, row


def softmax_aggregate(m, edges, t: float, table_dtype=None):
    """``a_i[c] = sum_j softmax_{j in N(i)}(t m_j[c]) m_j[c]``, the weights
    detached; ``m`` (n, C) in, (n, C) out."""
    dst, src, valid, row = edges
    nblocks, rows = row.shape
    table = m if table_dtype is None else m.astype(table_dtype)

    @jax.checkpoint
    def block(args):
        d, j, ok = args
        mj = table[j].astype(jnp.float32)
        e = jnp.where(ok[:, None], t * mj, _NEG)
        top = jax.ops.segment_max(e, d, num_segments=rows,
                                  indices_are_sorted=True)
        p = jnp.where(ok[:, None], jnp.exp(e - jnp.maximum(top, _NEG)[d]),
                      0.0)
        den = jax.ops.segment_sum(p, d, num_segments=rows,
                                  indices_are_sorted=True)
        w = lax.stop_gradient(p / jnp.maximum(den, 1e-30)[d])
        return jax.ops.segment_sum(w * mj, d, num_segments=rows,
                                   indices_are_sorted=True)

    out = lax.map(block, (dst, src, valid))
    return out.reshape(nblocks * rows, m.shape[1])[:m.shape[0]]


def batch_norm(h, gamma, beta):
    mean = h.mean(axis=0)
    var = h.var(axis=0)                     # biased, as BatchNorm normalises
    return gamma * (h - mean) / jnp.sqrt(var + BN_EPS) + beta


def gen_conv(x, w, b, edges, model, table_dtype):
    m = jax.nn.relu(x) + model["eps"]
    a = softmax_aggregate(m, edges, model["t"], table_dtype)
    return (x + a) @ w + b


def forward(params, h, edges, model: dict, table_dtype=None):
    enc, conv0, stack, head = (params[k] for k in ("enc", "conv0", "layers",
                                                   "head"))

    @jax.checkpoint
    def first(x):
        return gen_conv(x @ enc["w"] + enc["b"], conv0["w"], conv0["b"],
                        edges, model, table_dtype)

    h = first(h)
    for i in range(model["layers"] - 1):
        @jax.checkpoint
        def layer(h, p):
            x = jax.nn.relu(batch_norm(h, p["gamma"], p["beta"]))
            return h + gen_conv(x, p["w"], p["b"], edges, model, table_dtype)

        h = layer(h, jax.tree.map(lambda x, i=i: x[i], stack))
    x = jax.nn.relu(batch_norm(h, head["gamma"], head["beta"]))
    return x @ head["w"] + head["b"]


def loss_fn(params, h0, labels, edges, model):
    logp = jax.nn.log_softmax(forward(params, h0, edges, model), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -jnp.mean(picked)


def _f32(params):
    return jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), params)


def _static(model: dict) -> dict:
    """The numbers of the model block the equations read."""
    fixed = {"aggr": "softmax_sg", "norm": "batch", "block": "res+",
             "mlp_layers": 1}
    for key, only in fixed.items():
        if model.get(key, only) != only:
            raise ValueError(f"deepergcn_ref: {key}={model[key]!r} is not "
                             f"the published {only!r}")
    return {"layers": int(model["layers"]), "t": float(model["t"]),
            "eps": float(model["eps"])}


def logits(params, edges, h0, precision: str = "highest",
           model: dict | None = None, activation: str = "relu",
           table_dtype: str | None = None) -> np.ndarray:
    """One forward pass, on the host when done.  ``precision="default"`` runs
    the dense products as the platform does when nothing is said (on a TPU:
    bf16 multiplicands); ``table_dtype`` holds the table the aggregation
    gathers (the messages ``m``) in that dtype — the calibration reading."""
    del activation                  # ReLU is in the equations
    fn = jax.jit(functools.partial(forward, model=_static(model),
                                   table_dtype=table_dtype))
    with jax.default_matmul_precision(precision):
        out = fn(_f32(params), h0, edges)
    return np.asarray(out)


def training_losses(params0, steps, lr: float, model: dict,
                    activation: str = "relu") -> list[float]:
    """The loss before each of ``len(steps)`` Adam updates, starting from
    ``params0``.  ``steps`` yields ``(edges, features, labels)`` per update
    (the same triple every time for full-batch training)."""
    del activation
    opt = optax.adam(lr)
    model = _static(model)

    @jax.jit
    def step(params, opt_state, edges, h0, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, h0, labels, edges,
                                                  model)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params = _f32(params0)
    opt_state = opt.init(params)
    losses = []
    with jax.default_matmul_precision("highest"):
        for edges, h0, labels in steps:
            params, opt_state, loss = step(params, opt_state, edges, h0,
                                           labels)
            losses.append(float(loss))
    return losses
