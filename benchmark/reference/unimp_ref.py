"""Plain reference training step of UniMP's graph transformer, independent of
``sgcn_tpu``.

Shi et al.'s masked label prediction model (arXiv:2009.03509) in the form
PyTorch Geometric gives it (``TransformerConv(..., beta=True)``,
``MaskLabel``; ``model`` is the configuration's block: ``layers``,
``hidden``, ``heads``, ``label_rate``, ``label_seed``).  ``N(i)`` is the
pattern of Â without its diagonal (Â's values are not read).  With P the
label-input rows — ``jax.random.uniform(PRNGKey(label_seed), (n,)) <
label_rate`` over the global rows — and per layer K heads of C channels
(C = hidden ÷ K on the hidden layers, the number of classes on the last)::

    x0_i = h0_i + [i in P] E[label_i]
    q, k, v, r = x W_q + b_q, x W_k + b_k, x W_v + b_v, x W_r + b_r
    e_ij[h] = q_i[h] . k_j[h] / sqrt(C)   a_ij[h] = softmax_{j in N(i)} e_ij[h]
    o_i = concat_h sum_j a_ij[h] v_j[h]  (hidden)  |  mean_h ...  (last)
    beta_i = sigmoid(w_beta . [o_i, r_i, o_i - r_i])
    y_i = beta_i r_i + (1 - beta_i) o_i
    x'_i = ReLU(LayerNorm(y_i))  (hidden, eps 1e-5)  |  logits_i = y_i  (last)

an empty ``N(i)`` giving o_i = 0; the loss the mean negative log-likelihood of
``log_softmax`` over the rows NOT in P, ``optax.adam``.

Straightforward ``jax.numpy`` in float32 on one device: per layer, in blocks
of ``ROWS`` destination rows under ``lax.map`` (rematerialised in the
backward pass), each block's edges gathered from the whole ``q``, ``k``,
``v`` tables, a ``segment_max`` of the scores, a ``segment_sum`` of their
exponents and of the weighted value rows, every head at once — so that the
15.2 M edges' gathered rows never exist at once.  Gradients by ``jax.grad``
of this file, no custom gradients.  Every product runs under
``jax.default_matmul_precision("highest")``, as the program's do; the scores
are multiply-and-sum, which no precision setting touches.  The parameters
arrive as the program's tree: ``{"emb": (classes, f), "layers": [{"w_query",
"b_query", "w_key", "b_key", "w_value", "b_value", "w_skip", "b_skip",
"w_beta", "ln_scale", "ln_bias"}]}`` (no LayerNorm on the last layer).

Departures from the published code, each on purpose: dropout 0 (published
0.3 on features and attention coefficients), so a step is a function of the
seed; P is drawn once for the run (published: anew each epoch, over the
training rows), and ``logits`` reads the same P (published inference feeds
every training label; with every row training, that would feed each row its
own label).

Tolerances.  ``RTOL`` bounds ``|loss_trainer / loss_reference - 1|`` over
the first K = 2 losses from the same seeded initial weights; it is the
accepted cells' 1e-4.  A loss is a mean over the rows off P: it checks the
training arithmetic (every gradient, Adam) and is nearly blind to precision.

``LOGITS_CHECKS`` compare, row by row, the logits of the program's own
``predict()`` at the trained weights with this file's forward pass, as
``norm(trainer - reference) / rms(reference)``, both at ``highest``, at the
limits of the other attention cells (``rgat_ref.py``, ``deepergcn_ref.py``):
the largest gap may be 2e-4, the rms gap 2e-5.  Both sides compute the same
sums of the same f32 products in other orders (a slot pass against a segment
sum; the program scales q by 1/sqrt(C) before the product, this file the
product), so the gap is rounding; the runner also reads, on every run, the
gap to this file with the table the aggregation gathers, ``[k ‖ v]``, held
in bfloat16 — the reading both limits must refuse.  On the v5e, at the
cell's size and four seeds, the sound gap read 1.7e-6–3.3e-6 at most and
1.3e-7–1.6e-7 rms (×60 and ×129 under the limits) and the bfloat16 reading
2.4e-3–3.6e-3 and 2.4e-4–3.0e-4 (×12 and more over them): both limits lie
between the two, near their geometric mean (PERF.md §2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

RTOL = 1e-4
# (precision of the reference's dense products, norm, limit)
LOGITS_CHECKS = (("highest", "max", 2e-4), ("highest", "rms", 2e-5))
ROWS = 2048              # destination rows per block
EPS = 1e-5               # LayerNorm's


def label_rows(n: int, model: dict):
    """P over the ``n`` global rows, as the configuration's block draws it:
    ``jax.random``'s counter-based generator, bit-exact on every backend."""
    key = jax.random.PRNGKey(int(model["label_seed"]))
    return jax.random.uniform(key, (n,)) < float(model["label_rate"])


def coo_chunks(indptr, indices, data, rows: int = ROWS):
    """CSR → ``(dst, src, valid, row)`` WITHOUT the diagonal: the first three
    ``(nblocks, emax)`` — block r holds the off-diagonal entries of rows
    ``[r·rows, (r+1)·rows)``, ``dst`` relative to the block's first row,
    padded with invalid edges on the block's last row — and ``row``
    ``(nblocks, rows)``, the rows of each block (the last block's run past
    n; ``attention`` pads for them)."""
    del data
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    n = len(indptr) - 1
    rows = min(rows, n)
    deg = np.diff(indptr)
    row_of = np.repeat(np.arange(n), deg)
    keep = indices != row_of
    dst_all, src_all = row_of[keep], indices[keep]
    cnt = np.bincount(dst_all, minlength=n)
    starts = np.concatenate([[0], np.cumsum(cnt)])
    nblocks = -(-n // rows)
    bounds = starts[np.minimum(np.arange(nblocks + 1) * rows, n)]
    emax = max(int(np.diff(bounds).max()), 1)
    dst = np.full((nblocks, emax), rows - 1, np.int32)
    src = np.zeros((nblocks, emax), np.int32)
    valid = np.zeros((nblocks, emax), bool)
    for r in range(nblocks):
        lo, hi = int(bounds[r]), int(bounds[r + 1])
        dst[r, :hi - lo] = dst_all[lo:hi] - r * rows
        src[r, :hi - lo] = src_all[lo:hi]
        valid[r, :hi - lo] = True
    row = np.arange(nblocks * rows, dtype=np.int32).reshape(nblocks, rows)
    return dst, src, valid, row


def attention(q, k, v, edges, table_dtype=None):
    """``o`` (n, K, C) from ``q``, ``k``, ``v`` (n, K, C)."""
    dst, src, valid, row = edges
    nblocks, rows = row.shape
    n = q.shape[0]
    scale = 1.0 / np.sqrt(q.shape[-1])
    if table_dtype is not None:
        k, v = k.astype(table_dtype), v.astype(table_dtype)
    q_blocks = jnp.pad(q, ((0, nblocks * rows - n), (0, 0), (0, 0)))[row]

    @jax.checkpoint
    def block(args):
        d, j, ok, q_b = args
        e = (q_b[d] * k[j].astype(jnp.float32)).sum(-1) * scale    # (E, K)
        e = jnp.where(ok[:, None], e, -jnp.inf)
        m = lax.stop_gradient(jax.ops.segment_max(
            e, d, num_segments=rows, indices_are_sorted=True))
        p = jnp.where(ok[:, None], jnp.exp(e - jnp.where(
            jnp.isfinite(m), m, 0.0)[d]), 0.0)
        den = jax.ops.segment_sum(p, d, num_segments=rows,
                                  indices_are_sorted=True)
        alpha = p / jnp.maximum(den, 1e-30)[d]
        return jax.ops.segment_sum(
            alpha[:, :, None] * v[j].astype(jnp.float32), d,
            num_segments=rows, indices_are_sorted=True)

    out = lax.map(block, (dst, src, valid, q_blocks))
    return out.reshape(nblocks * rows, *q.shape[1:])[:n]


def forward(params, h0, labels, edges, model: dict, activation: str = "relu",
            table_dtype=None):
    act = {"relu": jax.nn.relu, "elu": jax.nn.elu,
           "none": lambda x: x}[activation]
    n = h0.shape[0]
    in_p = label_rows(n, model)
    x = h0 + jnp.where(in_p[:, None], params["emb"][labels], 0.0)
    heads = int(model["heads"])
    layers = params["layers"]
    for i, p in enumerate(layers):
        last = i == len(layers) - 1
        q = x @ p["w_query"] + p["b_query"]
        k = x @ p["w_key"] + p["b_key"]
        v = x @ p["w_value"] + p["b_value"]
        r = x @ p["w_skip"] + p["b_skip"]
        o = attention(*(t.reshape(n, heads, -1) for t in (q, k, v)), edges,
                      table_dtype)
        o = o.mean(axis=1) if last else o.reshape(n, -1)
        beta = jax.nn.sigmoid(jnp.concatenate([o, r, o - r], axis=1)
                              @ p["w_beta"])
        y = beta * r + (1.0 - beta) * o
        if last:
            return y
        mu = y.mean(axis=1, keepdims=True)
        var = ((y - mu) ** 2).mean(axis=1, keepdims=True)
        x = act((y - mu) / jnp.sqrt(var + EPS) * p["ln_scale"]
                + p["ln_bias"])
    raise ValueError("no layers")


def loss_fn(params, h0, labels, edges, model, activation):
    logp = jax.nn.log_softmax(
        forward(params, h0, labels, edges, model, activation), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    off = (~label_rows(h0.shape[0], model)).astype(jnp.float32)
    return -jnp.sum(picked * off) / jnp.maximum(off.sum(), 1.0)


def _f32(params):
    return jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), params)


def _static(model: dict) -> dict:
    return {k: v for k, v in model.items() if k != "name"}


def logits(params, edges, h0, precision: str = "highest",
           model: dict | None = None, activation: str = "relu",
           table_dtype: str | None = None, labels=None) -> np.ndarray:
    """One forward pass, on the host when done; ``labels`` are every row's
    (the label input reads P's).  ``table_dtype`` holds the gathered table
    ``[k ‖ v]`` in that dtype (the reading the limits must refuse)."""
    static = _static(model)
    fn = jax.jit(lambda p, h, y, e: forward(p, h, y, e, static, activation,
                                            table_dtype))
    with jax.default_matmul_precision(precision):
        out = fn(_f32(params), h0, labels, edges)
    return np.asarray(out)


def training_losses(params0, steps, lr: float, model: dict,
                    activation: str = "relu") -> list[float]:
    """The loss before each of ``len(steps)`` Adam updates, starting from
    ``params0``.  ``steps`` yields ``(edges, features, labels)`` per update
    (the same triple every time for full-batch training)."""
    opt = optax.adam(lr)
    static = _static(model)

    @jax.jit
    def step(params, opt_state, edges, h0, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, h0, labels, edges,
                                                  static, activation)
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
