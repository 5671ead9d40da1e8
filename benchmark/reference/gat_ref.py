"""Plain reference training step of the multi-head GAT, independent of
``sgcn_tpu``.

Per layer with K heads of C channels (``model`` is the configuration's block:
``heads``, ``channels``, ``concat``, ``slope``, ``skip``, ``bias``)::

    Z = H W           t_j[k] = Z_j[k,:]·a_src[k]          s_i[k] = Z_i[k,:]·a_dst[k]
    e_ij = LeakyReLU(s_i + t_j)  for j in N(i) = {j : Â_ij ≠ 0}     α_ij = softmax_j e_ij
    O_i[k,:] = Σ_j α_ij[k] Z_j[k,:]      out = concat_k O + b | mean_k O + b
    H' = act(out + H W_skip + b_skip)    (no activation after the last layer)

mean softmax cross-entropy over all rows, ``optax.adam``.  Straightforward
``jax.numpy`` in float32 on one device.  Â's pattern is cut into blocks of
``ROWS`` destination rows, each with its own dst-sorted edge list padded to
the longest block's; a block's softmax is a ``segment_max`` and two
``segment_sum``s over its edges, and the blocks run one after another under
``lax.map`` with the block rematerialised in the backward pass, so the
products-eighth shape's 15.5 M edges × 512 lanes never exist at once.  Every
product runs under ``jax.default_matmul_precision("highest")``; the score
projections are multiply-and-sum, not products, so no precision setting
touches them.  No kernels, no partitioning, no custom gradients: the backward
pass is ``jax.grad`` of this file.

Departure from the program, on purpose: the program's dense products (``H W``,
the skips) run at the TPU's default precision (bf16 multiplicands, f32
accumulation).

Tolerances, with what was measured on the v5e in PR 27 at the cell's size
(n = 306,129, 15,546,379 nonzeros; PERF.md §6).  The calibration readings
are the builder's chip call 9's (the tool's 16th of the PR): two seeds, this
file as it stands, at the TRAINED weights each run's ``correct`` compares at
(``benchmark/run.py`` with every ``logits`` reading taken a second time with
the table in bfloat16).  The trainer's own readings are every run's of calls
5, 6 and 9.

``RTOL`` bounds ``|loss_trainer / loss_reference − 1|`` over the first K = 2
losses from the same seeded initial weights; it is the accepted cells' 1e-4.
Read: 1.9e-6 to 2.6e-5 over the seeds of PR 27's runs (the first reading
1.3e-5: more than three times of room).  Larger than the GCN cells' 1e-6
because three layers of default-precision products stand against ``highest``
ones.  A loss is a mean over every row: it checks the training arithmetic
(gradients, Adam) and is blind to precision.

``LOGITS_CHECKS`` compare, row by row, the logits of the program's own
``predict()`` at the trained weights with this file's forward pass, as
``norm(trainer − reference) / rms(reference)``:

* against the reference as written (``highest``), the largest gap may be
  0.06.  Read: 0.017 to 0.020 (rms 2.9e-3 to 3.4e-3): what default-precision
  products cost the program — the reference alone, default against highest,
  differs by 0.0178 and 0.0182 at most (rms 2.9e-3 and 3.0e-3).  A check for
  gross errors; it is NOT the one that refuses a narrow table (held in
  bfloat16, ``Z`` moves the ``highest`` reference by 1.8e-3 and 2.2e-3 at
  most, rms 2.9e-4: a tenth of what the products do).
* against the reference with its dense products at the default precision
  too, the rms gap may be 4e-4.  Read: 4.4e-5 to 5.3e-5 (largest gap 3.5e-3
  to 4.6e-3): the order of the sums, and rare bf16 rounding flips where the
  next layer's product reads them.  With ``table_dtype="bfloat16"`` — ``Z``,
  the table the aggregation gathers and the exchange ships, held in
  bfloat16 — the same reference moves by 1.25e-3 and 1.29e-3 rms (8.1e-3 and
  9.0e-3 at most), and the trainer stands 1.25e-3 and 1.29e-3 rms from that
  form: 4e-4 passes the program's largest reading seven times over and fails
  a bf16 table by a factor of three.
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
LOGITS_CHECKS = (("highest", "max", 0.06), ("default", "rms", 4e-4))
ROWS = 4096              # destination rows per block
_NEG = -1e30


def coo_chunks(indptr, indices, data, rows: int = ROWS):
    """CSR → ``(dst, src, valid, row)``: the first three ``(nblocks, emax)``
    — block r holds the edges of rows ``[r·rows, (r+1)·rows)``, ``dst``
    relative to the block's first row, padded with invalid edges on the
    block's last row — and ``row`` ``(nblocks, rows)``, the rows of each
    block (the last block's run past n; ``attention`` pads for them).
    Â's values are not read: attention uses the pattern."""
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


def attention(z, s, t, edges, slope: float, table_dtype=None):
    """``O`` (n, K, C) from ``z`` (n, K, C), ``s``, ``t`` (n, K)."""
    dst, src, valid, row = edges
    nblocks, rows = row.shape
    n = z.shape[0]
    table = z if table_dtype is None else z.astype(table_dtype)
    s_blocks = jnp.pad(s, ((0, nblocks * rows - n), (0, 0)))[row]

    @jax.checkpoint
    def block(args):
        d, j, ok, s_b = args
        x = s_b[d] + t[j]
        e = jnp.where(ok[:, None], jnp.where(x > 0, x, slope * x), _NEG)
        m = jax.ops.segment_max(e, d, num_segments=rows,
                                indices_are_sorted=True)
        p = jnp.where(ok[:, None], jnp.exp(e - jnp.maximum(m, _NEG)[d]), 0.0)
        den = jax.ops.segment_sum(p, d, num_segments=rows,
                                  indices_are_sorted=True)
        alpha = p / jnp.maximum(den, 1e-30)[d]
        return jax.ops.segment_sum(
            alpha[:, :, None] * table[j].astype(jnp.float32), d,
            num_segments=rows, indices_are_sorted=True)

    out = lax.map(block, (dst, src, valid, s_blocks))
    return out.reshape(nblocks * rows, *z.shape[1:])[:n]


def forward(params, h, edges, model: dict, activation: str = "elu",
            table_dtype=None):
    act = {"elu": jax.nn.elu, "relu": jax.nn.relu,
           "none": lambda x: x}[activation]
    for i, p in enumerate(params):
        k, c = model["heads"][i], model["channels"][i]
        z = (h @ p["w"]).reshape(-1, k, c)
        t = (z * p["a_src"]).sum(-1)
        s = (z * p["a_dst"]).sum(-1)
        o = attention(z, s, t, edges, model["slope"], table_dtype)
        out = o.reshape(-1, k * c) if model["concat"][i] else o.mean(axis=1)
        if model["bias"]:
            out = out + p["b"]
        if model["skip"]:
            out = out + h @ p["w_skip"] + p["b_skip"]
        h = out if i == len(params) - 1 else act(out)
    return h


def loss_fn(params, h0, labels, edges, model, activation):
    logp = jax.nn.log_softmax(
        forward(params, h0, edges, model, activation), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -jnp.mean(picked)


def _f32(params):
    return jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), params)


def _static(model: dict) -> dict:
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in model.items() if k != "name"}


def logits(params, edges, h0, precision: str = "highest",
           model: dict | None = None, activation: str = "elu",
           table_dtype: str | None = None) -> np.ndarray:
    """One forward pass, on the host when done.  ``precision="default"`` runs
    the dense products as the platform does when nothing is said (on a TPU:
    bf16 multiplicands); ``table_dtype`` holds the gathered table ``Z`` in
    that dtype (the calibration reading of the module docstring)."""
    fn = jax.jit(functools.partial(forward, model=_static(model),
                                   activation=activation,
                                   table_dtype=table_dtype))
    with jax.default_matmul_precision(precision):
        out = fn(_f32(params), h0, edges)
    return np.asarray(out)


def training_losses(params0, steps, lr: float, model: dict,
                    activation: str = "elu") -> list[float]:
    """The loss before each of ``len(steps)`` Adam updates, starting from
    ``params0``.  ``steps`` yields ``(edges, features, labels)`` per update
    (the same triple every time for full-batch training)."""
    opt = optax.adam(lr)
    model = _static(model)

    @jax.jit
    def step(params, opt_state, edges, h0, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, h0, labels, edges,
                                                  model, activation)
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
