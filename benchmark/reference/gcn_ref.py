"""Plain reference GCN training step, independent of ``sgcn_tpu``.

``H_{l+1} = act(Â H_l W_l)`` with bias-free layers, ReLU between layers and
none after the last, mean softmax cross-entropy over all rows, ``optax.adam``
— the model of ``GPU/PGCN.py`` that ``sgcn_tpu/models/gcn.py`` implements.
Straightforward ``jax.numpy`` in float32 on one device: Â is a dst-sorted COO
list cut into equal chunks, aggregation is a ``segment_sum`` per chunk under
``lax.scan`` (so the products shape's 124 M edges never materialise a
``(nnz, f)`` array), and every product runs under
``jax.default_matmul_precision("highest")``.  No kernels, no partitioning,
no custom gradients: the backward pass is ``jax.grad`` of this file.

Departure from the program, on purpose: the program's dense products run at
the TPU's default precision (bf16 multiplicands, f32 accumulation).

Tolerances, with what was measured on the v5e in PR 22 (PERF.md §6):

``RTOL`` bounds ``|loss_trainer / loss_reference - 1|`` over the first K losses
from the same seeded initial weights.  Read: 3.7e-7 to 4.3e-6 over 12 runs at
the products shape (k = 1 and k = 4), 4.5e-7 to 2.5e-6 at the arxiv shape
(full-batch and mini-batch).  A loss is a mean over every row: it checks the
training arithmetic (gradients, Adam), and it is blind to precision — holding
the products feature table in bf16 moved neither of the first two losses in
the last printed digit.

``LOGITS_CHECKS`` compare, row by row, the logits of the program's own
``predict()`` at the trained weights with this file's forward pass, as
``norm(trainer - reference) / rms(reference)``:

* against the reference as written (``highest``), the largest gap may be 0.2.
  Read: 0.018 to 0.044 (rms 5.1e-4 to 6.5e-4 at k = 1): what default-precision
  products cost: the reference alone, default against highest, differs by
  0.091 (rms 2.6e-3) at the initial weights.  A check for gross errors.
* against the reference with its dense products at the default precision
  too, the rms gap may be 4e-4.  Read: exactly 0 at k = 1 (the ELL slot order
  is the CSR order, so the sums are the same sums), largest gap 2.1e-3 to
  3.9e-3 at k = 4 (local edges, then halo edges: another order).  Summing
  each row's edges in the reverse order moves the reference by 1.0e-2 at
  most and 9.7e-5 rms — rare bf16 rounding flips — while a feature table
  held in bf16 moves it by 2.8e-2 at most and 1.9e-3 rms.  Only the rms tells
  the two apart: 4e-4 passes a reordered sum four times over and fails a
  bf16 table by a factor of five.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

RTOL = 1e-4
# (precision of the reference's dense products, norm, limit)
LOGITS_CHECKS = (("highest", "max", 0.2), ("default", "rms", 4e-4))
CHUNK = 1 << 21          # edges per scan step


def coo_chunks(indptr, indices, data, chunk: int = CHUNK,
               nchunks: int | None = None):
    """CSR → ``(dst, src, w)`` each ``(nchunks, chunk)``, dst-sorted, padded
    with zero-weight edges on the last row."""
    n = len(indptr) - 1
    nnz = int(indptr[-1])
    if nchunks is None:
        chunk = min(chunk, max(nnz, 1))
    need = -(-nnz // chunk)
    nchunks = need if nchunks is None else nchunks
    if nchunks < need:
        raise ValueError(f"{nnz} edges need {need} chunks, got {nchunks}")
    total = nchunks * chunk
    dst = np.full(total, n - 1, np.int32)
    dst[:nnz] = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    src = np.zeros(total, np.int32)
    src[:nnz] = indices[:nnz]
    w = np.zeros(total, np.float32)
    w[:nnz] = data[:nnz]
    shape = (nchunks, chunk)
    return dst.reshape(shape), src.reshape(shape), w.reshape(shape)


def aggregate(h, edges):
    """``Â h`` by a scan over edge chunks."""
    def body(acc, chunk):
        dst, src, w = chunk
        part = jax.ops.segment_sum(w[:, None] * h[src], dst,
                                   num_segments=h.shape[0],
                                   indices_are_sorted=True)
        return acc + part, None

    out, _ = lax.scan(body, jnp.zeros_like(h), edges)
    return out


def forward(params, h, edges):
    for i, w in enumerate(params):
        h = aggregate(h, edges) @ w
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h


def loss_fn(params, h0, labels, edges):
    logp = jax.nn.log_softmax(forward(params, h0, edges), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -jnp.mean(picked)


def logits(params, edges, h0, precision: str = "highest") -> np.ndarray:
    """One forward pass, on the host when done.  ``precision="default"`` runs
    the dense products as the platform does when nothing is said (on a TPU:
    bf16 multiplicands), the aggregation in float32 as ever."""
    with jax.default_matmul_precision(precision):
        out = jax.jit(forward)([jnp.asarray(w, jnp.float32) for w in params],
                               h0, edges)
    return np.asarray(out)


def training_losses(params0, steps, lr: float) -> list[float]:
    """The loss before each of ``len(steps)`` Adam updates, starting from
    ``params0``.  ``steps`` yields ``(edges, features, labels)`` per update
    (the same triple every time for full-batch training)."""
    opt = optax.adam(lr)

    @jax.jit
    def step(params, opt_state, edges, h0, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, h0, labels, edges)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params = [jnp.asarray(w, jnp.float32) for w in params0]
    opt_state = opt.init(params)
    losses = []
    with jax.default_matmul_precision("highest"):
        for edges, h0, labels in steps:
            params, opt_state, loss = step(params, opt_state, edges, h0,
                                           labels)
            losses.append(float(loss))
    return losses
