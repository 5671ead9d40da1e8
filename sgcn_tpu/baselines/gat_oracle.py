"""Single-device dense GAT oracle — ground truth for distributed GAT parity.

Same role as ``DenseOracle`` (DGL-baseline analogue, SURVEY.md §4): identical
math to the distributed GAT — masked neighbor softmax ``e_ij = z1_i + z2_j``
over the Â nonzero pattern, ``H' = α·Z`` (``GPU/PGAT.py:137-150`` semantics
with proper -inf masking) — on one device with a dense mask.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import scipy.sparse as sp

from ..models.activations import get_activation
from ..models.gat import init_gat_params
from ..models.mhgat import init_mhgat_params, layer_shapes, resolve_args

_NEG = -1e30


class DenseGATOracle:
    def __init__(self, a: sp.spmatrix, fin: int, widths: list[int],
                 lr: float = 0.01, activation: str = "none",
                 final_activation: str = "none",
                 optimizer: optax.GradientTransformation | None = None,
                 seed: int = 0):
        self.mask = jnp.asarray(
            (sp.coo_matrix(a).todense() > 0), dtype=bool)
        dims = list(zip([fin] + widths[:-1], widths))
        self.params = init_gat_params(jax.random.PRNGKey(seed), dims)
        self.opt = optimizer if optimizer is not None else optax.adam(lr)
        self.opt_state = self.opt.init(self.params)
        self.activation = activation
        self.final_activation = final_activation
        self._step = jax.jit(self._make_step())

    def forward(self, params, h):
        act = get_activation(self.activation)
        fact = get_activation(self.final_activation)
        nl = len(params)
        for i, p in enumerate(params):
            z = h @ p["w"]
            scores = (z @ p["a1"])[:, None] + (z @ p["a2"])[None, :]
            scores = jnp.where(self.mask, scores, _NEG)
            alpha = jax.nn.softmax(scores, axis=-1)
            alpha = jnp.where(self.mask, alpha, 0.0)
            h = alpha @ z
            h = fact(h) if i == nl - 1 else act(h)
        return h

    def loss(self, params, h, labels, mask):
        logits = self.forward(params, h)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return -(picked * mask).sum() / mask.sum()

    def _make_step(self):
        def step(params, opt_state, h, labels, mask):
            loss, grads = jax.value_and_grad(self.loss)(params, h, labels, mask)
            updates, opt_state = self.opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss
        return step

    def step(self, h, labels, mask=None) -> float:
        h = jnp.asarray(h, jnp.float32)
        labels = jnp.asarray(labels, jnp.int32)
        mask = jnp.ones(h.shape[0]) if mask is None else jnp.asarray(mask, jnp.float32)
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, h, labels, mask)
        return float(loss)

    def predict(self, h) -> np.ndarray:
        return np.asarray(self.forward(self.params, jnp.asarray(h, jnp.float32)))

    def fit(self, h, labels, mask=None, epochs: int = 5) -> list[float]:
        return [self.step(h, labels, mask) for _ in range(epochs)]


class DenseMHGATOracle(DenseGATOracle):
    """Dense ground truth of ``models/mhgat.py``: K heads per layer, scores
    ``LeakyReLU(s_i + t_j)`` masked to Â's nonzero pattern, a dense row
    softmax per head, heads concatenated (hidden layers) or averaged (last),
    bias, linear skip, activation — the equations of that module's
    docstring on an (n, n) mask, gradients by plain autodiff."""

    def __init__(self, a: sp.spmatrix, fin: int, widths: list[int],
                 lr: float = 0.01, activation: str = "elu",
                 final_activation: str = "none",
                 optimizer: optax.GradientTransformation | None = None,
                 seed: int = 0, model_args: dict | None = None):
        self.mask = jnp.asarray(sp.coo_matrix(a).todense() != 0, dtype=bool)
        self.args = resolve_args(widths, model_args)
        self.shapes = layer_shapes(fin, widths, self.args["heads"],
                                   self.args["concat"])
        dims = list(zip([fin] + widths[:-1], widths))
        self.params = init_mhgat_params(jax.random.PRNGKey(seed), dims,
                                        **self.args)
        self.opt = optimizer if optimizer is not None else optax.adam(lr)
        self.opt_state = self.opt.init(self.params)
        self.activation = activation
        self.final_activation = final_activation
        self._step = jax.jit(self._make_step())

    def forward(self, params, h):
        act = get_activation(self.activation)
        fact = get_activation(self.final_activation)
        slope = self.args["slope"]
        nl = len(params)
        for i, (p, (_, k, c, _)) in enumerate(zip(params, self.shapes)):
            z = (h @ p["w"]).reshape(-1, k, c)                   # (n, K, C)
            t = jnp.einsum("nkc,kc->nk", z, p["a_src"])
            s = jnp.einsum("nkc,kc->nk", z, p["a_dst"])
            x = s[:, None, :] + t[None, :, :]                    # (i, j, K)
            e = jnp.where(x > 0, x, slope * x)
            e = jnp.where(self.mask[:, :, None], e, _NEG)
            alpha = jax.nn.softmax(e, axis=1)
            alpha = jnp.where(self.mask[:, :, None], alpha, 0.0)
            o = jnp.einsum("ijk,jkc->ikc", alpha, z)
            out = o.reshape(-1, k * c) if self.args["concat"][i] \
                else o.mean(axis=1)
            if self.args["bias"]:
                out = out + p["b"]
            if self.args["skip"]:
                out = out + h @ p["w_skip"] + p["b_skip"]
            h = fact(out) if i == nl - 1 else act(out)
        return h

    def grads(self, h, labels, mask=None):
        """``(loss, gradient tree)`` at the current parameters."""
        h = jnp.asarray(h, jnp.float32)
        mask = jnp.ones(h.shape[0]) if mask is None else jnp.asarray(mask)
        return jax.value_and_grad(self.loss)(
            self.params, h, jnp.asarray(labels, jnp.int32), mask)
