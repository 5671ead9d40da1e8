"""sgcn_tpu — TPU-native framework for scalable GCN training on partitioned graphs.

A ground-up JAX/XLA re-design of the capabilities of the reference artifact for
"Scalable Graph Convolutional Network Training on Distributed-Memory Systems"
(arXiv:2212.05009): full-batch and mini-batch GCN/GAT training over a
vertex-partitioned graph, one partition per chip, with

  * per-chip sparse adjacency blocks and segment-sum SpMM compiled under ``jit``,
  * boundary-vertex ("halo") feature exchange as a static padded ``all_to_all``
    over the ICI mesh, driven by a precomputed communication plan
    (``sgcn_tpu.parallel``, ``sgcn_tpu.ops``),
  * replicated dense weights whose gradients reduce via ``lax.psum``
    (``sgcn_tpu.train``),
  * a single-device dense oracle for parity testing (``sgcn_tpu.baselines``),
  * comm-volume / message-count / phase-time observability (``sgcn_tpu.utils``).

Consult each subpackage's docstring for what it provides; SURVEY.md §7 at the
repo root is the full build plan.

The package is importable both as ``sgcn_tpu`` and via the canonical repo-name
symlink. See SURVEY.md at the repo root for the reference structural analysis.
"""

__version__ = "0.1.0"
