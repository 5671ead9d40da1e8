"""Full-batch training of a model whose forward reads labels, built from the
configuration's ``model`` block: ``runners/fullbatch_model.py`` as it is —
its ``build`` (a program without the model exits at once), warm-up, windows,
release and its ``bench:`` line of the bfloat16-table reading — with the
run's labels handed to the reference's forward as well, which a label-input
model needs (``logits(..., labels=)``; the reference's training step already
receives them).

Series: ``step`` — wall seconds per epoch.
"""

from __future__ import annotations

import functools
import os

import manifest

_model = manifest.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fullbatch_model.py"))
build, warm, sample, traced = (_model.build, _model.warm, _model.sample,
                               _model.traced)
first_updates, release = _model.first_updates, _model.release
reference_losses = _model.reference_losses


class _Labelled:
    """The reference, its forward reading the run's labels."""

    def __init__(self, ref, labels):
        self._ref = ref
        self.logits = functools.partial(ref.logits, labels=labels)

    def __getattr__(self, name):
        return getattr(self._ref, name)


def logits_pair(state, ctx, ref, precisions) -> tuple:
    """``fullbatch_model.logits_pair`` with the labels bound into every
    forward of the reference, the bfloat16-table reading's included."""
    _, _, labels = _model._cfg._on_device(state, ctx, ref)
    return _model.logits_pair(state, ctx, _Labelled(ref, labels), precisions)
