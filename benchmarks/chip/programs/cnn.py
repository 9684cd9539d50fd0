"""The program's Section 4.2 CNN, as ``benchmarks/fig4_cnn.py`` builds it."""
from __future__ import annotations


def grad_fn(config: dict):
    from repro.models import cnn

    return cnn.make_grad_fn()
