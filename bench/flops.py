"""Operations of one DLRM training example, counted from the configuration's shapes.

Forward: ``2 * fan_in * fan_out`` per MLP layer, and the interaction's
own count from its file (``reference/interactions/<name>.py``; dot's is
``2 * n * n * dim`` for its ``n = tables + 1`` vectors). Forward and backward
together count three forwards (the backward takes the gradients of the
inputs and of the weights of each product), the convention of model FLOP
utilization. Nothing recomputed counts."""
from __future__ import annotations

from bench.reference.shapes import Shapes


def forward_flops(s: Shapes) -> int:
    f = s.interact.forward_flops(s)
    for dims in ((s.n_dense,) + s.bottom_mlp, (s.interact.out_dim(s),) + s.top_mlp):
        f += sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    return f


def train_flops_per_example(s: Shapes) -> int:
    return 3 * forward_flops(s)
