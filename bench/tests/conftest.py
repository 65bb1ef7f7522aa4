import json
from pathlib import Path

import pytest

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def tiny_cell():
    """A tiny DLRM under the benchmark cell's traffic and limits, for the CPU."""
    from bench.cell import Cell, load

    c = load("kaggle-shadow-easgd")
    return Cell("tiny", 1, json.loads((DATA / "dlrm-tiny.json").read_text()),
                dict(c.traffic, eval_batches=2, eval_batch_size=512), c.limits,
                c.end_to_end, c.per_layer)
