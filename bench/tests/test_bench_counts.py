"""Byte and FLOP counts against hand counts, the peaks table, and the cell files."""
import json

import numpy as np
import pytest

from bench import bytes as nbytes
from bench import check
from bench.cell import BENCH, ROOT, load
from bench.flops import forward_flops, train_flops_per_example
from bench.peaks import peaks
from bench.reference.shapes import Shapes
from bench.tests.conftest import DATA

# (B, F, m) = (2, 2, 2). Table 0 holds ids 0, 0, 0, 3 (2 distinct), table 1
# ids 1, 2, 2, 2 (2 distinct).
IDS = np.array([[[0, 0], [1, 2]], [[0, 3], [2, 2]]])


def test_lookup_bytes_by_hand():
    # 8 rows read and 4 bag sums written, 4 floats of 4 bytes each
    assert nbytes.lookup_bytes(IDS, 4) == (8 + 4) * 4 * 4


def test_update_bytes_by_hand():
    # 8 gradient rows read; 4 distinct rows of table and accumulator read and written
    assert nbytes.distinct_rows(IDS) == 4
    assert nbytes.update_bytes(IDS, 4) == (8 + 4 * 4) * 4 * 4
    assert nbytes.total([IDS, IDS], 4, nbytes.update_bytes) == 2 * (8 + 16) * 16


def test_tiny_flops_by_hand():
    s = Shapes(n_dense=3, table_sizes=(5, 5), dim=2, multi_hot=1, bottom_mlp=(4, 2), top_mlp=(1,))
    # bottom 3x4 + 4x2; interaction of 3 vectors of 2; top (2 + 3) x 1
    assert forward_flops(s) == 2 * (12 + 8) + 2 * 3 * 3 * 2 + 2 * 5
    assert train_flops_per_example(s) == 3 * forward_flops(s)


@pytest.mark.parametrize("path,flops", [(DATA / "dlrm-ctr.json", 3_267_456),
                                        (BENCH / "configs" / "dlrm-criteo-kaggle.json", 2_916_192)])
def test_config_flops(path, flops):
    s = Shapes.from_config(json.loads(path.read_text()))
    assert train_flops_per_example(s) == flops


def test_peaks_known_and_unknown():
    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")


def test_every_cell_loads_with_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = load(w["name"])
        assert set(cell.limits) == set(check.NAMES)
        assert cell.traffic["trainers"] >= 1 and cell.chips in (1, 4)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        for m in cell.per_layer:
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
