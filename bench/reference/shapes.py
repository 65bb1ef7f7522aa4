"""The sizes of one DLRM configuration, as the benchmark's config file gives them."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import numpy as np


@dataclass(frozen=True)
class Shapes:
    n_dense: int
    table_sizes: Tuple[int, ...]
    dim: int
    multi_hot: int
    bottom_mlp: Tuple[int, ...]
    top_mlp: Tuple[int, ...]

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(
            int(c["n_dense_features"]),
            tuple(int(s) for s in c["table_sizes"]),
            int(c["embedding_dim"]),
            int(c["multi_hot"]),
            tuple(int(s) for s in c["bottom_mlp"]),
            tuple(int(s) for s in c["top_mlp"]),
        )

    @property
    def n_tables(self) -> int:
        return len(self.table_sizes)

    @property
    def n_rows(self) -> int:
        return int(sum(self.table_sizes))

    @property
    def offsets(self) -> np.ndarray:
        """First global row of each table."""
        return np.concatenate([[0], np.cumsum(self.table_sizes)[:-1]]).astype(np.int64)

    @property
    def top_in(self) -> int:
        n = self.n_tables + 1
        return self.bottom_mlp[-1] + n * (n - 1) // 2
