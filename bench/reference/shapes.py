"""The sizes of one DLRM configuration, as the benchmark's config file gives them.

``multi_hot`` is one bag size for every table, or a list of one size per
table. One size gives a batch's ids as (B, F, m), the program's layout; a
list gives (B, sum of m_f), table f's bag in columns ``[start_f, start_f +
m_f)``. Flattened to (B, -1), the two layouts agree, and every function
here reads a batch through ``bags`` so that one path serves both.

``interaction`` names the file ``interactions/<interaction>.py`` beside
this one (``bench/cell.py`` gives its interface); a name with no file fails
when the configuration is read."""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

from bench.cell import load_module

INTERACTIONS = Path(__file__).resolve().parent / "interactions"


def interaction(name: str):
    """The interaction module of that name."""
    return load_module(INTERACTIONS, name)


def bag_sizes(ids, bags: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
    """``bags``, or for (B, F, m) ids the one size m of each of the F tables."""
    return tuple(bags) if bags is not None else (ids.shape[2],) * ids.shape[1]


def spans(bags: Sequence[int]) -> List[Tuple[int, int]]:
    """(first column, bag size) of each table once ids are flattened to (B, -1)."""
    starts = np.concatenate([[0], np.cumsum(bags)[:-1]]).astype(int)
    return [(int(a), int(m)) for a, m in zip(starts, bags)]


def column_tables(bags: Sequence[int]) -> np.ndarray:
    """The table of each column once ids are flattened to (B, -1)."""
    return np.repeat(np.arange(len(bags)), bags)


def blocks(ids, bags: Sequence[int]) -> list:
    """Each table's (B, m_f) block of a batch's ids."""
    flat = ids.reshape(ids.shape[0], -1)
    return [flat[:, a:a + m] for a, m in spans(bags)]


@dataclass(frozen=True)
class Shapes:
    n_dense: int
    table_sizes: Tuple[int, ...]
    dim: int
    multi_hot: Union[int, Tuple[int, ...]]
    bottom_mlp: Tuple[int, ...]
    top_mlp: Tuple[int, ...]
    interaction: str = "dot"

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        m = c["multi_hot"]
        s = cls(
            int(c["n_dense_features"]),
            tuple(int(v) for v in c["table_sizes"]),
            int(c["embedding_dim"]),
            int(m) if isinstance(m, int) else tuple(int(v) for v in m),
            tuple(int(v) for v in c["bottom_mlp"]),
            tuple(int(v) for v in c["top_mlp"]),
            str(c["interaction"]),
        )
        if len(s.bags) != s.n_tables:
            raise ValueError(f"multi_hot gives {len(s.bags)} bag sizes for {s.n_tables} tables")
        interaction(s.interaction)  # an unknown one fails here, naming its file
        return s

    @property
    def interact(self):
        return interaction(self.interaction)

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
    def bags(self) -> Tuple[int, ...]:
        """The bag size of each table."""
        m = self.multi_hot
        return (m,) * self.n_tables if isinstance(m, int) else m

    @property
    def n_ids(self) -> int:
        """Ids per example."""
        return int(sum(self.bags))

    def id_shape(self, batch_size: int) -> Tuple[int, ...]:
        if isinstance(self.multi_hot, int):
            return (batch_size, self.n_tables, self.multi_hot)
        return (batch_size, self.n_ids)

    def by_column(self, per_table, dtype=np.int64) -> np.ndarray:
        """A value per table, laid out as one example's ids are."""
        out = np.asarray(per_table, dtype)[column_tables(self.bags)]
        return out.reshape(self.id_shape(1)[1:])
