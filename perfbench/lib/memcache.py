"""A DatasetCache that serves one in-memory dataset.

The window measures searches, not ingestion: writing and parsing a 1 GB CSV
takes minutes and belongs to no search. The coordinator and its executor
both read datasets through a ``DatasetCache``; this one answers from memory
for the id the harness registered (the way ``FetchingDatasetCache`` plugs
in) and defers to the base class for anything else."""

from __future__ import annotations

from cs230_distributed_machine_learning_tpu.data.datasets import DatasetCache
from cs230_distributed_machine_learning_tpu.models.base import TrialData


class MemoryDatasetCache(DatasetCache):
    def __init__(self):
        super().__init__()
        self._mem = {}

    def register(self, dataset_id, X, y, n_classes):
        data = TrialData(X=X, y=y, n_classes=int(n_classes))
        self._mem[dataset_id] = data
        return data

    def metadata(self, dataset_id):
        data = self._mem.get(dataset_id)
        if data is None:
            return super().metadata(dataset_id)
        n, d = data.X.shape
        return {"n_rows": int(n), "n_cols": int(d) + 1,
                "size_mb": round(data.X.nbytes / 2**20, 2)}

    def get(self, dataset_id, task):
        data = self._mem.get(dataset_id)
        return data if data is not None else super().get(dataset_id, task)
