"""Host-side CSR container: sparse input without the dense matrix.

The framework's device storage IS dense binned columns (SURVEY §7: TPUs
have no fast gather/scatter; EFB re-compresses mutually-exclusive sparse
columns at construct) — but getting from a sparse matrix to those uint8
columns must not materialize the FULL ``[nrow, ncol]`` float64 matrix:
an 8-byte-per-cell spike dwarfing both the nnz-sized source and the
1-byte-per-cell destination.  :class:`CsrMatrix` keeps the CSR triplet
host-side — the C ABI's copied buffers, or a ``scipy.sparse`` matrix's
own (:func:`from_scipy`).  Every consumer peaks at one bounded run of
rows (:data:`CSR_CHUNK_BUDGET_BYTES`) beside the source and the
destination: dataset construction walks a run's stored entries column by
column (:meth:`CsrMatrix.iter_by_column`, ``dataset.construct_csr``:
time in the stored entries, never in the cells); PushRows ingest and
predict densify a run (:meth:`CsrMatrix.iter_dense_chunks`).
"""
from __future__ import annotations

from typing import Iterator, Optional, Tuple

import numpy as np

# dense-densify working-set ceiling: one yielded chunk is at most this
# many bytes of float64 (the peak the memory-budget test pins)
CSR_CHUNK_BUDGET_BYTES = 64 << 20


def csr_chunk_rows(ncol: int, budget_bytes: Optional[int] = None) -> int:
    """Rows per dense chunk so one chunk stays under the byte budget."""
    budget = CSR_CHUNK_BUDGET_BYTES if budget_bytes is None else budget_bytes
    return max(1, int(budget) // max(1, int(ncol) * 8))


def _keep_width(a, kinds: str, fallback) -> np.ndarray:
    """``a`` as an array of its own dtype where that is one of ``kinds``
    (80M stored entries widened to int64 / float64 are 1.3 GB nobody
    reads), else of ``fallback``."""
    a = np.asarray(a)
    return a if a.dtype.kind in kinds else a.astype(fallback)


class CsrMatrix:
    """CSR triplet (``indptr``/``indices``/``data``) + shape, in the
    caller's index and value widths.

    Buffers are copied on construction by default — C-ABI callers may
    free theirs the moment the call returns (reference
    ``LGBM_DatasetCreateFromCSR`` contract); ``copy=False`` keeps views
    of arrays the caller goes on holding (:func:`from_scipy`).
    ``np.asarray`` still works (full chunk-assembled densify) so legacy
    consumers that genuinely need the whole matrix — cv, subset,
    continued training — keep functioning; the construction / push /
    predict fast paths never call it."""

    def __init__(self, indptr, indices, data, ncol: int, copy: bool = True):
        self.indptr = _keep_width(indptr, "iu", np.int64)
        self.indices = _keep_width(indices, "iu", np.int64)
        self.data = _keep_width(data, "f", np.float64)
        if copy:
            self.indptr, self.indices, self.data = (
                a.copy() for a in (self.indptr, self.indices, self.data))
        if self.indptr.ndim != 1 or len(self.indptr) < 1:
            raise ValueError("CSR indptr must be a non-empty 1-D array")
        nnz = int(self.indptr[-1])
        if nnz != len(self.indices) or nnz != len(self.data):
            raise ValueError(
                f"CSR buffers disagree: indptr ends at {nnz}, "
                f"{len(self.indices)} indices / {len(self.data)} values")
        self.nrow = len(self.indptr) - 1
        self.ncol = int(ncol)
        self.shape: Tuple[int, int] = (self.nrow, self.ncol)

    @property
    def nnz(self) -> int:
        return len(self.data)

    @property
    def nbytes(self) -> int:
        """Host bytes the triplet holds (the sparse footprint the chunked
        densify keeps us near)."""
        return int(self.indptr.nbytes + self.indices.nbytes
                   + self.data.nbytes)

    def __len__(self) -> int:
        return self.nrow

    def rows(self, idx) -> np.ndarray:
        """Dense float64 ``[len(idx), ncol]`` of the selected rows, in
        the given order — CSR rows are O(nnz_row) random access, so the
        bin-mapper sample pass needs no full densify."""
        idx = np.asarray(idx, dtype=np.int64)
        starts = self.indptr[idx].astype(np.int64)
        counts = self.indptr[idx + 1].astype(np.int64) - starts
        out = np.zeros((len(idx), self.ncol), dtype=np.float64)
        total = int(counts.sum())
        if total:
            # element e of the gather = row_start[its row] + its rank
            # within that row, all vectorized
            offs = np.concatenate(([0], np.cumsum(counts)[:-1]))
            take = (np.repeat(starts, counts)
                    + np.arange(total) - np.repeat(offs, counts))
            out[np.repeat(np.arange(len(idx)), counts),
                self.indices[take]] = self.data[take]
        return out

    def iter_dense_chunks(
            self, chunk_rows: Optional[int] = None,
    ) -> Iterator[Tuple[int, np.ndarray]]:
        """Yield ``(row0, dense_chunk)`` pairs covering every row once;
        each chunk is at most ``chunk_rows`` (budget-derived by default)
        rows of dense float64 — the bounded working set that replaces
        the old full-matrix densify."""
        chunk = (csr_chunk_rows(self.ncol) if chunk_rows is None
                 else max(1, int(chunk_rows)))
        for r0 in range(0, self.nrow, chunk):
            r1 = min(self.nrow, r0 + chunk)
            lo = int(self.indptr[r0])
            hi = int(self.indptr[r1])
            block = np.zeros((r1 - r0, self.ncol), dtype=np.float64)
            row_of = np.repeat(np.arange(r1 - r0),
                               np.diff(self.indptr[r0:r1 + 1]))
            block[row_of, self.indices[lo:hi]] = self.data[lo:hi]
            yield r0, block

    def iter_by_column(
            self, max_rows: int, budget_bytes: Optional[int] = None,
    ) -> Iterator[Tuple[int, int, np.ndarray, np.ndarray, np.ndarray]]:
        """Yield ``(row0, row1, colptr, rows, values)`` over runs of rows
        covering every row once: the stored entries of rows ``[row0,
        row1)`` grouped by column, inside a column in row order (a stable
        counting sort: the CSC form of the run).  Column ``j``'s entries
        are ``rows[colptr[j]:colptr[j + 1]]``, counted from ``row0``, and
        the values beside them.

        A run's entries are one slice of the triplet, so no run costs a
        pass over the others; it ends at ``max_rows`` rows or where its
        temporaries (about 32 bytes an entry) reach the chunk budget
        (:data:`CSR_CHUNK_BUDGET_BYTES`), whichever comes first."""
        budget = CSR_CHUNK_BUDGET_BYTES if budget_bytes is None \
            else budget_bytes
        max_entries = max(1, int(budget) // 32)
        max_rows = max(1, int(max_rows))
        r0 = 0
        while r0 < self.nrow:
            # the last row that still fits the entries, at least one row
            r1 = int(np.searchsorted(
                self.indptr, int(self.indptr[r0]) + max_entries,
                side="right")) - 1
            r1 = min(self.nrow, r0 + max_rows, max(r1, r0 + 1))
            lo, hi = int(self.indptr[r0]), int(self.indptr[r1])
            idx = self.indices[lo:hi]
            # numpy's stable sort is a radix sort on 16-bit keys
            order = np.argsort(
                idx.astype(np.uint16) if self.ncol <= 1 << 16 else idx,
                kind="stable")
            row_of = np.repeat(np.arange(r1 - r0, dtype=np.int32),
                               np.diff(self.indptr[r0:r1 + 1]))
            colptr = np.concatenate(([0], np.cumsum(
                np.bincount(idx, minlength=self.ncol))))
            yield r0, r1, colptr, row_of[order], self.data[lo:hi][order]
            r0 = r1

    def __array__(self, dtype=None, copy=None):
        """Full densify, chunk-assembled (compat fallback only)."""
        out = np.zeros(self.shape, dtype=np.float64)
        for r0, block in self.iter_dense_chunks():
            out[r0:r0 + len(block)] = block
        if dtype is not None:
            out = out.astype(dtype, copy=False)
        return out


def from_scipy(mat) -> Optional[CsrMatrix]:
    """A ``scipy.sparse`` matrix or array of any format as a
    :class:`CsrMatrix` over its own buffers (CSR in canonical form is not
    copied; other formats convert through ``tocsr``); None for anything
    that is not ``scipy.sparse``'s, without importing scipy."""
    if not type(mat).__module__.startswith("scipy.sparse"):
        return None
    mat = mat.tocsr()
    if not mat.has_canonical_format:
        # duplicates add up in scipy's reading of a matrix: say so once,
        # on a copy (the caller's matrix stays as it was handed over)
        mat = mat.copy()
        mat.sum_duplicates()
    return CsrMatrix(mat.indptr, mat.indices, mat.data, mat.shape[1],
                     copy=False)
