"""Parquet scan (reference: GpuParquetScanBase.scala:83 + GpuMultiFileReader).

The reference offers three reader strategies (RapidsConf.scala:721):
- PERFILE: one reader per file
- COALESCING: stitch row groups of many small files, single device decode
  (MultiFileParquetPartitionReader, GpuParquetScanBase.scala:995)
- MULTITHREADED: background read+decode pipelining for cloud storage
  (MultiFileCloudParquetPartitionReader, :1194; pool :934)

Here decode runs host-side via pyarrow (the "host-decode then upload" stopgap
called out in SURVEY §7.5) with the same three scheduling strategies:
COALESCING merges small files into one batch per target size; MULTITHREADED
prefetches files on a thread pool. Predicate pushdown uses parquet row-group
statistics via pyarrow filters.
"""
from __future__ import annotations

import concurrent.futures as cf
import glob as _glob
import math
import os
from typing import Iterator, List, Optional, Sequence

import pyarrow as pa
import pyarrow.dataset as pads
import pyarrow.parquet as pq

from ..conf import MULTITHREAD_READ_NUM_THREADS, PARQUET_READER_TYPE, RapidsConf
from ..columnar.host import HostTable
from ..plan.logical import DataSource
from ..plan.schema import Field, Schema
from ..utils.tracing import get_tracer
from .memory import InMemorySource  # noqa: F401 (re-export convenience)

__all__ = ["ParquetSource"]


def _expand_paths(paths) -> List[str]:
    if isinstance(paths, (str, os.PathLike)):
        paths = [paths]
    out: List[str] = []
    for p in paths:
        p = os.fspath(p)
        if os.path.isdir(p):
            out.extend(sorted(_glob.glob(os.path.join(p, "**", "*.parquet"),
                                         recursive=True)))
        elif any(ch in p for ch in "*?["):
            out.extend(sorted(_glob.glob(p)))
        else:
            out.append(p)
    if not out:
        raise FileNotFoundError(f"no parquet files for {paths}")
    return out


def _host_table(t: pa.Table) -> HostTable:
    """Arrow -> host columns for one batch of the host scan: the host
    path's share of ``scan.parse``."""
    with get_tracer().span("scan.parse", "scan", step="from_arrow",
                           rows=t.num_rows):
        return HostTable.from_arrow(t)


class ParquetSource(DataSource):
    def __init__(self, paths, conf: Optional[RapidsConf] = None,
                 num_partitions: Optional[int] = None,
                 batch_rows: Optional[int] = None,
                 filter_expr=None):
        self.files = _expand_paths(paths)
        self.conf = conf or RapidsConf()
        self.reader_type = str(self.conf.get(PARQUET_READER_TYPE)).upper()
        from ..conf import READER_BATCH_SIZE_ROWS
        self.batch_rows = batch_rows if batch_rows is not None \
            else self.conf.get(READER_BATCH_SIZE_ROWS)
        self.filter_expr = filter_expr  # pyarrow dataset filter (pushdown)
        first = pq.read_schema(self.files[0])
        ht = HostTable.from_arrow(first.empty_table())
        self._schema = Schema([Field(n, c.dtype, True)
                               for n, c in zip(ht.names, ht.columns)])
        nparts = num_partitions or min(len(self.files), 8)
        per = math.ceil(len(self.files) / nparts)
        self._file_parts = [self.files[i * per:(i + 1) * per]
                            for i in range(nparts)
                            if self.files[i * per:(i + 1) * per]]

    def schema(self) -> Schema:
        return self._schema

    def push_filter(self, arrow_expr) -> None:
        """Planner pushdown hook (io/pushdown.py): AND into any existing
        filter; row groups whose statistics exclude the predicate are
        skipped (reference: GpuParquetScanBase filter pushdown)."""
        self.filter_expr = arrow_expr if self.filter_expr is None \
            else (self.filter_expr & arrow_expr)

    def partitions(self) -> int:
        return len(self._file_parts)

    def read_partition(self, pidx: int, columns: Optional[List[str]] = None
                       ) -> Iterator[HostTable]:
        files = self._file_parts[pidx]
        if self.reader_type == "MULTITHREADED":
            yield from self._read_multithreaded(files, columns)
        elif self.reader_type == "PERFILE":
            for f in files:
                for t in self._read_file_batches(f, columns):
                    yield t
        else:  # COALESCING (also AUTO)
            yield from self._read_coalescing(files, columns)

    # -- strategies ----------------------------------------------------------
    def _read_file(self, path: str, columns) -> pa.Table:
        """One file's rows as an arrow table — the funnel of all three
        reader strategies, and the host scan's ``scan.read`` span."""
        with get_tracer().span("scan.read", "scan") as span:
            if self.filter_expr is not None:
                ds = pads.dataset(path, format="parquet")
                t = ds.to_table(columns=columns, filter=self.filter_expr)
            else:
                t = pq.read_table(path, columns=columns, use_threads=True)
            span.note(bytes=t.nbytes, rows=t.num_rows)
        return t

    def _read_file_batches(self, path: str, columns) -> Iterator[HostTable]:
        from .file_block import set_input_file
        t = self._read_file(path, columns)
        set_input_file(path, 0, os.path.getsize(path))
        pos = 0
        while pos < t.num_rows:
            yield _host_table(t.slice(pos, self.batch_rows))
            pos += self.batch_rows
        if t.num_rows == 0:
            yield _host_table(t)

    def _read_coalescing(self, files: Sequence[str], columns
                         ) -> Iterator[HostTable]:
        # merged batches span files: no single-file attribution (the
        # InputFileBlockRule analogue selects PERFILE when file-info
        # expressions appear, exactly like the reference's readers)
        from .file_block import clear_input_file
        clear_input_file()
        pending: List[pa.Table] = []
        pending_rows = 0
        for f in files:
            t = self._read_file(f, columns)
            pending.append(t)
            pending_rows += t.num_rows
            if pending_rows >= self.batch_rows:
                merged = pa.concat_tables(pending)
                yield from self._slice_out(merged)
                pending, pending_rows = [], 0
        if pending:
            merged = pa.concat_tables(pending)
            yield from self._slice_out(merged, allow_empty=True)

    def _slice_out(self, t: pa.Table, allow_empty: bool = False
                   ) -> Iterator[HostTable]:
        if t.num_rows == 0 and allow_empty:
            yield _host_table(t)
            return
        pos = 0
        while pos < t.num_rows:
            yield _host_table(t.slice(pos, self.batch_rows))
            pos += self.batch_rows

    def _read_multithreaded(self, files: Sequence[str], columns
                            ) -> Iterator[HostTable]:
        nthreads = self.conf.get(MULTITHREAD_READ_NUM_THREADS)
        with cf.ThreadPoolExecutor(max_workers=nthreads,
                                   thread_name_prefix="srtpu-pq-read") \
                as pool:
            from .file_block import set_input_file
            read = get_tracer().bind_query(self._read_file)
            futures = [pool.submit(read, f, columns) for f in files]
            for f, fut in zip(files, futures):  # file order kept, reads overlap
                t = fut.result()
                set_input_file(f, 0, os.path.getsize(f))
                yield from self._slice_out(t, allow_empty=True)

    def estimated_size_bytes(self):
        return sum(os.path.getsize(f) for f in self.files)

    def name(self) -> str:
        return f"Parquet[{len(self.files)} files, {self.reader_type}]"
