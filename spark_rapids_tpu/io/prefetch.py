"""Bounded read-ahead over an ordered work list.

Shared by the file scanners (reference: the multithreaded readers'
read-pool pipelining, GpuMultiFileReader.scala:934): submit up to
``window`` items to a thread pool, yield results in ORDER as
``(item, result)`` pairs, and keep the window full as items complete.
Bounding the window caps resident decoded data (a whole-partition submit
would pin every file's result until the consumer drains).
"""
from __future__ import annotations

import concurrent.futures as cf
from collections import deque
from typing import Callable, Iterable, Iterator, Tuple, TypeVar

__all__ = ["prefetched", "coalesce_tables"]


def coalesce_tables(files, read_fn, batch_rows: int):
    """COALESCING reader core shared by the file formats: accumulate small
    files until at least ``batch_rows`` rows are pending, then yield ONE
    concatenated arrow table (reference: the coalescing multi-file readers,
    GpuMultiFileReader.scala:126 — small files stitch into full-size
    batches so each device upload/decode sees real work)."""
    import pyarrow as pa
    pending, pending_rows = [], 0
    for f in files:
        t = read_fn(f)
        pending.append(t)
        pending_rows += t.num_rows
        if pending_rows >= batch_rows:
            yield pa.concat_tables(pending)
            pending, pending_rows = [], 0
    if pending:
        yield pa.concat_tables(pending)

T = TypeVar("T")
R = TypeVar("R")


def prefetched(items: Iterable[T], fn: Callable[[T], R],
               window: int) -> Iterator[Tuple[T, R]]:
    items = list(items)
    if not items:
        return
    window = max(1, window)
    # the read-ahead threads book their spans to the query that asked
    from ..utils.tracing import get_tracer
    fn = get_tracer().bind_query(fn)
    with cf.ThreadPoolExecutor(max_workers=window,
                               thread_name_prefix="srtpu-io-prefetch") \
            as pool:
        pending: deque = deque()  # (item, future): pairing stays exact
        it = iter(items)
        for x in it:
            pending.append((x, pool.submit(fn, x)))
            if len(pending) >= window:
                break
        while pending:
            item, fut = pending.popleft()
            result = fut.result()
            nxt = next(it, None)
            if nxt is not None:
                pending.append((nxt, pool.submit(fn, nxt)))
            yield item, result
