"""Paged KV cache (vLLM-style) with load-aware run coalescing.

The KV pool is a big array of fixed-size pages ``[num_pages, page_tokens,
kv_features]``; each sequence owns a page list. Two RDMAbox ideas live
here:

* ``plan_page_runs`` — the merge-queue adjacency rule at the memory tier:
  a sequence's page list is turned into maximal *contiguous* runs, so the
  gather (or the remote fetch, or the Pallas kernel's DMA pipeline) issues
  one descriptor per run instead of one per page. Allocation POLICY makes
  runs likely: the allocator hands out the lowest-numbered contiguous
  free span it can find (best-effort), exactly like the paging system's
  striped placement makes sequential swap-outs mergeable.

* spill/fetch through the RDMABox engine — pages evicted from the (HBM)
  pool go to the remote memory cluster via coalesced writes, and come back
  via coalesced reads. The admission window paces the spill traffic.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import trace
from .._deprecation import warn_once
from ..core.descriptors import PAGE_SIZE
from ..core.rdmabox import RDMABox


@dataclass
class PageRun:
    start: int
    length: int

    @property
    def stop(self) -> int:
        return self.start + self.length


def plan_page_runs(page_ids: Sequence[int]) -> List[PageRun]:
    """Maximal contiguous runs of a page list, preserving order.

    This is exactly `core.descriptors.contiguous_runs` specialized to page
    indices: adjacent ⇒ one descriptor.
    """
    runs: List[PageRun] = []
    for pid in page_ids:
        if runs and pid == runs[-1].stop:
            runs[-1].length += 1
        else:
            runs.append(PageRun(int(pid), 1))
    return runs


class PageAllocator:
    """Contiguity-seeking free-list allocator.

    ``alloc(n)`` prefers the lowest contiguous free span ≥ n; falls back to
    scattered pages when fragmented. Frees coalesce back into spans.
    """

    def __init__(self, num_pages: int) -> None:
        self.num_pages = num_pages
        self._free = np.ones(num_pages, dtype=bool)
        self.free_count = num_pages

    def alloc(self, n: int = 1) -> List[int]:
        if n > self.free_count:
            raise MemoryError(f"KV pool exhausted: want {n}, free {self.free_count}")
        free_idx = np.flatnonzero(self._free)
        # find lowest contiguous span of length >= n
        out: List[int] = []
        if len(free_idx) >= n:
            breaks = np.where(np.diff(free_idx) != 1)[0]
            starts = np.concatenate([[0], breaks + 1])
            ends = np.concatenate([breaks, [len(free_idx) - 1]])
            for s, e in zip(starts, ends):
                if e - s + 1 >= n:
                    out = free_idx[s : s + n].tolist()
                    break
        if not out:  # fragmented: take lowest n free pages
            out = free_idx[:n].tolist()
        self._free[out] = False
        self.free_count -= n
        return out

    def free(self, pages: Sequence[int]) -> None:
        pages = list(pages)
        assert not self._free[pages].any(), "double free"
        self._free[pages] = True
        self.free_count += len(pages)

    def fragmentation(self) -> float:
        """1 − (largest free span / total free)."""
        free_idx = np.flatnonzero(self._free)
        if len(free_idx) == 0:
            return 0.0
        spans = np.split(free_idx, np.where(np.diff(free_idx) != 1)[0] + 1)
        return 1.0 - max(len(s) for s in spans) / len(free_idx)


class PagedKVCache:
    """Host-side paged KV pool with optional remote spill tier."""

    def __init__(self, num_pages: int, page_tokens: int, kv_features: int,
                 dtype=np.float32, box: Optional[RDMABox] = None,
                 remote_base_page: int = 0) -> None:
        if not getattr(self, "_box_internal", False):
            warn_once(
                "PagedKVCache",
                "constructing PagedKVCache directly is deprecated; use "
                "repro.box.open(spec).kv_store(...)")
        self.page_tokens = page_tokens
        self.kv_features = kv_features
        self.dtype = np.dtype(dtype)
        self.pool = np.zeros((num_pages, page_tokens, kv_features), dtype)
        self.alloc = PageAllocator(num_pages)
        self.tables: Dict[int, List[int]] = {}      # seq id → page list
        self.lengths: Dict[int, int] = {}           # seq id → tokens used
        self.box = box
        self.remote_base = remote_base_page
        self._page_bytes = page_tokens * kv_features * self.dtype.itemsize
        self._rdma_pages = max(1, -(-self._page_bytes // PAGE_SIZE))
        self._spilled: Dict[Tuple[int, int], int] = {}  # (seq, pos) → remote page
        self._remote_next = remote_base_page                # bump allocator
        self._remote_free: List[int] = []
        self._lock = threading.Lock()   # guards alloc/tables/remote maps
        # stats; spill and fetch bytes are what crosses the fabric, each
        # page padded to whole RDMA pages
        self.gather_descriptors = 0
        self.gather_pages = 0
        self.rows_appended = 0
        self.pages_spilled = 0
        self.bytes_spilled = 0
        self.pages_fetched = 0
        self.bytes_fetched = 0

    # ---- sequence lifecycle -------------------------------------------------
    def add_sequence(self, seq_id: int, num_tokens: int = 0) -> None:
        assert seq_id not in self.tables
        n = -(-num_tokens // self.page_tokens) if num_tokens else 0
        with self._lock:
            self.tables[seq_id] = self.alloc.alloc(n) if n else []
        self.lengths[seq_id] = num_tokens

    def append_tokens(self, seq_id: int, kv: np.ndarray) -> None:
        """kv: (T, kv_features) new tokens for the sequence."""
        with trace.span("kv.append") as span:
            t = self.lengths[seq_id]
            need = (-(-(t + len(kv)) // self.page_tokens)
                    - len(self.tables[seq_id]))
            if need > 0:
                with self._lock:
                    self.tables[seq_id].extend(self.alloc.alloc(need))
                span.add("pages", need)
            for row in kv:
                page = self.tables[seq_id][t // self.page_tokens]
                self.pool[page, t % self.page_tokens] = row
                t += 1
            self.lengths[seq_id] = t
            with self._lock:
                self.rows_appended += len(kv)
            span.add("rows", len(kv))

    def free_sequence(self, seq_id: int) -> None:
        self.alloc.free(self.tables.pop(seq_id))
        self.lengths.pop(seq_id)

    # ---- coalesced gather (the paper's technique, local form) ---------------
    def gather(self, seq_id: int) -> np.ndarray:
        """Materialize a sequence's KV as (tokens, kv_features).

        One slice per contiguous *run*, not per page — load-aware batching
        applied to the gather. Stats record the descriptor reduction.
        """
        pages = self.tables[seq_id]
        runs = plan_page_runs(pages)
        self.gather_descriptors += len(runs)
        self.gather_pages += len(pages)
        parts = [self.pool[r.start : r.stop].reshape(-1, self.kv_features)
                 for r in runs]
        out = np.concatenate(parts, axis=0) if parts else np.zeros(
            (0, self.kv_features), self.dtype)
        return out[: self.lengths[seq_id]]

    # ---- remote spill tier ---------------------------------------------------
    def spill_sequence(self, seq_id: int, donor: int) -> None:
        """Evict a sequence's pages to the remote pool (coalesced writes)."""
        assert self.box is not None, "no RDMA box attached"
        with trace.span("kv.spill") as span:
            pages = self.tables[seq_id]
            nbytes = len(pages) * self._rdma_pages * PAGE_SIZE
            # reserve ONE contiguous remote range per sequence: sequential
            # spill writes stay adjacent ⇒ the merge queue coalesces them
            # (and the fetch path reads back whole runs). Interleaving a
            # shared bump pointer across threads would destroy exactly the
            # adjacency the engine exploits.
            with self._lock:
                base_remote = self._remote_next
                self._remote_next += len(pages) * self._rdma_pages
            pairs = []
            for pos, page in enumerate(pages):
                remote = base_remote + pos * self._rdma_pages
                data = np.ascontiguousarray(
                    self.pool[page]).view(np.uint8).reshape(-1)
                want = self._rdma_pages * PAGE_SIZE
                if data.nbytes < want:                   # pad to page multiple
                    data = np.concatenate(
                        [data, np.zeros(want - data.nbytes, np.uint8)])
                pairs.append((remote, data))
                self._spilled[(seq_id, pos)] = remote
            # the sequence's whole range rides the batch API: one submit-lock
            # acquisition, one future for the spill instead of one per page
            self.box.write_pages(donor, pairs).wait()
            with self._lock:
                self.alloc.free(pages)
                self.pages_spilled += len(pages)
                self.bytes_spilled += nbytes
            self.tables[seq_id] = [-1] * len(pages)   # -1 = remote
            span.add("pages", len(pages))
            span.add("bytes", nbytes)

    def fetch_sequence(self, seq_id: int, donor: int) -> None:
        """Bring a spilled sequence back (coalesced reads)."""
        assert self.box is not None
        with trace.span("kv.fetch") as span:
            n = len(self.tables[seq_id])
            nbytes = n * self._rdma_pages * PAGE_SIZE
            with self._lock:
                local = self.alloc.alloc(n)
            pairs, bufs = [], []
            for pos, page in enumerate(local):
                with self._lock:
                    remote = self._spilled.pop((seq_id, pos))
                    self._remote_free.append(remote)
                buf = np.empty(self._rdma_pages * PAGE_SIZE, np.uint8)
                pairs.append((remote, buf))
                bufs.append((page, buf))
            # one batched read for the sequence: donor-side copies land
            # straight in the per-page buffers, one event for the whole fetch
            self.box.read_pages(donor, pairs).wait()
            for page, buf in bufs:
                flat = buf[: self._page_bytes].view(self.dtype)
                self.pool[page] = flat.reshape(self.page_tokens,
                                               self.kv_features)
            self.tables[seq_id] = local
            with self._lock:
                self.pages_fetched += n
                self.bytes_fetched += nbytes
            span.add("pages", n)
            span.add("bytes", nbytes)
