"""Paged-KV configuration (the ``serving.paging`` sub-block).

Stdlib-only (same contract as ``serving/config.py``): ``runtime/config.py``
reaches this dataclass through ``ServingConfig``, and that import path must
stay jax-free for the dependency-free tooling jobs (ds_tpu_lint in CI).

Reference frame: vLLM-style block paging applied under the TPU
compile-once discipline — pages are fixed-size, the page table is a dense
``[num_slots, max_pages]`` int32 array, and every paged program keeps
static shapes so decode still compiles exactly once (see
docs/serving.md, "Paged KV cache").
"""

from dataclasses import dataclass
from typing import Optional

# widths, in pages, of the one prefill chunk program an iteration when
# ``prefill_chunk`` is None, widest first: one constant for every model,
# each compiled by ``InferenceEngine.serve()`` before the first request.
# A width costs every process a trace, a lowering and a read from the
# compile cache (0.6 s for a scanned 1.3B GPT-2, 2.2 s for LFM2's ten
# unscanned layers: PERF.md section 6, PR 35), which a width of 2 pages
# between these does not earn back
CHUNK_PAGES = (4, 1)


def chunk_pages(waiting: int, decoding: int, pages_left: int,
                degraded: bool = False) -> int:
    """Pages the next prefill chunk takes when the width is the server's
    to choose: the widest of ``CHUNK_PAGES`` that the head request's
    ``pages_left`` fill (its last, partly filled page counts as one),
    and wider than one page only while the requests admitted and
    ``waiting`` for prefill outnumber the rows that are ``decoding`` and
    the QoS ladder is not ``degraded`` (the level at which it shrinks
    the chunk budget). A wide chunk reads the weights once for all its
    pages, so it is how a queue of long prompts drains; one page is what
    a decoding row waits for. Host scheduler counts on the iteration
    clock only, so the choice replays bit-exactly."""
    if degraded or waiting <= decoding:
        return 1
    return next(w for w in CHUNK_PAGES if w <= pages_left)


@dataclass
class PagingConfig:
    """Block-paged KV cache knobs.

    The pool holds ``num_pages`` pages of ``page_len`` tokens each (K^T
    layout, one pool per attention unit). Page 0 is reserved as the null
    page: unowned page-table entries point at it and masked/inactive
    writes land there, so scatters never need a branch.
    """
    page_len: int = 128              # tokens per page (128 = the Pallas
                                     # tiling quantum; smaller only for
                                     # CPU-backend tests)
    num_pages: Optional[int] = None  # pool size INCLUDING the null page;
                                     # None = num_slots * (cache_len /
                                     # page_len) + 1 (every slot can hold
                                     # a full-length request at once)
    enable_prefix_cache: bool = True  # radix-tree sharing of full prompt-
                                      # prefix pages (system prompts)
    prefill_chunk: Optional[int] = None  # tokens of one prefill chunk
                                     # program (a page_len multiple).
                                     # None = chosen per dispatch
                                     # (``chunk_pages``): one page while
                                     # rows decode, CHUNK_PAGES[0] pages
                                     # while the requests waiting for
                                     # prefill outnumber them. A
                                     # number fixes the width (the tail
                                     # is cut to the pages left).
    max_chunks_per_iter: int = 1     # prefill chunk PROGRAMS run between
                                     # two decode dispatches, whatever
                                     # their width (1 = a decoding row
                                     # never waits for more than one)
    state_snapshots: Optional[int] = None  # entries of the snapshot pool
                                     # a model with a state-space mixer
                                     # keeps its page-end states in
                                     # (paging/snapshots.py; one entry is
                                     # a sequence's whole state in every
                                     # layer). None = half the slots. Read
                                     # by no other model.
    kernel: str = "auto"             # paged decode-attention kernel
                                     # (ops/pallas/paged_attention.py):
                                     # "auto" = on real TPU with a
                                     # 128-aligned page_len (the gather
                                     # fallback elsewhere — CPU runs stay
                                     # bit-identical to the pre-kernel
                                     # engine), "on" = force (tests/
                                     # interpret mode), "off" = always
                                     # gather the contiguous view

    def validate(self, cache_len: int):
        """Validate against the owning ServingConfig's slot capacity."""
        if self.page_len < 1:
            raise ValueError(
                f"serving.paging.page_len must be >= 1, got {self.page_len}")
        if cache_len % self.page_len != 0:
            raise ValueError(
                f"serving.paging.page_len ({self.page_len}) must divide the "
                f"slot capacity cache_len ({cache_len}) so page tables tile "
                "it exactly")
        chunk = self.chunk_tokens
        if chunk < self.page_len or chunk % self.page_len != 0:
            raise ValueError(
                f"serving.paging.prefill_chunk ({chunk}) must be a positive "
                f"multiple of page_len ({self.page_len}) — chunk starts must "
                "stay page-aligned for the page scatter")
        if self.max_chunks_per_iter < 1:
            raise ValueError(
                "serving.paging.max_chunks_per_iter must be >= 1, got "
                f"{self.max_chunks_per_iter}")
        if self.state_snapshots is not None and self.state_snapshots < 1:
            raise ValueError(
                "serving.paging.state_snapshots must be >= 1, got "
                f"{self.state_snapshots}")
        if self.kernel not in ("auto", "on", "off"):
            raise ValueError(
                f"serving.paging.kernel must be 'auto', 'on', or 'off', "
                f"got {self.kernel!r}")
        max_pages = cache_len // self.page_len
        if self.num_pages is not None and self.num_pages < max_pages + 1:
            raise ValueError(
                f"serving.paging.num_pages ({self.num_pages}) cannot hold "
                f"even one full-length request: need >= {max_pages} usable "
                "pages plus the reserved null page")
        return self

    @property
    def chunk_tokens(self) -> int:
        """The prefill chunk size when it is fixed (``prefill_chunk``),
        else the narrowest the server chooses: one page."""
        return (self.prefill_chunk if self.prefill_chunk is not None
                else self.page_len)

    def snapshot_entries(self, num_slots: int) -> int:
        """Usable entries of the snapshot pool (the null one not
        counted)."""
        if self.state_snapshots is not None:
            return self.state_snapshots
        return max(1, num_slots // 2)

    def pool_pages(self, num_slots: int, cache_len: int) -> int:
        """Total pool pages including the reserved null page."""
        if self.num_pages is not None:
            return self.num_pages
        return num_slots * (cache_len // self.page_len) + 1
