"""Host-side table of a snapshot pool: which physical page's END has its
recurrent state stored in which entry (``inference/cache.py``: the layout
of a state unit too large to keep one a page).

A model with a state-space mixer carries a matrix state a sequence that
weighs what 2,048 tokens of its K/V do (Falcon-H1-34B: 4.2 MB a layer),
so the pool holds tens of entries where the page pool holds a thousand
pages, and a prefix hit can start only where a page's end has one. The
rule for which page ends get one (``PagedKVManager`` applies it):

- **the leaf**: the end of a prompt's last whole page, taken by the chunk
  that fills it — what a repeat of the prompt, or the request itself
  resumed after a preemption, matches down to;
- **the branch**: the end of the run a later prompt *matched* in the
  prefix cache, when that page's end has none. Requests that share a
  system prompt part ways at the prefix's last page, which is nobody's
  leaf; the first request that matches down to it finds no state there,
  has its hit shortened to the deepest page that has one (``missed``),
  computes the pages between, and takes the snapshot as it passes —
  every later request hits. A rule of leaves alone would never hit under
  shared system prompts; one of every page end would need a pool the
  size of the page pool (38.7 GB for that model on one chip).

Who frees one: the table, least recently used first, when ``take`` finds
no free entry (an entry pinned by an admitted request whose first chunk
has not read it yet is passed over); and the allocator, when the page
itself goes back to the free list (``PageAllocator.on_free``: the prefix
cache evicted it, or its request was released before publishing it) — a
page id that is handed out again must not name the old tokens' state.
Entry 0 is the null entry: what a chunk writes a page end to when no
snapshot is wanted, never read.
"""

from collections import OrderedDict
from typing import Iterable, Optional


class SnapshotTable:
    """``entries`` usable entries, 1..entries; page -> entry."""

    def __init__(self, entries: int):
        if entries < 1:
            raise ValueError(
                f"a snapshot pool needs at least 1 entry, got {entries}")
        self.entries = entries
        self._free = list(range(entries, 0, -1))
        self._of = OrderedDict()       # page -> entry, least recent first
        self._pins = {}                # page -> admitted readers
        self.taken = 0
        self.evicted = 0

    @property
    def in_use(self) -> int:
        return len(self._of)

    def has(self, page: int) -> bool:
        return page in self._of

    def lookup(self, page: int) -> Optional[int]:
        """The page's entry (now the most recently used), or None."""
        entry = self._of.get(page)
        if entry is not None:
            self._of.move_to_end(page)
        return entry

    def pin(self, page: int):
        """Hold the page's entry until ``unpin``: a request was admitted
        against it and its first chunk has not been dispatched yet."""
        self._pins[page] = self._pins.get(page, 0) + 1

    def unpin(self, page: int):
        left = self._pins[page] - 1
        if left:
            self._pins[page] = left
        else:
            del self._pins[page]

    def take(self, page: int) -> Optional[int]:
        """An entry for the state at ``page``'s end: its own if it has
        one, a free one, or the least recently used unpinned one; None
        when every entry is pinned."""
        entry = self.lookup(page)
        if entry is not None:
            return entry
        if not self._free:
            victim = next((p for p in self._of if p not in self._pins), None)
            if victim is None:
                return None
            self._free.append(self._of.pop(victim))
            self.evicted += 1
        entry = self._free.pop()
        self._of[page] = entry
        self.taken += 1
        return entry

    def drop(self, pages: Iterable[int]):
        """The pages went back to the free list: so do their entries."""
        for page in pages:
            entry = self._of.pop(page, None)
            if entry is not None:
                self._free.append(entry)
                self.evicted += 1
