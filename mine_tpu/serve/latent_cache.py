"""The token model's cache: pages of latent rows on the device, and the host's
book of who holds which page.

Beside `serve/cache.py` (whole images' planes, one entry an image) this is a
cache of another kind: one ROW a token and layer, `kv_lora_rank +
qk_rope_head_dim` values (models/moe_mla.py: [c_kv | k_rope]) padded to a
multiple of the 128 lanes, in pages of `page_size` tokens. The device array
is `rows` [layers, pages * page_size, row_width]; a sequence reads and writes
it through its block table (a list of page ids), position p in row
`table[p // page_size] * page_size + p % page_size`.

  * Page 0 is never handed out: padded rows of a step write there.
  * A DOCUMENT's pages are found by its id and shared, read-only, by every
    request that asks about it. Only whole pages are shared: the tokens past
    a document's last page boundary are written again into each request's
    own pages, with its question and its answer.
  * A request's own pages are freed when it completes.
  * When a new document or request lacks pages, whole documents that no
    request is reading are evicted, least recently used first. A document
    being read (or being written by its first request) is never evicted.

Where the model has them (models/moe_mla.py: full layers under an indexer,
sliding-window layers), the one book keeps three kinds of row:

  * `rows` [full layers, ...]: the full layers' latent rows, as above.
  * `index_rows` [full layers, pages * page_size, index width]: the full
    layers' INDEX KEYS, one a token, kept for the whole context: the same
    pages under the same ids as the latent rows (a page of a block table is a
    page of both), so they live and die with their document or request.
  * `window_rows` [sliding layers, window pages * page_size, row width]: the
    sliding layers' latent rows, in a POOL OF THEIR OWN. Only a sequence's
    last `window` tokens are ever read, so a sequence takes window pages as
    it advances and gives back each page once it lies wholly behind
    `length - window` (`WindowTable`, serve/lm_scheduler.py), and holds a few
    pages where a full layer holds its whole context. A document keeps the
    window pages that cover its last `window - 1` tokens before its last page
    boundary (`Document.window`), shared read-only like its other pages and
    evicted with them: a question on a cached document starts from them.
    `reserve_window` / `window_reserved`: admission sets aside the most a
    sequence can hold at once, so that no running sequence ever finds the
    pool empty; only admission evicts.

Host bookkeeping only; the engine (serve/lm_engine.py) owns the device arrays
and replaces them after every step. One thread (the server's) calls in.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Optional

from mine_tpu import telemetry

LANES = 128


class WindowTable:
    """A sequence's (or a document's) pages of the window pool: `pages[i]`
    holds positions [(first + i) * page_size, (first + i + 1) * page_size);
    those in `shared` are a document's, read-only and never given back."""

    __slots__ = ("first", "pages", "shared")

    def __init__(self, first: int = 0, pages=(), shared=()):
        self.first, self.pages = first, list(pages)
        self.shared = set(shared)

    def owned(self) -> List[int]:
        return [p for p in self.pages if p not in self.shared]

    def drop(self, n: int) -> List[int]:
        """Forget the first `n` pages; -> those of them that were owned."""
        gone = [p for p in self.pages[:n] if p not in self.shared]
        del self.pages[:n]
        self.first += n
        return gone


class Document:
    """A resident document: `pages` hold its first `len(pages) * page_size`
    tokens; `ready` once they are all written; `readers` requests hold it;
    `window` the window pages kept at its end (sliding layers)."""

    __slots__ = ("doc_id", "pages", "tokens", "ready", "readers", "window")

    def __init__(self, doc_id, pages: List[int], tokens: int):
        self.doc_id, self.pages, self.tokens = doc_id, pages, tokens
        self.ready, self.readers = False, 0
        self.window: Optional[WindowTable] = None


class LatentCache:
    def __init__(self, layers: int, tokens: int, page_size: int, width: int,
                 dtype="bfloat16", index_width: int = 0,
                 window_layers: int = 0, window_tokens: int = 0,
                 window_width: int = 0, window: int = 0):
        import jax.numpy as jnp
        if tokens % page_size or window_tokens % page_size:
            raise ValueError("the cache's tokens must be whole pages")
        self.layers, self.page_size, self.width = layers, page_size, width
        self.row_width = -(-width // LANES) * LANES
        self.num_pages = tokens // page_size + 1          # + page 0
        self.dtype = jnp.dtype(dtype)
        self.rows = jnp.zeros((layers, self.num_pages * page_size,
                               self.row_width), self.dtype)
        self._free = list(range(self.num_pages - 1, 0, -1))
        self.documents: "collections.OrderedDict[object, Document]" = (
            collections.OrderedDict())           # least recently used first
        self._gauge = telemetry.gauge("serve.lm.pages_used")
        self._evictions = telemetry.counter("serve.lm.evictions")
        # the full layers' index keys: the same pages, rows of their own
        self.index_rows = None
        if index_width:
            self.index_rows = jnp.zeros(
                (layers, self.num_pages * page_size, index_width), self.dtype)
            self._index_gauge = telemetry.gauge("serve.lm.index_pages_used")
        # the sliding layers' window pages: a pool of their own
        self.window, self.window_layers = int(window), window_layers
        self.window_width = window_width
        self.window_row_width = -(-window_width // LANES) * LANES
        self.window_num_pages = (window_tokens // page_size + 1
                                 if window_layers else 0)
        self.window_rows = None
        self._window_free: List[int] = []
        self.window_reserved = 0     # set aside for admitted sequences
        if window_layers:
            self.window_rows = jnp.zeros(
                (window_layers, self.window_num_pages * page_size,
                 self.window_row_width), self.dtype)
            self._window_free = list(range(self.window_num_pages - 1, 0, -1))
            self._window_gauge = telemetry.gauge("serve.lm.window_pages_used")
            self._window_released = telemetry.counter(
                "serve.lm.window_pages_released")

    # ---- pages ----

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_used(self) -> int:
        return self.num_pages - 1 - len(self._free)

    @property
    def window_pages_used(self) -> int:
        return max(self.window_num_pages - 1, 0) - len(self._window_free)

    def arrays(self):
        """The device arrays a step reads and writes: the latent rows alone
        where there is no other kind, else a dict of the kinds."""
        if self.index_rows is None and self.window_rows is None:
            return self.rows
        out = {"latent": self.rows}
        if self.index_rows is not None:
            out["index"] = self.index_rows
        if self.window_rows is not None:
            out["window"] = self.window_rows
        return out

    def set_arrays(self, arrays) -> None:
        """What a step returned (as `arrays` gave them); None releases all."""
        if not isinstance(arrays, dict):
            arrays = {"latent": arrays}
        self.rows = arrays.get("latent")
        self.index_rows = arrays.get("index")
        self.window_rows = arrays.get("window")

    @property
    def nbytes(self) -> int:
        held = (self.rows, self.index_rows, self.window_rows)
        return sum(int(a.size) for a in held
                   if a is not None) * self.dtype.itemsize

    def pages_for(self, tokens: int) -> int:
        return -(-tokens // self.page_size)

    def allocate(self, n: int) -> Optional[List[int]]:
        """`n` pages, evicting idle documents as needed; None (and nothing
        changed) where even that does not free enough."""
        if n > len(self._free) + sum(len(d.pages)
                                     for d in self._idle_documents()):
            return None
        while n > len(self._free):
            self.evict(self._idle_documents()[0].doc_id)
        pages = [self._free.pop() for _ in range(n)]
        self._set_gauges()
        return pages

    def release(self, pages: List[int]) -> None:
        self._free.extend(pages)
        self._set_gauges()

    def _set_gauges(self) -> None:
        self._gauge.set(self.pages_used)
        if self.index_rows is not None:
            self._index_gauge.set(self.pages_used)

    # ---- the window pool ----

    def reserve_window(self, n: int) -> bool:
        """Set `n` window pages aside for a sequence that is being admitted,
        evicting idle documents as needed; False (and nothing changed) where
        the pool cannot promise them."""
        idle = [d for d in self._idle_documents() if d.window is not None]
        spare = len(self._window_free) - self.window_reserved
        if n > spare + sum(len(d.window.pages) for d in idle):
            return False
        while n > len(self._window_free) - self.window_reserved:
            self.evict(idle.pop(0).doc_id)
        self.window_reserved += n
        return True

    def unreserve_window(self, n: int) -> None:
        self.window_reserved -= n

    def take_window(self, n: int) -> List[int]:
        """`n` pages out of what `reserve_window` set aside."""
        if n > self.window_reserved or n > len(self._window_free):
            raise RuntimeError("the window pool was not reserved for %d "
                               "pages" % n)
        self.window_reserved -= n
        pages = [self._window_free.pop() for _ in range(n)]
        self._window_gauge.set(self.window_pages_used)
        return pages

    def give_window(self, pages: List[int], reserve: bool) -> None:
        """Pages back to the pool: `reserve` keeps them set aside for the
        sequence that gives them (it runs on), else they are anyone's."""
        self._window_free.extend(pages)
        if reserve:
            self.window_reserved += len(pages)
            self._window_released.inc(len(pages))
        self._window_gauge.set(self.window_pages_used)

    # ---- documents ----

    def _idle_documents(self) -> List[Document]:
        return [d for d in self.documents.values() if d.readers == 0]

    def lookup(self, doc_id) -> Optional[Document]:
        """The resident document, marked most recently used; None."""
        doc = self.documents.get(doc_id)
        if doc is not None:
            self.documents.move_to_end(doc_id)
        return doc

    def reserve_document(self, doc_id, tokens: int) -> Optional[Document]:
        """Pages for a new document's whole pages (not yet `ready`); None
        where they cannot be had."""
        pages = self.allocate(tokens // self.page_size)
        if pages is None:
            return None
        doc = self.documents[doc_id] = Document(
            doc_id, pages, len(pages) * self.page_size)
        return doc

    def evict(self, doc_id) -> None:
        doc = self.documents[doc_id]
        if doc.readers:
            raise RuntimeError("document %r is being read" % (doc_id,))
        with telemetry.span("serve.lm.evict", pages=len(doc.pages)):
            self.drop_document(doc_id)
        self._evictions.inc()

    def drop_document(self, doc_id) -> None:
        """A reservation whose request could not be admitted after all (or
        an eviction's pages: every kind the document holds)."""
        doc = self.documents.pop(doc_id)
        self.release(doc.pages)
        if doc.window is not None:
            self.give_window(doc.window.pages, reserve=False)

    def stats(self) -> Dict[str, int]:
        return {"pages": self.num_pages - 1, "pages_used": self.pages_used,
                "window_pages": max(self.window_num_pages - 1, 0),
                "window_pages_used": self.window_pages_used,
                "documents": len(self.documents),
                "documents_read": sum(1 for d in self.documents.values()
                                      if d.readers),
                "nbytes": self.nbytes}
