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

Host bookkeeping only; the engine (serve/lm_engine.py) owns the device array
and replaces `rows` after every step. One thread (the server's) calls in.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Optional

from mine_tpu import telemetry

LANES = 128


class Document:
    """A resident document: `pages` hold its first `len(pages) * page_size`
    tokens; `ready` once they are all written; `readers` requests hold it."""

    __slots__ = ("doc_id", "pages", "tokens", "ready", "readers")

    def __init__(self, doc_id, pages: List[int], tokens: int):
        self.doc_id, self.pages, self.tokens = doc_id, pages, tokens
        self.ready, self.readers = False, 0


class LatentCache:
    def __init__(self, layers: int, tokens: int, page_size: int, width: int,
                 dtype="bfloat16"):
        import jax.numpy as jnp
        if tokens % page_size:
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

    # ---- pages ----

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_used(self) -> int:
        return self.num_pages - 1 - len(self._free)

    @property
    def nbytes(self) -> int:
        return int(self.rows.size) * self.dtype.itemsize

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
        self._gauge.set(self.pages_used)
        return pages

    def release(self, pages: List[int]) -> None:
        self._free.extend(pages)
        self._gauge.set(self.pages_used)

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
            del self.documents[doc_id]
            self.release(doc.pages)
        self._evictions.inc()

    def drop_document(self, doc_id) -> None:
        """A reservation whose request could not be admitted after all."""
        doc = self.documents.pop(doc_id)
        self.release(doc.pages)

    def stats(self) -> Dict[str, int]:
        return {"pages": self.num_pages - 1, "pages_used": self.pages_used,
                "documents": len(self.documents),
                "documents_read": sum(1 for d in self.documents.values()
                                      if d.readers),
                "nbytes": self.nbytes}
