"""Per-page checksum ledger: silent-corruption detection for the store.

The serving stack already catches corruption that *announces itself* —
``validate_ids`` rejects out-of-bounds indices before they index anything,
and ``scrub_scores`` zeroes non-finite outputs — but a bit flip that yields
a finite wrong embedding sails through both.  This module closes that gap
with an end-to-end integrity invariant:

  * every page of the live store (int8 codes + its carried fp32 scale, or
    fp32 values) has a host-side checksum computed over its *native-domain*
    bits — the exact bytes resident in the current tier;
  * the ledger is updated incrementally on every legitimate mutation path
    (``apply_deltas`` chunks, replan migrations, ``requant_hot_pages``
    snaps, requant-demotes, elastic re-meshes), so at any quiescent point
    ``ledger == recompute(store)`` holds bit-for-bit;
  * anything that mutates a page *without* going through a mutation path —
    a cosmic-ray flip, a bad DMA, a buggy kernel — breaks the invariant
    and is caught by the scrub sweep (``serving/scrub.py``).

Checksum definition (shared by the jitted device reduction in
``PIFSEmbeddingEngine.page_checksums`` and the numpy twin here): a
Fletcher-style pair in uint32 wraparound arithmetic over the page's lane
stream.  Lanes are the page's rows reinterpreted as unsigned integers
(int8 codes -> uint8 -> uint32; fp32 values -> their IEEE-754 bit patterns
as uint32) followed by the page scale's fp32 bit pattern:

    s1 = (sum_i lane_i            + scale_bits)           mod 2^32
    s2 = (sum_i lane_i * (i + 1)  + scale_bits * (N + 1)) mod 2^32

with ``N = page_size * store_dim`` lanes, stored host-side as the uint64
``(s2 << 32) | s1``.  The position-weighted ``s2`` term makes swapped or
shifted rows detectable, not just changed sums.  All arithmetic is exact
integer wraparound, so the numpy fold is *guaranteed* bit-identical to the
device reduction — no float-order caveats — which is what lets page repair
verify a snapshot page read on the host against the ledger recorded at
snapshot time.

Tier semantics: a page's checksum covers its current-tier content.  Moves
that carry content verbatim (cold page to another cold slot/shard, hot
page to another hot slot, any page across an elastic re-mesh without a
tier change) leave the checksum untouched — that is why the ledger
survives a re-mesh verbatim (page geometry is shard-count-invariant).
Tier *flips* change the native-domain content deterministically
(promote = dequantize with the carried scale, demote = requantize with
it), so flipped pages are recomputed at the flip site.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.paging import HOT_SHARD


def page_checksum_host(rows: np.ndarray, scale: float) -> int:
    """Numpy twin of the device per-page checksum (bit-identical).

    ``rows``: the page's (page_size, store_dim) stored content, native dtype
    (int8 codes or float32 values); ``scale``: the page's carried fp32
    scale.  Returns the uint64 ``(s2 << 32) | s1`` as a Python int.
    """
    rows = np.ascontiguousarray(rows)
    if rows.dtype == np.int8:
        lanes = rows.view(np.uint8).astype(np.uint32).ravel()
    elif rows.dtype == np.float32:
        lanes = rows.view(np.uint32).ravel()
    else:
        raise TypeError(f"unsupported page dtype {rows.dtype}: the store "
                        "holds int8 codes or fp32 values")
    sc = int(np.asarray(scale, np.float32).view(np.uint32))
    n = int(lanes.size)
    w = np.arange(1, n + 1, dtype=np.uint32)
    # fold in python-int space mod 2^32: numpy uint32 sums already wrap,
    # the final adds must too (a numpy-scalar add would warn on overflow)
    s1 = (int(lanes.sum(dtype=np.uint32)) + sc) % (1 << 32)
    s2 = (int((lanes * w).sum(dtype=np.uint32)) + sc * (n + 1)) % (1 << 32)
    return (s2 << 32) | s1


class PageChecksumLedger:
    """Host-side per-page checksum ledger over a live EngineState.

    The ledger holds one uint64 per global page id.  Callers notify it on
    every mutation path (``note_rows`` after delta application,
    ``note_pages`` after requant snaps, ``note_tier_changes`` after any
    placement change that may flip tiers); ``verify`` recomputes a window
    of pages on device and returns the ids whose live checksum diverges
    from the ledger — silent corruption, by construction, since every
    legitimate mutation updated the ledger.

    All device recomputation goes through one fixed window size
    (``chunk``, -1-padded), so the engine sees exactly one checksum plan
    signature and steady-state scrubbing causes zero retraces.
    """

    def __init__(self, engine, chunk: int = 64):
        self.engine = engine
        self.chunk = int(chunk)
        self.checksums = np.zeros(engine.cfg.num_pages, np.uint64)

    @classmethod
    def build(cls, engine, state, chunk: int = 64) -> "PageChecksumLedger":
        """Ledger for ``state`` with every page's checksum populated."""
        ledger = cls(engine, chunk=chunk)
        ledger.note_pages(state,
                          np.arange(engine.cfg.num_pages, dtype=np.int64))
        return ledger

    # -------------------------------------------------------------- device
    def compute(self, state, pages) -> np.ndarray:
        """Recompute checksums for ``pages`` on device -> uint64 array.

        Chunks through the single fixed-``chunk`` plan signature; pad
        entries (-1) contribute zeros and are sliced off.
        """
        pages = np.asarray(pages, np.int32).ravel()
        out = np.zeros(pages.size, np.uint64)
        for i in range(0, pages.size, self.chunk):
            win = pages[i:i + self.chunk]
            pad = np.full(self.chunk, -1, np.int32)
            pad[:win.size] = win
            cs = np.asarray(self.engine.page_checksums(state,
                                                       jnp.asarray(pad)))
            s1 = cs[:win.size, 0].astype(np.uint64)
            s2 = cs[:win.size, 1].astype(np.uint64)
            out[i:i + win.size] = (s2 << np.uint64(32)) | s1
        return out

    def warmup(self, state) -> None:
        """Compile the checksum plan outside the timed path (an all-pad
        window: reads nothing, returns zeros, state untouched)."""
        pad = jnp.asarray(np.full(self.chunk, -1, np.int32))
        np.asarray(self.engine.page_checksums(state, pad))

    # --------------------------------------------------------- maintenance
    def note_pages(self, state, pages) -> None:
        """Re-record the listed pages' checksums from the live state."""
        pages = np.asarray(pages, np.int64).ravel()
        pages = pages[pages >= 0]
        if pages.size == 0:
            return
        self.checksums[pages] = self.compute(state, pages)

    def note_rows(self, state, rows) -> np.ndarray:
        """Re-record the checksums of every page touching ``rows``
        (global row ids; pads < 0 ignored).  Returns the touched pages."""
        rows = np.asarray(rows, np.int64).ravel()
        rows = rows[rows >= 0]
        if rows.size == 0:
            return rows
        pages = np.unique(rows // self.engine.cfg.page_size)
        self.note_pages(state, pages)
        return pages

    def note_tier_changes(self, state, old_p2s, new_p2s) -> np.ndarray:
        """Re-record pages whose tier flipped between two placements.

        Content moves verbatim unless the tier changed (promote/demote
        transform through the carried scale), so only flipped pages need
        recomputation — a pure slot/shard move keeps its checksum.
        Returns the flipped page ids.
        """
        old_hot = np.asarray(old_p2s) == HOT_SHARD
        new_hot = np.asarray(new_p2s) == HOT_SHARD
        flipped = np.nonzero(old_hot != new_hot)[0]
        if flipped.size:
            self.note_pages(state, flipped)
        return flipped

    def rebind(self, engine) -> None:
        """Point the ledger at a re-meshed engine.  Page geometry is
        shard-count-invariant, so the recorded checksums carry verbatim;
        the caller recomputes any tier-flipped pages via
        :meth:`note_tier_changes`."""
        if int(engine.cfg.num_pages) != self.checksums.size:
            raise ValueError(
                f"cannot rebind ledger across a page-geometry change: "
                f"{self.checksums.size} pages recorded, new engine has "
                f"{engine.cfg.num_pages}")
        self.engine = engine

    # ------------------------------------------------------------ auditing
    def verify(self, state, pages=None) -> np.ndarray:
        """Recompute ``pages`` (default: all) and return the ids whose
        live checksum diverges from the ledger."""
        if pages is None:
            pages = np.arange(self.engine.cfg.num_pages, dtype=np.int64)
        pages = np.asarray(pages, np.int64).ravel()
        pages = pages[pages >= 0]
        if pages.size == 0:
            return pages
        live = self.compute(state, pages)
        return pages[live != self.checksums[pages]]

    # -------------------------------------------------------- serialization
    def export(self) -> dict:
        """JSON-serializable form (snapshot manifest ``extra`` payload)."""
        return {"version": 1, "chunk": self.chunk,
                "checksums": [f"{int(c):016x}" for c in self.checksums]}

    def load(self, data: dict) -> None:
        """Adopt an exported ledger (snapshot-restore path)."""
        recorded = data["checksums"]
        if len(recorded) != self.checksums.size:
            raise ValueError(
                f"ledger size mismatch: {len(recorded)} recorded pages vs "
                f"{self.checksums.size} in this engine")
        self.checksums = np.array([int(c, 16) for c in recorded],
                                  dtype=np.uint64)


def fetch_snapshot_page(checkpointer, cfg, page: int,
                        step: Optional[int] = None) -> dict:
    """Read ONE page's rows (and metadata) out of a committed snapshot
    without materializing any full store leaf.

    Uses the checkpointer's partial-read API: the small page tables and
    scales load whole (CRC-checked), the big store leaf is sliced through
    a memory map.  Returns ``{page, tier, shard, slot, rows, scale,
    checksum}`` where ``checksum`` is the snapshot-time ledger entry for
    the page (None on pre-ledger snapshots) — repair verifies the read
    rows against it via :func:`page_checksum_host` before trusting them.
    """
    step = checkpointer.latest_step() if step is None else step
    if step is None:
        raise FileNotFoundError("no committed snapshot to read a page from")
    p2s = checkpointer.read_leaf("page_to_shard", step=step)
    p2slot = checkpointer.read_leaf("page_to_slot", step=step)
    scales = checkpointer.read_leaf("page_scales", step=step)
    shard, slot = int(p2s[page]), int(p2slot[page])
    ps = cfg.page_size
    if shard == HOT_SHARD:
        tier = "hot"
        rows = checkpointer.read_page("hot", slot * ps, ps, step=step)
    else:
        tier = "cold"
        rows = checkpointer.read_page(
            "cold", shard * cfg.rows_per_shard + slot * ps, ps, step=step)
    rec = checkpointer.extra(step).get("page_checksums")
    checksum = (int(rec["checksums"][page], 16)
                if rec and rec.get("checksums") else None)
    return {"page": int(page), "tier": tier, "shard": shard, "slot": slot,
            "rows": rows, "scale": float(scales[page]), "checksum": checksum}
