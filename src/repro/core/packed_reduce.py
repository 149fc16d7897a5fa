"""Bit-packed serial-parallel reduction engine (Dory §4.4 × kernels/gf2).

``reduce_dimension_batched`` (the host serial-parallel engine) spends its
time in per-column Python work: one ``merge_cancel`` sort per GF(2) add and
several one-element adapter probes per reduction (the profile is dominated
by ``cobdy``/``min_cobdy``/``owner_of_low`` calls on ``np.array([x])``
singletons).  This engine keeps the paper's batch structure — parallel
phase against the committed pivots, serial phase for intra-batch
collisions, clearance commit — but holds each batch in *one* bit-packed
block for its whole reduction:

* **rank compression** — per batch, the sorted unique key set of the
  batch's coboundaries plus the first round of gathered addends becomes the
  block's bit-space (``kernels.gf2.scatter_bits``): key ``universe[i]``
  lives at bit ``i``, so ascending keys are ascending ranks, a
  first-set-bit scan (inside the gf2 kernels, ``find_low_np`` on host)
  *is* the engine's ``low``, and one 32-word VREG XOR covers 32,768 matrix
  entries;
* **parallel phase** — one :meth:`PivotStore.lookup_addends_batched` probe
  per round (one ``owner_of_low`` / ``min_cobdy`` / ``cobdy`` call for the
  whole batch), then the hit rows absorb their gathered committed-pivot
  addends by an in-place bit scatter-XOR into the host block, followed by a
  find-low over the hit rows — on every platform: the add is a few thousand
  words, far less than one device round trip costs, and the next probe
  needs the lows on the host anyway.  Only rows whose low moved are probed
  again;
* **segmented growth vs eviction** — an addend with keys outside the
  bit-space either *expands* the space (the new keys append as a fresh
  word-aligned segment; no re-ranking, lows become a min over per-segment
  find-lows) or *evicts* its row to plain sorted-key form (``merge_cancel``
  chains, as in the host engine).  Dense rounds expand — many rows keep
  XOR-ing in block form; sparse rounds (a few deep single-column chains,
  e.g. H1* on a near-clique) evict — one stubborn chain must not balloon
  the whole block's bit-space.  Segments consolidate to one sorted universe
  only past ``_MAX_SEGMENTS`` — or eagerly on the kernel path, where the
  serial kernel needs the single globally-sorted bit-space;
* **serial phase** — intra-batch low collisions resolve in one host walk
  over the batch in filtration order (a ``low -> row`` dict; packed rows
  XOR whole block rows, evicted rows ``merge_cancel``), with gens updated
  per absorption exactly like the host engine.  On the kernel path a
  ``gf2_serial_reduce`` pre-pass first clears the packed-vs-packed
  collisions in VMEM: ``ceil(B/32)`` *V-words* ride at the block's tail,
  reset to the identity before the pass, so afterwards each row's V bits
  name exactly the batch mates it absorbed — the δ-expansion bookkeeping
  recovered by unpacking ``ceil(B/32)`` words instead of per-XOR updates;
* **clearance** — lows unpack back to int64 keys and commit through the
  existing :class:`PivotStore` (budgeted, largest-explicit-first spill), so
  explicit/implicit/budget semantics are shared with the other engines.
  Trivial pairs commit nothing, so their rows are never unpacked at all.

Diagrams are bit-identical to ``reduce_dimension`` for every mode/budget
(asserted in tests): all engines perform left-to-right GF(2) column
additions, and the lows of any fully reduced matrix are canonical.

**Distributed mode** (``n_shards``/``mesh``): column batches partition
round-robin over the shards (batch ``t`` -> shard ``t % P``, the same
dealing :func:`repro.scale.shard.partition_tiles` uses for tiles), and each
*superstep* fuses the P shards' next batches into ONE resident block of
``P·B`` rows — per-device blocks simulated as row slices, which is also
what amortizes the per-batch fixed costs (one coboundary enumeration, one
block build, one store probe per round for all P slices) that bound the
single-device engine.  Phases per superstep:

* **concurrent phase** — the parallel phase of every slice runs against a
  per-device *replica* of the pivot store, complete exactly up to the
  previous superstep (pivots arrive only through the exchange wire — see
  below), with per-slice serial passes for intra-slice collisions;
* **tournament catch-up** — cross-slice collisions resolve in ``log2 P``
  hypercube rounds (partner ``j XOR step``): the later-ranked slice's row
  absorbs the earlier one's current (R, gens) snapshot — later batch
  columns follow earlier ones in processing order, so this matches the
  left-to-right schedule and only removes work;
* **commit sweep** — slices commit strictly in global batch order; each
  slice first re-probes the *authoritative* store (which now holds this
  superstep's earlier-slice pivots) until stable, so the final schedule is
  exactly a left-to-right reduction and diagrams stay bit-identical to the
  single-device engines for every shard count;
* **pivot exchange** — the superstep's non-trivial commits encode into one
  Elias–Fano wire payload per shard (:mod:`repro.core.pivot_cache`),
  cross-ship (``jax.lax.all_gather`` under ``shard_map`` with a mesh; host
  loop-back under ``n_shards``), decode, and install into the replica.  The
  concurrent phase reads pivots *only* from the replica, so the wire codec
  sits on the bit-identity critical path by construction.

The shared :class:`~repro.core.pivot_cache.PackedPivotCache` memoizes each
pivot's packed bit positions per block epoch — one pack serves every slice
of the superstep that consumes the pivot, replacing the per-consuming-batch
re-pack — and each implicit pivot's materialized R keys (1 enumeration per
pivot across the whole reduction).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..analyze.invariants import active_sanitizer
from ..kernels.gf2 import (NO_LOW, find_low_np, scatter_bits,
                           scatter_xor_bits, set_bit_positions,
                           stack_wire_payloads, unstack_wire_payloads)
from ..obs.metrics import MetricsRegistry
from ..obs.trace import Tracer, active_tracer, critical_path, span
from ..resilience.faults import (TransientFault, active_injector,
                                 corrupt_payload, retry_with_backoff)
from ..resilience.supervisor import ShardSupervisor
from .pairing import EMPTY_KEY
from .reduction import (DimensionAdapter, PivotStore, ReductionResult,
                        clearance_commit, clearing_filter, finalize_result,
                        merge_cancel, seed_column)

_MAX_SEGMENTS = 12   # host path consolidates past this many segments
_EVICT_MAX = 8       # rounds needing new keys for fewer rows evict instead


def _resolve_use_kernels(use_kernels: Optional[bool]) -> bool:
    """Pallas kernels on TPU, numpy mirrors elsewhere (repo-wide policy:
    Mosaic only exists on TPU; interpret-mode Pallas is for tests)."""
    if use_kernels is None:
        import jax
        return jax.default_backend() == "tpu"
    return bool(use_kernels)


def _words(n_keys: int, use_kernels: bool) -> int:
    """Segment width in words; bucketed on the kernel path so the jitted
    Pallas calls see a handful of shapes, not one per universe size."""
    w = max(1, (n_keys + 31) // 32)
    return -(-w // 128) * 128 if use_kernels else w


def _find_low_row(col: np.ndarray) -> int:
    """First-set-bit rank of one packed uint32 row; NO_LOW when zero."""
    nz = col != 0
    if not nz.any():
        return NO_LOW
    w = int(nz.argmax())
    word = int(col[w])
    return w * 32 + ((word & -word).bit_length() - 1)


def _budgeted_batch_size(batch_size: int, cob_width: int,
                         store_budget_bytes: Optional[int]) -> int:
    """Cap the batch so the resident bit block fits the byte budget.

    The batch block is ``B`` rows × ``~B·K/32`` words ≈ ``B²K/8`` bytes.
    Inverting for ``B`` bounds the packed-block scratch the same way
    ``h2_columns`` bounds its enumeration scratch; neither changes the
    output.  Best-effort: the batch never shrinks below 32 rows (a
    narrower batch loses the batching the engine exists for), so very
    small budgets bound the block at the 32-row floor, not the budget.
    """
    if store_budget_bytes is None:
        return batch_size
    b = int(np.sqrt(max(1.0, 4.0 * store_budget_bytes / max(1, cob_width))))
    return int(np.clip(b, 32, batch_size))


class _PackedBatch:
    """One batch resident in packed form, with a scalar escape hatch.

    Layout: ``block[:, 0:cap]`` is the R region — a sequence of
    word-aligned segments, each a sorted key array mapped to consecutive
    bit ranks — and ``block[:, cap:cap+VW]`` are the V-words the kernel
    serial pre-pass uses for δ-expansion tracking (zero otherwise).
    ``scalar`` maps evicted rows to plain int64 key arrays; ``lows`` holds
    every row's current low *key* (-1 = empty), which survives segment
    growth, consolidation and eviction unchanged.
    """

    def __init__(self, cob: np.ndarray, seed_addends: List[np.ndarray],
                 use_kernels: bool, cache=None):
        B = cob.shape[0]
        self.B = B
        self.VW = (B + 31) // 32
        self.use_kernels = use_kernels
        self.cache = cache
        if cache is not None:
            cache.bump_epoch()   # fresh universe: prior positions are stale
        mask = cob != EMPTY_KEY
        seg0 = np.unique(np.concatenate([cob[mask]] + seed_addends))
        self.segs: List[np.ndarray] = [seg0]
        self.seg_off: List[int] = [0]          # word offset per segment
        self.r_words = _words(len(seg0), use_kernels)
        self.cap = self.r_words
        self.block = np.zeros((B, self.cap + self.VW), dtype=np.uint32)
        ridx, _ = np.nonzero(mask)
        pos = np.searchsorted(seg0, cob[mask])
        scatter_bits(self.block, ridx, pos)
        self.scalar: Dict[int, np.ndarray] = {}
        self.lows = np.where(cob[:, 0] == EMPTY_KEY, np.int64(-1), cob[:, 0])
        self.peak_bytes = self.block.nbytes
        self.max_words = 0    # widest row issued to the serial kernel
        self.n_consolidations = 0
        self.n_expansions = 0
        self.n_evictions = 0
        self.n_device_calls = 0   # gf2 kernel round trips (serial pre-pass)
        self.n_kernel_lows = 0    # rows whose low came back from the pre-pass
        self.n_host_xor_rounds = 0   # rounds whose packed rows XORed in place

    # -- universe bookkeeping ------------------------------------------------

    def _grow_cap(self, need: int) -> None:
        new_cap = max(need, 2 * self.cap)
        block = np.zeros((self.B, new_cap + self.VW), dtype=np.uint32)
        block[:, :self.r_words] = self.block[:, :self.r_words]
        # V region is zero outside the kernel pre-pass — nothing to move
        self.block = block
        self.cap = new_cap
        self.peak_bytes = max(self.peak_bytes, block.nbytes)

    def add_segment(self, new_keys: np.ndarray) -> None:
        """Append new addend keys as a fresh word-aligned segment — no
        re-ranking of resident bits (rank order only holds per segment;
        lows are reconstructed as a min over segments)."""
        w = _words(len(new_keys), self.use_kernels)
        if self.r_words + w > self.cap:
            self._grow_cap(self.r_words + w)
        self.segs.append(new_keys)
        self.seg_off.append(self.r_words)
        self.r_words += w
        if self.use_kernels or len(self.segs) > _MAX_SEGMENTS:
            self.consolidate()

    def consolidate(self) -> None:
        """Merge all segments into one sorted universe (one global remap).
        The kernel path runs consolidated always: the lows
        ``gf2_serial_reduce`` returns are first set *bits*, which equal the
        min *key* only in a single globally-sorted bit-space."""
        if len(self.segs) == 1:
            return
        self.n_consolidations += 1
        san = active_sanitizer()
        if self.cache is not None:
            self.cache.bump_epoch()   # re-ranking invalidates cached positions
        ridx_all, keys_all = [], []
        for seg, off in zip(self.segs, self.seg_off):
            w = _words(len(seg), self.use_kernels)
            ridx, pos, _ = set_bit_positions(self.block[:, off:off + w])
            keep = pos < len(seg)
            if san is not None:
                # the keep filter below silently drops any bit past the
                # segment universe — under the sanitizer that is a lost
                # GF(2) coordinate, not slack
                san.check_segment_bits(pos, len(seg))
            ridx_all.append(ridx[keep])
            keys_all.append(seg[pos[keep]])
        ridx = np.concatenate(ridx_all)
        keys = np.concatenate(keys_all)
        universe = np.unique(np.concatenate(self.segs))
        self.segs = [universe]
        self.seg_off = [0]
        self.r_words = _words(len(universe), self.use_kernels)
        if self.r_words > self.cap:
            self.cap = self.r_words
        self.block = np.zeros((self.B, self.cap + self.VW), dtype=np.uint32)
        self.peak_bytes = max(self.peak_bytes, self.block.nbytes)
        pos = np.searchsorted(universe, keys)
        order = np.lexsort((pos, ridx))
        scatter_bits(self.block, ridx[order], pos[order])
        if san is not None:
            san.check_consolidation(ridx, keys, universe,
                                    self.block[:, :self.r_words])

    def _abs_positions(self, keys: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """Absolute bit position of each key (32·segment word offset +
        in-segment rank) plus the mask of keys in no segment yet."""
        out = np.full(len(keys), -1, dtype=np.int64)
        todo = np.ones(len(keys), dtype=bool)
        for seg, off in zip(self.segs, self.seg_off):
            if not len(seg) or not todo.any():
                continue
            pos = np.minimum(np.searchsorted(seg, keys), len(seg) - 1)
            hit = todo & (seg[pos] == keys)
            out[hit] = off * 32 + pos[hit]
            todo &= ~hit
        return out, todo

    # -- representation moves ------------------------------------------------

    def _unpack_row(self, c: int) -> np.ndarray:
        parts = []
        for seg, off in zip(self.segs, self.seg_off):
            if not len(seg):
                continue
            w = _words(len(seg), self.use_kernels)
            _, pos, _ = set_bit_positions(self.block[c:c + 1, off:off + w])
            pos = pos[pos < len(seg)]
            if pos.size:
                parts.append(seg[pos])
        return np.concatenate(parts) if parts else np.zeros(0, dtype=np.int64)

    def evict(self, c: int) -> None:
        """Move row ``c`` to scalar (sorted-key) form: one stubborn chain
        must not balloon the shared bit-space."""
        if c in self.scalar:
            return
        self.n_evictions += 1
        keys = self._unpack_row(c)
        keys.sort(kind="stable")
        self.block[c, :self.r_words] = 0
        self.scalar[c] = keys

    # -- lows ----------------------------------------------------------------

    def refresh_lows(self, rows: np.ndarray) -> None:
        """Recompute ``lows[rows]`` (packed rows) as the min key over
        per-segment find-lows.  Each segment is scanned over its own words
        only, so the V-words at the block's tail never register a low, and
        a first set bit past a segment's universe (slack) counts as empty —
        the same keys :meth:`_set_kernel_lows` gives on one segment."""
        rows = np.asarray(rows, dtype=np.int64)
        if not rows.size:
            return
        best = np.full(len(rows), EMPTY_KEY, dtype=np.int64)
        for seg, off in zip(self.segs, self.seg_off):
            if not len(seg):
                continue
            w = _words(len(seg), self.use_kernels)
            lb = find_low_np(self.block[rows, off:off + w])
            k = np.where((lb == NO_LOW) | (lb >= len(seg)), EMPTY_KEY,
                         seg[np.minimum(lb, len(seg) - 1)])
            best = np.minimum(best, k)
        self.lows[rows] = np.where(best == EMPTY_KEY, -1, best)

    def _set_kernel_lows(self, rows: List[int], lb: np.ndarray) -> None:
        """Set ``lows[rows]`` from the first-set-bit ranks the serial
        kernel returned on the kernel path's single segment: -1 for NO_LOW
        and for ranks past the universe (zero slack words, or the V-words of
        an R-empty row)."""
        seg = self.segs[0]
        inside = lb < len(seg)
        keys = np.full(len(lb), -1, dtype=np.int64)
        keys[inside] = seg[lb[inside]]
        self.lows[rows] = keys
        self.n_kernel_lows += len(rows)

    def _row_low(self, c: int) -> int:
        best = -1
        for seg, off in zip(self.segs, self.seg_off):
            if not len(seg):
                continue
            w = _words(len(seg), self.use_kernels)
            lb = _find_low_row(self.block[c, off:off + w])
            if lb != NO_LOW and lb < len(seg):
                k = int(seg[lb])
                if best < 0 or k < best:
                    best = k
        return best

    # -- parallel phase ------------------------------------------------------

    def xor_addends(self, hit: List[int],
                    addends: List[Optional[np.ndarray]],
                    addend_lows: Optional[np.ndarray] = None) -> None:
        """Parallel-phase GF(2) add: gathered addends into the hit rows —
        packed rows by an in-place scatter-XOR into the host block and a
        find-low over those rows (:meth:`refresh_lows`), on the kernel path
        too; scalar rows ``merge_cancel``.

        Addend keys outside every segment either append as a fresh segment
        (dense rounds) or evict their rows (sparse rounds, ``_EVICT_MAX``).

        ``addend_lows[i]`` names the pivot low row ``i``'s addend came from;
        a pivot's key array is canonical per low, so its packed positions
        memoize in the shared cache per block epoch — repeat consumers (in
        particular the other slices of a fused superstep) skip the
        per-segment ``searchsorted`` re-pack entirely.
        """
        scalar_hit = [i for i in hit if i in self.scalar]
        packed_hit = [i for i in hit if i not in self.scalar]
        memo_rows: List[int] = []
        memo_pos: List[np.ndarray] = []
        if packed_hit and self.cache is not None and addend_lows is not None:
            rest = []
            for i in packed_hit:
                p = self.cache.get_positions(int(addend_lows[i]))
                if p is not None and len(p) == len(addends[i]):
                    memo_rows.append(i)
                    memo_pos.append(p)
                else:
                    rest.append(i)
            packed_hit = rest
        if packed_hit:
            epoch0 = self.n_consolidations
            lens = np.array([len(addends[i]) for i in packed_hit],
                            dtype=np.int64)
            keys = np.concatenate([addends[i] for i in packed_hit])
            ridx = np.repeat(np.asarray(packed_hit, dtype=np.int64), lens)
            pos, missing = self._abs_positions(keys)
            if missing.any():
                miss_rows = np.unique(ridx[missing])
                if len(miss_rows) <= _EVICT_MAX:
                    for i in miss_rows:
                        self.evict(int(i))
                        scalar_hit.append(int(i))
                    keep = ~np.isin(ridx, miss_rows)
                    ridx, pos, keys = ridx[keep], pos[keep], keys[keep]
                    mask = ~np.isin(np.asarray(packed_hit), miss_rows)
                    packed_hit = [i for i in packed_hit
                                  if i not in self.scalar]
                    lens = lens[mask]
                else:
                    self.n_expansions += 1
                    new_seg = np.unique(keys[missing])
                    n_segs = len(self.segs) + 1
                    self.add_segment(new_seg)
                    if len(self.segs) == n_segs:
                        # append-only: found positions are still valid
                        off = self.seg_off[-1]
                        pos[missing] = off * 32 + np.searchsorted(
                            new_seg, keys[missing])
                    else:   # consolidation re-ranked everything
                        pos, miss2 = self._abs_positions(keys)
                        assert not miss2.any()
            if self.cache is not None and addend_lows is not None \
                    and packed_hit:
                starts = np.zeros(len(packed_hit) + 1, dtype=np.int64)
                np.cumsum(lens, out=starts[1:])
                for k, i in enumerate(packed_hit):
                    self.cache.put_positions(int(addend_lows[i]),
                                             pos[starts[k]:starts[k + 1]])
            if memo_rows and self.n_consolidations != epoch0:
                # a consolidation re-ranked the universe under the memoized
                # rows: recompute them (their keys were resident, so they
                # cannot miss) and re-memoize against the new epoch
                mkeys = np.concatenate([addends[i] for i in memo_rows])
                mpos, mmiss = self._abs_positions(mkeys)
                assert not mmiss.any()
                mlens = np.array([len(addends[i]) for i in memo_rows],
                                 dtype=np.int64)
                starts = np.zeros(len(memo_rows) + 1, dtype=np.int64)
                np.cumsum(mlens, out=starts[1:])
                memo_pos = [mpos[starts[k]:starts[k + 1]]
                            for k in range(len(memo_rows))]
                for k, i in enumerate(memo_rows):
                    self.cache.put_positions(int(addend_lows[i]),
                                             memo_pos[k])
        if memo_rows:
            mlens = np.array([len(p) for p in memo_pos], dtype=np.int64)
            mridx = np.repeat(np.asarray(memo_rows, dtype=np.int64), mlens)
            mpos = (np.concatenate(memo_pos) if memo_pos
                    else np.zeros(0, dtype=np.int64))
            if packed_hit:
                ridx = np.concatenate([ridx, mridx])
                pos = np.concatenate([pos, mpos])
                packed_hit = packed_hit + memo_rows
            else:
                ridx, pos = mridx, mpos
                packed_hit = list(memo_rows)
        if packed_hit:
            order = np.lexsort((pos, ridx))
            scatter_xor_bits(self.block, ridx[order], pos[order])
            self.refresh_lows(np.asarray(packed_hit, dtype=np.int64))
            self.n_host_xor_rounds += 1
        for i in scalar_hit:
            merged = merge_cancel(self.scalar[i], addends[i])
            self.scalar[i] = merged
            self.lows[i] = int(merged[0]) if merged.size else -1

    # -- serial phase --------------------------------------------------------

    def _absorb(self, c: int, j: int, gens: List[Dict[int, int]],
                ids_int: List[int]) -> int:
        """Row ``c <- c ⊕ j`` over GF(2) with gens bookkeeping; returns
        ``c``'s new low key (does not write ``lows``).  ``c`` must come
        after ``j`` in processing order.  Packed rows XOR whole block rows;
        scalar rows ``merge_cancel``; a packed row absorbing a scalar mate
        evicts first."""
        c_packed = c not in self.scalar
        j_packed = j not in self.scalar
        if c_packed and not j_packed:
            self.evict(c)
            c_packed = False
        if c_packed:
            self.block[c] ^= self.block[j]
            low = self._row_low(c)
        else:
            jkeys = self.scalar[j] if not j_packed \
                else self._unpack_row(j)
            merged = merge_cancel(self.scalar[c], jkeys)
            self.scalar[c] = merged
            low = int(merged[0]) if merged.size else -1
        gens[c][ids_int[j]] = gens[c].get(ids_int[j], 0) + 1
        for g, p in gens[j].items():
            gens[c][g] = gens[c].get(g, 0) + p
        return low

    def serial_pass(self, gens: List[Dict[int, int]],
                    ids_int: List[int],
                    rows: Optional[np.ndarray] = None
                    ) -> Tuple[int, np.ndarray]:
        """Resolve intra-batch low collisions in filtration order.

        Kernel path: a ``gf2_serial_reduce`` V-augmented pre-pass clears
        packed-vs-packed collisions in VMEM (V bits -> gens merge), then
        the host walk finishes scalar-involved collisions.  Host path: the
        walk does everything via :meth:`_absorb`.  ``rows`` restricts the
        walk to one contiguous slice (the fused-superstep drivers resolve
        per-device slices independently; the kernel pre-pass assumes the
        whole block and only runs unrestricted).  Returns
        ``(n_reductions, changed_row_indices)``.
        """
        n_red = 0
        changed: Dict[int, bool] = {}
        if rows is None:
            if self.use_kernels:
                n_red += self._serial_kernel_prepass(gens, ids_int, changed)
            row_iter = range(self.B)
        else:
            row_iter = [int(r) for r in rows]
        with span("reduce/serial"):
            low_to_row: Dict[int, int] = {}
            for c in row_iter:
                low = int(self.lows[c])
                while low >= 0:
                    j = low_to_row.get(low)
                    if j is None:
                        break
                    n_red += 1
                    changed[c] = True
                    low = self._absorb(c, j, gens, ids_int)
                self.lows[c] = low
                if low >= 0:
                    low_to_row[low] = c
        return n_red, np.array(sorted(changed), dtype=np.int64)

    def _serial_kernel_prepass(self, gens: List[Dict[int, int]],
                               ids_int: List[int],
                               changed: Dict[int, bool]) -> int:
        """Kernel pre-pass on the packed rows: V-identity words ride the
        block tail, ``gf2_serial_reduce`` XORs colliding rows in VMEM, and
        the V bits name each row's absorbed mates afterwards (scalar rows'
        block rows are zero, hence inert; zero slack words between the R
        segment and the V-words are skipped by the kernel's find-low; and
        V-rank collisions only ever involve R-empty rows).  The kernel's
        own lows become the touched rows' low keys: a rank past the R
        universe means the row's R part is empty."""
        import jax
        import jax.numpy as jnp

        from ..kernels.gf2 import gf2_serial_reduce

        assert len(self.segs) == 1
        B, cap = self.B, self.cap
        vbit = np.arange(B)
        vslice = self.block[:, cap:]
        vslice[...] = 0
        # scalar rows get no identity bit: inert rows must not register lows
        live = np.array([i not in self.scalar for i in range(B)])
        lv = vbit[live]
        vslice[lv, lv >> 5] |= np.uint32(1) << (lv & 31).astype(np.uint32)
        C, W = B, cap + self.VW
        Cp, Wp = -(-C // 32) * 32, -(-W // 128) * 128
        self.max_words = max(self.max_words, Wp)
        padded = np.zeros((Cp, Wp), dtype=np.uint32)
        padded[:C, :W] = self.block
        with span("gf2/serial"):
            red, lb, n_red = jax.device_get(
                gf2_serial_reduce(jnp.asarray(padded[None])))
            self.block[...] = red[0, :C, :W]
            n_red = int(n_red[0])
        self.n_device_calls += 1
        if n_red == 0:
            vslice[...] = 0
            return 0
        vrid, vpos, _ = set_bit_positions(vslice)
        vkeep = vpos < B
        counts = np.bincount(vrid[vkeep], minlength=B).astype(np.int64)
        vrows = np.split(vpos[vkeep], np.cumsum(counts)[:-1])
        touched = [i for i in range(B) if vrows[i].size > 1]
        entry = {int(i): dict(gens[i]) for i in touched}
        for i in touched:
            changed[int(i)] = True
            newg = dict(entry[int(i)])
            for j in vrows[i]:
                j = int(j)
                if j == i:
                    continue
                newg[ids_int[j]] = newg.get(ids_int[j], 0) + 1
                # unchanged mates keep their live gens; changed mates use
                # their pass-entry snapshot (the kernel walk is ascending)
                for g, p in entry.get(j, gens[j]).items():
                    newg[g] = newg.get(g, 0) + p
            gens[i] = newg
        vslice[...] = 0
        if touched:
            self._set_kernel_lows(touched, lb[0, touched])
        return n_red

    # -- clearance -----------------------------------------------------------

    def unpack(self, rows: np.ndarray) -> List[np.ndarray]:
        """``rows`` as int64 key arrays, one block pass per segment.

        Row keys come out ascending *within* each segment's contribution
        (segment-major order overall, not globally sorted) — every consumer
        either re-ranks per key (the pack/scatter paths) or re-sorts
        (``merge_cancel``, ``parity_reduce``), so a global per-row sort
        would buy nothing.  Clearance also only unpacks the rows it will
        store: trivial pairs commit nothing."""
        rows = np.asarray(rows, dtype=np.int64)
        n = len(rows)
        if not n:
            return []
        out_scalar = {int(i): self.scalar[int(i)] for i in rows
                      if int(i) in self.scalar}
        packed_rows = np.array([i for i in rows if int(i) not in self.scalar],
                               dtype=np.int64)
        np_rows = len(packed_rows)
        parts = []
        counts = np.zeros(np_rows, dtype=np.int64)
        for seg, off in zip(self.segs, self.seg_off):
            if not len(seg) or not np_rows:
                continue
            w = _words(len(seg), self.use_kernels)
            ridx, pos, cnt = set_bit_positions(
                self.block[packed_rows, off:off + w])
            keep = pos < len(seg)
            if not keep.all():
                ridx, pos = ridx[keep], pos[keep]
                cnt = np.bincount(ridx, minlength=np_rows).astype(np.int64)
            parts.append((ridx, seg[pos], cnt))
            counts += cnt
        out = np.empty(int(counts.sum()), dtype=np.int64)
        row_start = np.zeros(np_rows + 1, dtype=np.int64)
        np.cumsum(counts, out=row_start[1:])
        fill = row_start[:-1].copy()
        for ridx, keys, cnt in parts:
            if not len(keys):
                continue
            part_off = np.cumsum(cnt) - cnt
            within = np.arange(len(keys), dtype=np.int64) - part_off[ridx]
            out[fill[ridx] + within] = keys
            fill += cnt
        packed_cols = np.split(out, row_start[1:-1]) if np_rows else []
        packed_iter = iter(packed_cols)
        return [out_scalar[int(i)] if int(i) in out_scalar
                else next(packed_iter) for i in rows]


def _tournament_merge(blk: _PackedBatch, gens: List[Dict[int, int]],
                      ids_int: List[int],
                      bounds: np.ndarray) -> Tuple[int, np.ndarray]:
    """Cross-slice catch-up in ``log2 P`` hypercube rounds.

    Pairing is the hypercube's ``(j, j XOR step)``; the later-ranked slice
    absorbs, because every column of a later batch follows every column of
    an earlier one in processing order — so each absorption is a legal
    left-to-right column addition and only removes work.  Collisions the
    hypercube pairing does not cover (and any it creates) are caught by the
    driver's store-probe / per-slice serial-pass loop and the exact commit
    sweep."""
    n_red = 0
    changed: set = set()
    P = len(bounds) - 1
    step = 1
    while step < P:
        for j in range(P):
            p = j ^ step
            if p >= j or p >= P:
                continue   # absorber is the later-ranked slice of the pair
            plow: Dict[int, int] = {}
            for r in range(int(bounds[p]), int(bounds[p + 1])):
                lw = int(blk.lows[r])
                if lw >= 0:
                    plow[lw] = r
            for c in range(int(bounds[j]), int(bounds[j + 1])):
                lw = int(blk.lows[c])
                while lw >= 0 and lw in plow:
                    n_red += 1
                    changed.add(c)
                    lw = blk._absorb(c, plow[lw], gens, ids_int)
                blk.lows[c] = lw
        step <<= 1
    return n_red, np.array(sorted(changed), dtype=np.int64)


def _resolve_reduce_shards(mesh, n_shards: Optional[int]) -> int:
    """Shard count for the distributed driver: the mesh's data-axis size,
    or ``n_shards`` for the host-partitioned simulation (same work split,
    no devices needed — mirrors ``scale.shard.harvest_edges_sharded``)."""
    if mesh is not None:
        from ..scale.shard import shard_of_mesh
        axis, mesh_shards = shard_of_mesh(mesh)
        if n_shards is not None and int(n_shards) != mesh_shards:
            raise ValueError(
                f"n_shards={n_shards} disagrees with the mesh's "
                f"{axis}-axis size {mesh_shards}; pass only one of them")
        return mesh_shards
    return 1 if n_shards is None else int(n_shards)


def _exchange_round_fn(x, axis_name: str):
    """Per-device body of one pivot-exchange round: block ``(1, L)`` in,
    every shard's ``(P, L)`` out.  Module-level (closed only over the
    static ``axis_name``) so ``repro.analyze.collectives`` can trace its
    collective schedule without building the mesh driver."""
    import jax

    return jax.lax.all_gather(x[0], axis_name)


def reduce_specs(mesh):
    """Specs for the pivot-exchange ``shard_map``.

    The exchange round moves one ``(P, L)`` uint32 payload buffer — shard
    ``k``'s Elias–Fano-encoded commit delta in row ``k`` — through an
    ``all_gather`` over the same innermost data axis the tile harvest
    shards on: in, the leading axis shards over ``data`` (each device holds
    its own row); out, every device returns the full gathered ``(P, L)``
    buffer, i.e. the result is replicated (spec ``P()``), which is exactly
    the replica-install contract: every shard sees every shard's pivots.

    Returns ``(in_spec, out_spec, axis_name)`` for ``jax.shard_map``.
    """
    from jax.sharding import PartitionSpec

    from ..scale.shard import shard_of_mesh

    axis, _ = shard_of_mesh(mesh)
    return PartitionSpec(axis), PartitionSpec(), axis


def _make_exchange(mesh, n_shards: int):
    """Pivot-exchange round: per-shard wire payloads -> all shards' payloads.

    With a mesh, payloads stack into a ``(P, L)`` uint32 buffer (``L``
    bucketed to a power of two so the jitted collective retraces a handful
    of times, not once per superstep) and cross-ship through
    ``jax.lax.all_gather`` under ``shard_map`` with the specs from
    :func:`reduce_specs`.  Without a mesh the exchange is the host
    loop-back — identical payload path (encode -> exchange -> decode), no
    devices."""
    if mesh is None:
        return lambda payloads: payloads
    import jax
    import jax.numpy as jnp

    in_spec, out_spec, axis = reduce_specs(mesh)
    fns: Dict[int, object] = {}

    def exchange(payloads: List[np.ndarray]) -> List[np.ndarray]:
        buf, lens = stack_wire_payloads(payloads)
        L = buf.shape[1]
        if L not in fns:
            fns[L] = jax.jit(jax.shard_map(
                functools.partial(_exchange_round_fn, axis_name=axis),
                mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                check_vma=False))
        return unstack_wire_payloads(fns[L](jnp.asarray(buf)), lens)

    return exchange


def reduce_dimension_packed(
    adapter: DimensionAdapter,
    column_ids: np.ndarray,
    mode: str = "explicit",
    cleared=None,
    batch_size: int = 256,
    store_budget_bytes: Optional[int] = None,
    use_kernels: Optional[bool] = None,
    n_shards: Optional[int] = None,
    mesh=None,
    cache=None,
    exchange_every: int = 4,
    seed_gens: Optional[Dict[int, np.ndarray]] = None,
    commit_sink: Optional[list] = None,
    essential_log: Optional[list] = None,
) -> ReductionResult:
    """Bit-packed serial-parallel cohomology reduction (module docstring).

    Same contract as ``reduce_dimension`` / ``reduce_dimension_batched``:
    ``column_ids`` in decreasing filtration order, diagrams bit-identical to
    both for every shard count.  ``use_kernels=None`` resolves to the Pallas
    kernels on TPU and the numpy block mirrors elsewhere; ``True`` forces
    the kernels (they interpret off-TPU — the test path).

    ``n_shards`` > 1 or a ``mesh`` runs the fused-superstep distributed
    driver: batches deal round-robin over the shards, each superstep's P
    batches reduce concurrently against per-device pivot replicas fed by
    Elias–Fano-compressed pivot-exchange rounds, and commits happen in
    exact global batch order (module docstring).  ``exchange_every``
    batches the exchange rounds — payloads ship every that-many supersteps,
    amortizing the codec's fixed per-round cost (the default of 4 is where
    the fractal benchmark's exchange time flattens; much larger backlogs
    inflate the fused Elias–Fano universe instead).  Staleness is
    exact-safe because the commit sweep re-probes every pivot the replica
    has not seen yet (``pending`` below).  ``cache`` threads a caller-owned
    :class:`~repro.core.pivot_cache.PackedPivotCache` (one is created per
    call otherwise).

    Distributed stats report two walls: the host really runs every shard's
    work back-to-back on one process, so ``sim_wall_s`` accounts the
    critical path a P-device mesh would execute — per-shard busy time for
    the data-parallel phases (fused block ops attributed by row share,
    per-slice serial passes timed directly), plus the genuinely sequential
    parts at full cost (tournament, the in-order commit sweep, decode +
    install, which every device performs on all P payloads).  For P == 1
    the same accounting reproduces the measured wall.

    Every timed region is a span on a local, always-on tracer — each phase
    carries its lane (shard) and superstep, so a run under
    ``compute_ph(trace=...)`` renders as P parallel device lanes — and
    ``sim_wall_s`` is *derived* from that span timeline
    (:func:`repro.obs.trace.critical_path`).  Leaf spans of the host work
    and of each gf2 kernel round trip (``reduce/probe``, ``gf2/serial``, ...)
    go to the caller's tracer only and carry no ``step``, so they never
    enter that accounting.
    """
    san = active_sanitizer()
    # local timeline: always on (sim_wall is derived from it), forwarding
    # into the user's tracer when compute_ph(trace=...) activated one
    tl = Tracer(forward_to=active_tracer())
    use_kernels = _resolve_use_kernels(use_kernels)
    P = _resolve_reduce_shards(mesh, n_shards)
    if exchange_every < 1:
        raise ValueError("exchange_every must be >= 1")
    if cache is None:
        from .pivot_cache import PackedPivotCache
        cache = PackedPivotCache()
    # P == 1 appends commits straight into the caller's sink (if any);
    # P > 1 owns a scratch log that is drained into per-shard wire
    # backlogs every slice — the sink then receives copies of each
    # drained record (``seed_gens`` / ``commit_sink`` / ``essential_log``
    # carry the same warm-restart + capture contract as
    # ``reduce_dimension``; see repro.core.resume)
    commit_log: Optional[list] = [] if P > 1 else commit_sink
    store = PivotStore(adapter, mode, store_budget_bytes=store_budget_bytes,
                       cache=cache, commit_log=commit_log)
    if P > 1:
        from .pivot_cache import (decode_commit_delta, encode_commit_delta,
                                  verify_commit_delta)
        # the replica mirrors the authority's track_gens: with an explicit
        # budgeted store the wire ships δ-expansions precisely so that
        # replica probes can return them (install() never spills, so the
        # budget carries no other behavior here)
        replica = PivotStore(adapter, mode,
                             store_budget_bytes=store_budget_bytes,
                             cache=cache)
        exchange = _make_exchange(mesh, P)
        lookup_store = replica
        # commits the replica has not installed yet: each shard's wire
        # backlog plus a map of their pivot lows -> (shard, superstep) —
        # the only lows at which the sweep's store re-probe can possibly
        # hit for rows that already stabilized against the replica, and
        # the provenance that drives the sweep's critical-path accounting
        shard_logs: List[list] = [[] for _ in range(P)]
        pending: Dict[int, Tuple[int, int]] = {}
        # -- resilience (docs/resilience.md): heartbeat supervision on the
        # deterministic superstep clock.  Every live shard beats once per
        # superstep; a shard that misses a beat past the timeout is dead
        # and its remaining batch queue re-deals to the survivors from the
        # last exact commit sweep (nothing commits before the sweep, so
        # the restart line is exact by construction).  Stragglers are
        # sidelined from dealing for a cooldown but stay live.  An armed
        # FaultInjector (repro.resilience) is what kills/slows shards and
        # drops/corrupts wire payloads — on a seeded, reproducible
        # schedule; with none armed this is all no-op bookkeeping.
        sup = ShardSupervisor(n_shards=P, timeout=0.75, factor=3.0,
                              sideline=1)
        inj = active_injector()
        killed: set = set()
        slow_lag: Dict[int, Tuple[float, int]] = {}  # shard -> (lag, until)
        n_shard_deaths = 0
        n_redeals = 0
        n_sidelines = 0
        n_exchange_retries = 0
        n_exchange_deferrals = 0
        n_wire_corruptions = 0
        n_faults_seen = 0
    else:
        lookup_store = store
        inj = None
    pairs: List[tuple] = []
    essentials: List[float] = []
    essential_ids: List[int] = []
    n_reductions = 0
    n_rounds = 0
    n_expansions = 0
    n_evictions = 0
    n_consolidations = 0
    n_supersteps = 0
    n_exchange_rounds = 0
    n_tournament_reductions = 0
    n_sweep_probes = 0
    exchange_bytes = 0
    peak_block_bytes = 0
    max_block_words = 0
    n_device_calls = 0
    n_kernel_lows = 0
    n_host_xor_rounds = 0
    reg = MetricsRegistry()
    queue = clearing_filter(column_ids, cleared)
    eff_batch = batch_size
    if len(queue):
        with span("reduce/cobdy"):
            cob0 = adapter.cobdy(queue[:min(batch_size, len(queue))])
        eff_batch = _budgeted_batch_size(batch_size, cob0.shape[1],
                                         store_budget_bytes)

    pos = 0
    while pos < len(queue):
        # ---- superstep: the next up-to-|active| batches, dealt
        # round-robin over the supervisor's active shards (all P when
        # nothing failed); slice k is shard active[k]'s local batch ----
        n_supersteps += 1
        step = n_supersteps
        mid_kills: List[int] = []
        if P > 1:
            if inj is not None:
                for s in list(sup.live):
                    for f in inj.fire("reduce.superstep", index=step,
                                      shard=s):
                        if f.kind in ("kill_shard", "slow_shard") \
                                and mesh is not None:
                            raise ValueError(
                                f"{f.kind} injection requires the "
                                "host-partitioned driver (mesh=None): a "
                                "jax mesh cannot shrink mid-collective")
                        n_faults_seen += 1
                        if f.kind == "kill_shard":
                            if f.param("when", "start") == "mid":
                                # participates in the concurrent phase,
                                # dies before its commit sweep
                                mid_kills.append(s)
                            else:
                                killed.add(s)
                        elif f.kind == "slow_shard":
                            # beat lag clamped below the death timeout:
                            # "slow" degrades, it does not kill
                            slow_lag[s] = (
                                min(float(f.param("lag", 0.6)), 0.6),
                                step + int(f.param("duration", 1)))
            beats: Dict[int, float] = {}
            for s in sup.live:
                if s in killed:
                    continue                  # a dead shard stops beating
                lag = slow_lag.get(s)
                beats[s] = (float(step) - lag[0]
                            if lag is not None and step <= lag[1]
                            else float(step))
            plan = sup.observe(float(step), beats)
            if not sup.live:
                raise RuntimeError(
                    "every reduction shard died; cannot recover")
            if plan.dead:
                # re-deal the dead shards' remaining queue to survivors
                # (automatic: dealing below only feeds active shards) and
                # hand their un-replicated wire backlog to an heir so the
                # replicas eventually hear about those commits
                with tl.span("resilience/recover", step=step,
                             kind="kill_start",
                             shards=tuple(plan.dead)) as rsp:
                    n_shard_deaths += len(plan.dead)
                    n_redeals += 1
                    heir = sup.live[0]
                    for d in plan.dead:
                        if shard_logs[d]:
                            shard_logs[heir].extend(shard_logs[d])
                            shard_logs[d] = []
                reg.histogram("resilience_recover_s").observe(rsp.dur)
            if plan.stragglers:
                n_sidelines += len(plan.stragglers)
            active = plan.active
        else:
            active = [0]
        slice_sizes = []
        start = pos
        for _ in range(len(active)):
            if pos >= len(queue):
                break
            take = min(eff_batch, len(queue) - pos)
            slice_sizes.append(take)
            pos += take
        ids_arr = np.asarray(queue[start:pos], dtype=np.int64)
        bounds = np.zeros(len(slice_sizes) + 1, dtype=np.int64)
        np.cumsum(slice_sizes, out=bounds[1:])
        n_slices = len(slice_sizes)
        B = len(ids_arr)
        ids_int = [int(i) for i in ids_arr]
        if san is not None:
            san.set_context(superstep=n_supersteps,
                            batch=f"{start}:{pos}")
        gens: List[Dict[int, int]] = [dict() for _ in range(B)]
        # per-shard busy accounting, span-encoded (obs.trace.critical_path):
        # fused block ops split by row share (the ``weights`` attr),
        # per-slice work on its own device lane, sync parts at full cost
        wt = tuple(float(sz) / max(B, 1) for sz in slice_sizes)
        t_fused = 0.0
        t_slice = np.zeros(max(n_slices, 1))
        t_seq = 0.0
        with tl.span("reduce/fused", step=step, weights=wt) as sp:
            with span("reduce/cobdy"):
                cob = adapter.cobdy(ids_arr)
            if seed_gens:
                # warm restart: seeded rows start from their recorded
                # residual (a valid left-to-right partial reduction state)
                # with gens parity pre-loaded — pad the row width when a
                # residual outgrows one coboundary row
                residuals: Dict[int, np.ndarray] = {}
                for i in range(B):
                    seed = seed_gens.get(ids_int[i])
                    if seed is not None and len(seed):
                        residuals[i] = seed_column(adapter, ids_int[i], seed)
                        gens[i] = {int(g): 1 for g in seed}
                if residuals:
                    width = max(cob.shape[1],
                                max(r.size for r in residuals.values()))
                    if width > cob.shape[1]:
                        pad = np.full((B, width - cob.shape[1]), EMPTY_KEY,
                                      dtype=np.int64)
                        cob = np.concatenate([cob, pad], axis=1)
                    else:
                        cob = cob.copy()
                    for i, r in residuals.items():
                        cob[i, :] = EMPTY_KEY
                        cob[i, :r.size] = r

            # seed the bit-space with the first round of addends so the
            # common case packs exactly once; the concurrent phase probes
            # the replica (P > 1) — complete up to the last exchange
            # round — or the store
            lows0 = np.where(cob[:, 0] == EMPTY_KEY, np.int64(-1), cob[:, 0])
            with span("reduce/probe"):
                addends, owners, owner_gens = \
                    lookup_store.lookup_addends_batched(lows0, ids_arr)
            addend_lows = lows0
            with span("reduce/pack"):
                batchblk = _PackedBatch(
                    cob, [a for a in addends if a is not None], use_kernels,
                    cache=cache)
        t_fused += sp.dur

        probe = np.zeros(B, dtype=bool)   # rows whose low moved since probe
        while True:
            with tl.span("reduce/fused", step=step, weights=wt) as sp:
                hit = [i for i in range(B) if addends[i] is not None]
                if hit:
                    n_rounds += 1
                    n_reductions += len(hit)
                    with span("reduce/gens"):
                        for i in hit:
                            o = int(owners[i])
                            gens[i][o] = gens[i].get(o, 0) + 1
                            for g in owner_gens[i]:
                                g = int(g)
                                gens[i][g] = gens[i].get(g, 0) + 1
                    with span("reduce/xor"):
                        batchblk.xor_addends(hit, addends, addend_lows)
                    probe[hit] = batchblk.lows[hit] >= 0
            t_fused += sp.dur

            # intra-slice collisions -> per-slice serial pass in filtration
            # order (the whole block is one slice when P == 1)
            for k in range(n_slices):
                s0, s1 = int(bounds[k]), int(bounds[k + 1])
                sl_lows = batchblk.lows[s0:s1]
                nz = sl_lows[sl_lows >= 0]
                if len(np.unique(nz)) != len(nz):
                    with tl.span("reduce/slice", lane=k, step=step) as sp:
                        rows = None if n_slices == 1 else np.arange(s0, s1)
                        n_red, changed = batchblk.serial_pass(gens, ids_int,
                                                              rows=rows)
                        n_reductions += n_red
                        probe[changed] = batchblk.lows[changed] >= 0
                    t_slice[k] += sp.dur

            if not probe.any() and n_slices > 1:
                with tl.span("reduce/tournament", step=step) as sp:
                    n_red, changed = _tournament_merge(batchblk, gens,
                                                       ids_int, bounds)
                    n_reductions += n_red
                    n_tournament_reductions += n_red
                    probe[changed] = batchblk.lows[changed] >= 0
                t_seq += sp.dur

            if not probe.any():
                break
            with tl.span("reduce/fused", step=step, weights=wt) as sp:
                probe_lows = np.where(probe, batchblk.lows, -1)
                probe[:] = False
                with span("reduce/probe"):
                    addends, owners, owner_gens = \
                        lookup_store.lookup_addends_batched(probe_lows,
                                                            ids_arr)
                addend_lows = probe_lows
            t_fused += sp.dur

        if P > 1 and mid_kills:
            # the shard died after its concurrent phase but before its
            # commit sweep: nothing of this superstep has committed, so
            # the last commit sweep is still the exact recovery line —
            # discard the superstep and restart it from ``start`` with
            # the survivors (bit-identical: commits replay in the same
            # global batch order, just dealt to fewer shards)
            with tl.span("resilience/recover", step=step, kind="kill_mid",
                         shards=tuple(mid_kills)) as rsp:
                for s in mid_kills:
                    killed.add(s)
                    sup.kill(s)
                n_shard_deaths += len(mid_kills)
                n_redeals += 1
                if sup.live:
                    heir = sup.live[0]
                    for s in mid_kills:
                        if shard_logs[s]:
                            shard_logs[heir].extend(shard_logs[s])
                            shard_logs[s] = []
            if not sup.live:
                raise RuntimeError(
                    "every reduction shard died; cannot recover")
            # time-to-recover = the discarded concurrent work + the
            # bookkeeping above (the re-dealt batches rerun next loop)
            reg.histogram("resilience_recover_s").observe(
                t_fused + float(t_slice[:max(n_slices, 1)].sum())
                + t_seq + rsp.dur)
            pos = start
            continue

        # ---- exact commit sweep, slice by slice in global batch order:
        # re-probe the *authoritative* store until stable, then
        # clearance-commit — the realized schedule is a left-to-right
        # reduction, so diagrams are bit-identical to the single-device
        # engines.  Every row already stabilized against the replica, so a
        # store probe can only hit at a ``pending`` low (committed since
        # the last exchange round — including this superstep's
        # earlier-slice pivots); only rows at those lows, or rows the
        # sweep itself changed ("dirty"), need re-probing.  For the
        # simulated wall, slice k's sweep waits only on the slices whose
        # *this-superstep* pivots it actually absorbed (a device learns
        # the earlier stable lows from a tiny broadcast and otherwise
        # sweeps + commits concurrently) — ``deps`` records that DAG ----
        deps: List[set] = [set() for _ in range(max(n_slices, 1))]
        for k in range(n_slices):
            with tl.span("reduce/sweep", lane=k, step=step) as sw_sp:
                if san is not None:
                    san.set_context(slice=k)
                s0, s1 = int(bounds[k]), int(bounds[k + 1])
                rows = np.arange(s0, s1)
                sids = ids_arr[s0:s1]
                if P > 1:
                    pending_arr = np.fromiter(pending, dtype=np.int64,
                                              count=len(pending))
                    dirty = np.zeros(len(sids), dtype=bool)
                    while True:
                        sl_lows = batchblk.lows[s0:s1].copy()
                        cand = dirty.copy()
                        if pending_arr.size:
                            cand |= np.isin(sl_lows, pending_arr)
                        cand &= sl_lows >= 0
                        if not cand.any():
                            break
                        sl_lows[~cand] = -1
                        n_sweep_probes += 1
                        with span("reduce/probe"):
                            adds, owns, ogens = \
                                store.lookup_addends_batched(sl_lows, sids)
                        dirty[:] = False
                        hit_local = [i for i in range(len(sids))
                                     if adds[i] is not None]
                        if hit_local:
                            n_rounds += 1
                            n_reductions += len(hit_local)
                            with span("reduce/gens"):
                                for i in hit_local:
                                    c = s0 + i
                                    o = int(owns[i])
                                    gens[c][o] = gens[c].get(o, 0) + 1
                                    for g in ogens[i]:
                                        g = int(g)
                                        gens[c][g] = gens[c].get(g, 0) + 1
                                    src = pending.get(int(sl_lows[i]))
                                    if src is not None \
                                            and src[1] == n_supersteps:
                                        deps[k].add(src[0])
                            full_adds: List[Optional[np.ndarray]] = [None] * B
                            full_lows = np.full(B, -1, dtype=np.int64)
                            for i in hit_local:
                                full_adds[s0 + i] = adds[i]
                                full_lows[s0 + i] = sl_lows[i]
                            with span("reduce/xor"):
                                batchblk.xor_addends(
                                    [s0 + i for i in hit_local],
                                    full_adds, full_lows)
                            dirty[hit_local] = True
                        cur = batchblk.lows[s0:s1]
                        nz = cur[cur >= 0]
                        if len(np.unique(nz)) != len(nz):
                            n_red, changed = batchblk.serial_pass(
                                gens, ids_int, rows=rows)
                            n_reductions += n_red
                            dirty[changed - s0] = True
                        dirty &= batchblk.lows[s0:s1] >= 0

                log_mark = len(commit_log) \
                    if (P > 1 and commit_log is not None) else 0
                with span("reduce/commit"):
                    clearance_commit(
                        store, adapter, sids, batchblk.lows[s0:s1],
                        gens[s0:s1],
                        lambda rr, rows=rows: batchblk.unpack(
                            rows[np.asarray(rr, dtype=np.int64)]),
                        pairs, essentials, essential_ids=essential_ids,
                        essential_log=essential_log)
                if P > 1 and len(commit_log) > log_mark:
                    # drain this slice's commits straight into its shard's
                    # wire backlog; their lows are pending until the next
                    # exchange.  With gens untracked (explicit, no budget)
                    # neither side of the wire ever reads a δ-expansion —
                    # don't ship them.  The caller's sink gets record
                    # copies *before* the gens strip mutates them.
                    fresh = commit_log[log_mark:]
                    if commit_sink is not None:
                        commit_sink.extend(dict(r) for r in fresh)
                    if not store.track_gens:
                        for r in fresh:
                            r["gens"] = None
                    shard_logs[active[k]].extend(fresh)
                    for r in fresh:
                        pending[r["low"]] = (k, n_supersteps)
                    del commit_log[log_mark:]
                # the dep DAG is known only now — amend the span so the
                # timeline alone reconstructs the sweep critical path
                sw_sp.set(deps=tuple(sorted(deps[k])))

        peak_block_bytes = max(peak_block_bytes, batchblk.peak_bytes)
        # block rows only widen; the serial kernel sees them padded
        max_block_words = max(max_block_words, batchblk.block.shape[1],
                              batchblk.max_words)
        n_consolidations += batchblk.n_consolidations
        n_expansions += batchblk.n_expansions
        n_evictions += batchblk.n_evictions
        n_device_calls += batchblk.n_device_calls
        n_kernel_lows += batchblk.n_kernel_lows
        n_host_xor_rounds += batchblk.n_host_xor_rounds

        frac = np.asarray(wt, dtype=np.float64)
        step_conc = float(np.max(t_fused * frac + t_slice[:n_slices]))
        reg.histogram("superstep_conc_s").observe(step_conc)

        # ---- pivot exchange (every ``exchange_every`` supersteps, and
        # skipped once the queue is drained — the replica is never read
        # again): each shard ships its backlog as one EF-compressed
        # payload; every shard installs all decoded payloads into its
        # replica (the host simulation installs once, which is exactly one
        # device's worth of decode + install work) ----
        if (P > 1 and pos < len(queue)
                and n_supersteps % exchange_every == 0
                and any(shard_logs)):
            n_exchange_rounds += 1
            payloads = []
            shipped_lows: List[List[int]] = []
            for k in range(P):
                with tl.span("reduce/encode", lane=k, step=step):
                    payloads.append(encode_commit_delta(shard_logs[k]))
                shipped_lows.append([r["low"] for r in shard_logs[k]])
            # wire-level faults: each payload's delivery gets a bounded
            # retry with deterministic jittered backoff (the schedule is
            # accounted, not slept — this transport is host-simulated); a
            # payload that exhausts its budget is *deferred* — an empty
            # payload ships in its slot and its backlog + pending lows
            # survive to the next round, exact by the same staleness
            # argument as the exchange cadence itself
            delivered = [True] * P
            if inj is not None:
                empty_payload = encode_commit_delta([])

                def note_retry(a, e, delay):
                    nonlocal n_exchange_retries
                    n_exchange_retries += 1
                    reg.histogram("resilience_backoff_s").observe(delay)

                for k in range(P):
                    def attempt(a, k=k, buf0=payloads[k]):
                        nonlocal n_faults_seen, n_wire_corruptions
                        buf = buf0
                        for f in inj.fire("exchange.wire",
                                          index=n_exchange_rounds,
                                          shard=k):
                            n_faults_seen += 1
                            if f.kind == "drop":
                                raise TransientFault(
                                    f"exchange payload {k} dropped")
                            if f.kind == "corrupt":
                                buf = corrupt_payload(
                                    buf, int(f.param("bit", 17)))
                            elif f.kind == "delay":
                                reg.histogram(
                                    "resilience_backoff_s").observe(
                                    float(f.param("delay_s", 1e-3)))
                        if not verify_commit_delta(buf):
                            n_wire_corruptions += 1
                            raise TransientFault(
                                f"exchange payload {k} corrupt on the "
                                "wire (checksum)")
                        return buf

                    try:
                        payloads[k] = retry_with_backoff(
                            attempt, attempts=3, base_s=1e-4,
                            seed=(n_exchange_rounds << 8) | k,
                            sleep=None, on_retry=note_retry)
                    except TransientFault:
                        n_exchange_deferrals += 1
                        payloads[k] = empty_payload
                        delivered[k] = False
            wire = sum(p.nbytes for p in payloads)
            exchange_bytes += wire
            with tl.span("reduce/exchange", step=step, bytes=int(wire)):
                for payload in exchange(payloads):
                    for rec in decode_commit_delta(payload):
                        replica.install(rec["low"], rec["col_id"],
                                        rec["mode"], rec["column"],
                                        rec["gens"])
            for k in range(P):
                if delivered[k]:
                    for low in shipped_lows[k]:
                        pending.pop(low, None)
                    shard_logs[k] = []

    if san is not None:
        san.set_context(superstep=None, batch=None, slice=None)
    # the reported sim walls are derived from the span timeline
    cp = critical_path(tl.spans)
    reg.counter("n_columns").inc(len(queue))
    reg.counter("n_reductions").inc(n_reductions)
    reg.counter("n_pairs").inc(len(pairs))
    reg.counter("n_essential").inc(len(essentials))
    reg.gauge("stored_bytes").set(store.bytes_stored)
    reg.gauge("n_stored_columns").set(len(store.columns))
    reg.counter("n_spilled").inc(store.n_spilled)
    reg.gauge("batch_size").set(eff_batch)
    reg.counter("n_rounds").inc(n_rounds)
    reg.counter("n_expansions").inc(n_expansions)
    reg.counter("n_evictions").inc(n_evictions)
    reg.counter("n_consolidations").inc(n_consolidations)
    reg.gauge("peak_block_bytes").record_max(peak_block_bytes)
    reg.gauge("max_block_words").record_max(max_block_words)
    reg.gauge("use_kernels").set(float(use_kernels))
    reg.counter("n_device_calls").inc(n_device_calls)
    reg.counter("n_kernel_lows").inc(n_kernel_lows)
    reg.counter("n_host_xor_rounds").inc(n_host_xor_rounds)
    reg.gauge("n_shards").set(P)
    reg.counter("n_supersteps").inc(n_supersteps)
    reg.counter("n_exchange_rounds").inc(n_exchange_rounds)
    reg.counter("n_tournament_reductions").inc(n_tournament_reductions)
    reg.counter("n_sweep_probes").inc(n_sweep_probes)
    reg.counter("exchange_bytes").inc(exchange_bytes)
    if P > 1:
        reg.counter("resilience_n_faults").inc(n_faults_seen)
        reg.counter("resilience_n_shard_deaths").inc(n_shard_deaths)
        reg.counter("resilience_n_redeals").inc(n_redeals)
        reg.counter("resilience_n_straggler_sidelines").inc(n_sidelines)
        reg.counter("resilience_n_exchange_retries").inc(n_exchange_retries)
        reg.counter("resilience_n_exchange_deferrals").inc(
            n_exchange_deferrals)
        reg.counter("resilience_n_wire_corruptions").inc(n_wire_corruptions)
    for key, val in cp.items():
        reg.gauge(key).set(val)
    reg.update_from(cache.stats())
    return finalize_result(pairs, essentials, essential_ids, reg.as_stats())
