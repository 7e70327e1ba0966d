"""Shard pipeline — the I/O subsystem behind the streamed engine.

PR 3's StreamedEngine bounded device memory (O(shard + cap)) but left the
host loop fully synchronous: every routed shard of every CIVS iteration
re-gathered its rows from the DataSource (a scattered fancy-index read for
memmap sources), and nothing overlapped the device compute. This module
supplies the three layers that hide that I/O, mirroring how local-clustering
systems hide graph access behind computation — an ALID instance's ROI only
ever touches a handful of shards, which is exactly what makes a small cache
and a short prefetch ring effective:

  * ScratchShards   — the spatially-reordered shard payloads written ONCE at
                      build time to a scratch memmap, so a steady-state shard
                      read is one sequential (cap, d) slab instead of a
                      scattered per-row gather from the source;
  * ShardBundleCache— a bounded host LRU of shard bundles (points +
                      sorted_keys + perm + global_idx). Hot shards — the
                      ones every ROI intersects — skip disk entirely. Only
                      the points slab owns memory; the three metadata leaves
                      are zero-copy views of the StreamedStore arrays, so
                      the budget is charged for points bytes only;
  * ShardPipeline   — fetch orchestration (cache -> scratch -> source) plus
                      a background READER thread that walks the routed shard
                      list, pulls bundles and `device_put`s them into a
                      depth-k slot ring, so the disk read + H2D upload of
                      shard s+1 overlap the device compute of shard s.

Determinism contract: shards are CONSUMED in routed order regardless of
arrival order (the ring is a FIFO fed in routed order), bundles are
bit-identical whichever tier served them (the scratch slab and the cache
entry hold exactly the bytes `store.shard_points` would re-gather), and the
window math is shared — so the pipelined engine's labels are bit-identical
to the synchronous path and the engine stays in the parity suite
(tests/test_pipeline.py).

Device-memory bound: at most `prefetch_depth` bundles sit in the ring while
one is being consumed, so peak device bytes are
(prefetch_depth + 1) * shard_bytes + the O(cap) per-seed state — verified by
`benchmarks/mem_footprint.py`; `prefetch_depth=0` falls back to the PR 3
two-slot synchronous rotation.
"""

from __future__ import annotations

import os
import queue
import tempfile
import threading
import warnings
import zlib
from collections import OrderedDict
from typing import Iterable, Iterator, Optional

import jax
import numpy as np

from repro.core.resilience import (CorruptionError, DEFAULT_RETRY,
                                   RetryPolicy)
from repro.utils.trace import Counters, span

__all__ = ["PipelineStats", "ScratchShards", "ShardBundleCache",
           "ShardPipeline", "DEFAULT_CACHE_BYTES"]


def _crc32(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).reshape(-1).view(np.uint8))

DEFAULT_CACHE_BYTES = 256 * 2**20          # 256 MiB of hot shard payloads


class PipelineStats(Counters):
    """Per-engine counters for the read / put / compute stage breakdown.

    Stage seconds are HOST-SIDE times, accumulated where the work is issued:
    `read_s` on the host fetch (cache/scratch/source — synchronous, so this
    is true read time), `put_s` around `jax.device_put`, `compute_s` around
    the engine's chunk-fold call, and `wait_s` on the consumer side of the
    ring (time the compute loop spent starved — the I/O-bound indicator).
    Caveat: device_put and jitted calls are ASYNC dispatches, so put_s /
    compute_s measure issue cost, not device occupancy — the device-bound
    share of an engine run is wall − read_s − put_s (the XLA stream drains
    behind the host loop's sync points). With the prefetch thread on,
    read_s + put_s accrue CONCURRENTLY with the main loop, so read_s
    shrinking to ~0 while wall drops is the signature of successful overlap.
    """

    _FIELDS = ("read_s", "put_s", "compute_s", "wait_s", "cache_hits",
               "cache_misses", "cache_stale", "scratch_reads", "source_reads",
               "shards_streamed", "seed_prefetch_hits", "seed_prefetch_misses",
               "rounds_speculated", "rounds_resampled", "read_retries",
               "corruptions", "tier_fallbacks", "reader_deaths",
               "readers_abandoned")

    def report(self) -> str:
        s = self.snapshot()
        return ("pipeline stages: "
                f"read={s['read_s']:.3f}s put={s['put_s']:.3f}s "
                f"compute={s['compute_s']:.3f}s wait={s['wait_s']:.3f}s | "
                f"shards={s['shards_streamed']} "
                f"cache={s['cache_hits']}/{s['cache_hits'] + s['cache_misses']}"
                f" hit ({s['cache_stale']} stale) | "
                f"reads: scratch={s['scratch_reads']} "
                f"source={s['source_reads']} | seed-prefetch "
                f"{s['seed_prefetch_hits']}/{s['seed_prefetch_hits'] + s['seed_prefetch_misses']}"
                f" hit, rounds speculated={s['rounds_speculated']} "
                f"resampled={s['rounds_resampled']} | resilience: "
                f"retries={s['read_retries']} corrupt={s['corruptions']} "
                f"fallbacks={s['tier_fallbacks']} "
                f"reader_deaths={s['reader_deaths']} "
                f"abandoned={s['readers_abandoned']}")


class ScratchShards:
    """(S, cap, d) f32 scratch memmap of the spatially-reordered payloads.

    `build_store_streamed` writes each shard's rows exactly once (zero-padded
    to cap, the same bytes `shard_points` would re-gather), after which a
    shard read is one contiguous slab — sequential disk I/O instead of a
    scattered per-row gather through the source. The file is unlinked by
    `close()` (invoked from the engine's teardown).

    Integrity: every `write` records a crc32 of the FULL zero-padded slab,
    and `read(verify=True)` checks it — a flipped bit on the scratch tier
    surfaces as `CorruptionError` instead of silently poisoning a fit. The
    pipeline handles the error by refetching from the source (generation 0
    shards only — a mutated shard's scratch slab is the sole owner of its
    bytes). `corrupt()` is the test/chaos hook: it tampers the slab without
    updating the checksum.
    """

    def __init__(self, path: str, mm: np.memmap):
        self.path = path
        self._mm = mm
        self._crc: dict[int, int] = {}

    @classmethod
    def create(cls, n_shards: int, cap: int, dim: int,
               scratch_dir: str = "") -> "ScratchShards":
        """Open a fresh zero-filled scratch file. Empty `scratch_dir` uses
        the system temp dir; the file name is unique per store build."""
        directory = scratch_dir or None
        if directory:
            os.makedirs(directory, exist_ok=True)
        fd, path = tempfile.mkstemp(suffix=".npy", prefix="alid_scratch_",
                                    dir=directory)
        os.close(fd)
        mm = np.lib.format.open_memmap(path, mode="w+", dtype=np.float32,
                                       shape=(n_shards, cap, dim))
        return cls(path, mm)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self._mm.shape)) * 4

    def write(self, s: int, rows: np.ndarray) -> None:
        self._mm[s, :rows.shape[0]] = rows
        # checksum the full padded slab (what read() returns), so a verify
        # covers the zero tail as well as the written rows
        self._crc[int(s)] = _crc32(np.asarray(self._mm[s]))

    def read(self, s: int, verify: bool = True) -> np.ndarray:
        """One sequential (cap, d) slab read, returned as an OWNED array so
        callers (the LRU, device_put) never hold views into the file.
        `verify=True` checks the slab against the crc recorded at write
        time and raises `CorruptionError` on mismatch."""
        out = np.array(self._mm[s], np.float32)
        if verify:
            want = self._crc.get(int(s))
            if want is not None and _crc32(out) != want:
                raise CorruptionError(
                    f"scratch slab for shard {int(s)} failed its checksum")
        return out

    def corrupt(self, s: int) -> None:
        """Chaos hook: flip one mantissa bit in shard `s`'s slab WITHOUT
        updating the recorded checksum — the next verified read must detect
        it (an XOR changes the bytes for ANY float value, unlike += 1.0
        which is absorbed above 2**24)."""
        v = np.array(self._mm[s, 0, 0], np.float32)
        self._mm[s, 0, 0] = (v.view(np.uint32) ^ np.uint32(1)).view(
            np.float32)

    def flush(self) -> None:
        self._mm.flush()

    def close(self) -> None:
        """Drop the mapping and unlink the backing file (idempotent)."""
        if self._mm is not None:
            del self._mm
            self._mm = None
        if self.path is not None:
            try:
                os.unlink(self.path)
            except OSError:
                pass
            self.path = None


class ShardBundleCache:
    """Bounded host LRU of shard bundles keyed by shard id.

    A bundle is the 4-tuple (points, sorted_keys, perm, global_idx) exactly
    as the engine device_puts it. Only `points` owns bytes (the metadata
    leaves are views of the store's resident arrays), so the budget charges
    points bytes; an entry larger than the whole budget is simply never
    cached (the forced-eviction degenerate the tests pin). Hits return the
    SAME arrays that were stored — bit-identical by construction.

    Each entry remembers the shard GENERATION it was filled at (the store's
    per-shard mutation counter, `store.generations`; 0 for immutable
    stores). A probe with a newer generation drops the entry and misses —
    an online `update_shard_points` can therefore never be shadowed by a
    stale cached bundle. `stale_evictions` counts those drops.

    Each entry also carries a crc32 of its points bytes, recorded at `put`
    and (with `verify` on) re-checked at `get`: a corrupted resident bundle
    drops + misses (`corrupt_evictions`) instead of serving poisoned bytes,
    and the fetch falls through to the scratch/source tiers below.
    """

    def __init__(self, budget_bytes: int, verify: bool = True):
        self.budget = int(budget_bytes)
        self.verify = bool(verify)
        self._entries: OrderedDict[int, tuple[int, int, tuple]] = OrderedDict()
        self._bytes = 0
        self.stale_evictions = 0
        self.corrupt_evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def nbytes(self) -> int:
        return self._bytes

    def _drop(self, s: int) -> None:
        _, _, old = self._entries.pop(s)
        self._bytes -= int(old[0].nbytes)

    def get(self, s: int, gen: int = 0):
        entry = self._entries.get(s)
        if entry is None:
            return None
        egen, ecrc, bundle = entry
        if egen != gen:                     # filled before the last mutation
            self._drop(s)
            self.stale_evictions += 1
            return None
        if self.verify and _crc32(bundle[0]) != ecrc:
            self._drop(s)                   # poisoned resident bytes
            self.corrupt_evictions += 1
            return None
        self._entries.move_to_end(s)
        return bundle

    def put(self, s: int, bundle: tuple, gen: int = 0) -> None:
        cost = int(bundle[0].nbytes)
        if cost > self.budget:
            return                          # one shard exceeds the budget
        if s in self._entries:
            if self._entries[s][0] == gen:
                self._entries.move_to_end(s)
                return
            self._drop(s)                   # replace the stale entry
            self.stale_evictions += 1
        while self._bytes + cost > self.budget and self._entries:
            _, (_, _, old) = self._entries.popitem(last=False)
            self._bytes -= int(old[0].nbytes)
        self._entries[s] = (gen, _crc32(bundle[0]), bundle)
        self._bytes += cost

    def clear(self) -> None:
        self._entries.clear()
        self._bytes = 0


class _ProducerError:
    def __init__(self, exc: BaseException):
        self.exc = exc


class ShardPipeline:
    """Fetch + prefetch orchestrator over a StreamedStore-shaped object.

    `store` must expose `shard_points(s)` (scratch-aware), plus the host
    metadata arrays `sorted_keys` / `perm` / `global_idx` with a leading S
    axis. `stream(routed)` yields `(pos, s, device_bundle)` strictly in
    routed order:

      * prefetch_depth == 0 — the PR 3 synchronous path: fetch + device_put
        inline into two alternating slots (upload of s+1 still overlaps the
        probe of s via device_put's async copy);
      * prefetch_depth >= 1 — a reader thread walks the routed list, pulls
        bundles (cache -> scratch -> source) and device_puts them into a
        bounded FIFO ring of `prefetch_depth` slots; the consumer blocks on
        the ring head, so consumption order — and therefore every carry
        fold — is identical to the synchronous path.
    """

    def __init__(self, store, cache_bytes: int = 0, prefetch_depth: int = 0,
                 stats: Optional[PipelineStats] = None,
                 retry: RetryPolicy = DEFAULT_RETRY,
                 verify_checksums: bool = True, faults=None,
                 join_timeout: float = 5.0):
        self.store = store
        self.depth = max(0, int(prefetch_depth))
        self.verify_checksums = bool(verify_checksums)
        self.cache = (ShardBundleCache(cache_bytes, verify=verify_checksums)
                      if cache_bytes > 0 else None)
        self.stats = stats if stats is not None else PipelineStats()
        self.retry = retry if retry is not None else RetryPolicy(attempts=1)
        # fault-injection hooks (core.resilience.PipelineFaults) — None in
        # production; installed by chaos tests / run_palid --inject-faults
        self.faults = faults
        self.join_timeout = float(join_timeout)
        self._slots: list = [None, None]    # sync-mode double buffer
        self._slot = 0

    # -- host fetch tier: cache -> scratch -> source -----------------------
    def _count_retry(self, attempt, exc) -> None:
        self.stats.add("read_retries")

    def _read_points(self, s: int, gen: int) -> np.ndarray:
        """Tiered shard-payload read below the cache: scratch slab (verified
        + retried) first, source re-gather as the fallback. Transient
        `OSError`s retry under the policy; a checksum failure falls back ONE
        tier (re-reading corrupt bytes cannot help) — unless the shard was
        mutated in place, in which case the scratch slab is the sole owner
        of its bytes and the corruption is surfaced."""
        store = self.store
        scratch = getattr(store, "scratch", None)
        if scratch is not None:
            try:
                pts = self.retry.call(scratch.read, s,
                                      verify=self.verify_checksums,
                                      on_retry=self._count_retry)
                self.stats.add("scratch_reads")
                return pts
            except CorruptionError:
                self.stats.add("corruptions")
                if gen > 0:
                    raise CorruptionError(
                        f"scratch slab for shard {s} is corrupt at "
                        f"generation {gen}: the shard was mutated in place "
                        "(update_shard_points), so the source holds "
                        "pre-mutation bytes and no clean tier remains")
        gather = getattr(store, "gather_shard_points", store.shard_points)
        pts = self.retry.call(gather, s, on_retry=self._count_retry)
        self.stats.add("source_reads")
        if scratch is not None:
            # heal the corrupt slab with the authoritative source bytes so
            # the next read is a clean sequential slab again
            self.stats.add("tier_fallbacks")
            scratch.write(s, pts)
        return pts

    def fetch_bundle(self, s: int) -> tuple:
        stats = self.stats
        s = int(s)
        gens = getattr(self.store, "generations", None)
        gen = int(gens[s]) if gens is not None else 0
        if self.faults is not None:
            self.faults.on_fetch(self, s)
        if self.cache is not None:
            stale0 = self.cache.stale_evictions
            corrupt0 = self.cache.corrupt_evictions
            bundle = self.cache.get(s, gen=gen)
            if bundle is not None:
                stats.add("cache_hits")
                return bundle
            stats.add("cache_misses")
            if self.cache.stale_evictions > stale0:
                stats.add("cache_stale")
            if self.cache.corrupt_evictions > corrupt0:
                stats.add("corruptions")
                stats.add("tier_fallbacks")
        with span("pipeline.read", stats, "read_s"):
            pts = self._read_points(s, gen)
        bundle = (pts, self.store.sorted_keys[s], self.store.perm[s],
                  self.store.global_idx[s])
        if self.cache is not None:
            self.cache.put(s, bundle, gen=gen)
        return bundle

    def _device_put(self, bundle: tuple):
        with span("pipeline.put", self.stats, "put_s"):
            return jax.device_put(bundle)

    # -- streaming ---------------------------------------------------------
    def stream(self, routed: Iterable[int]) -> Iterator[tuple]:
        routed = [int(s) for s in routed]
        self.stats.add("shards_streamed", len(routed))
        if self.depth <= 0:
            yield from self._stream_sync(routed)
        else:
            yield from self._stream_prefetched(routed)

    def _stream_sync(self, routed) -> Iterator[tuple]:
        for pos, s in enumerate(routed):
            dev = self._device_put(self.fetch_bundle(s))
            # two alternating slots: overwriting drops the 2-generations-old
            # buffer, so at most two bundles are device-live (PR 3 behavior)
            self._slot ^= 1
            self._slots[self._slot] = dev
            yield pos, s, dev

    def _stream_prefetched(self, routed) -> Iterator[tuple]:
        # the ring itself is unbounded; `slots` bounds how many bundles are
        # produced-but-unconsumed. The reader RESERVES a slot before it
        # fetches or uploads, so at most `depth` bundles sit device-live in
        # the ring while the consumer holds one more — the documented
        # (depth+1)·shard peak, with no transient (depth+2)-th bundle parked
        # in the reader's hand behind a full queue
        ring: queue.Queue = queue.Queue()
        slots = threading.Semaphore(self.depth)
        cancel = threading.Event()

        def acquire_cancellable() -> bool:
            # bounded wait that gives up if the consumer is gone — otherwise
            # an aborted compute loop would leave the reader blocked forever
            while not cancel.is_set():
                if slots.acquire(timeout=0.05):
                    return True
            return False

        def producer():
            try:
                for s in routed:
                    if not acquire_cancellable():
                        return
                    if self.faults is not None:
                        self.faults.on_produce()
                    ring.put(self._device_put(self.fetch_bundle(s)))
            except BaseException as exc:    # surfaced on the consumer side
                ring.put(_ProducerError(exc))

        reader = threading.Thread(target=producer, daemon=True,
                                  name="alid-shard-prefetch")
        reader.start()
        try:
            for pos, s in enumerate(routed):
                with span("pipeline.wait", self.stats, "wait_s"):
                    item = ring.get()
                if isinstance(item, _ProducerError):
                    # the reader died before producing bundle `pos` (its
                    # error lands in FIFO order after its last good bundle).
                    # A dead reader must not kill the fit: finish the routed
                    # list INLINE, in order — consumption order is unchanged,
                    # so the carry folds (and the labels) stay bit-identical.
                    # A genuine per-shard error (bad index, exhausted
                    # retries) re-raises right here when the inline fetch
                    # hits the same shard — fallback never masks bugs.
                    self.stats.add("reader_deaths")
                    for pos2 in range(pos, len(routed)):
                        dev = self._device_put(
                            self.fetch_bundle(routed[pos2]))
                        self._slot ^= 1
                        self._slots[self._slot] = dev
                        yield pos2, routed[pos2], dev
                    return
                # the popped bundle is now the consumer-held "+1"; free its
                # ring slot so the reader can run one further ahead
                slots.release()
                yield pos, s, item
        finally:
            cancel.set()
            reader.join(self.join_timeout)
            if reader.is_alive():
                # a source read stuck past the cancel flag: abandoning the
                # daemon thread (bounded join) beats hanging fit teardown
                # forever — the satellite fix for the unbounded join
                self.stats.add("readers_abandoned")
                warnings.warn(
                    "alid-shard-prefetch reader did not exit within "
                    f"{self.join_timeout}s of cancellation; abandoning the "
                    "daemon thread", RuntimeWarning)

    def release(self) -> None:
        """Drop every reference the pipeline holds (device slots + host
        cache) — the engine's close() path."""
        self._slots = [None, None]
        if self.cache is not None:
            self.cache.clear()
