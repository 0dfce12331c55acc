"""Block-allocated KV cache: a bounded pool, per-request block tables.

The serving engine's KV memory is the scarce resource admission control
reasons about. Instead of one contiguous ``(lanes, max_seq_len, ...)``
cache sized for every lane's worst case, the cache is a POOL of
fixed-size blocks (``block_size`` token slots each, the vLLM paged-KV
idea at allocation granularity):

- each layer's keys and values live as ``(num_blocks, block_size,
  h_kv * head_dim)`` arrays (a block's token slots as rows, kv head g in
  lanes ``[g * head_dim, (g + 1) * head_dim)``: one block with all its
  heads is one contiguous, lane-dense copy) — ONE donated pytree threaded
  through the compiled prefill/decode steps, so steady-state serving
  reuses the same HBM in place;
- each admitted request owns a BLOCK TABLE row: lane-local block ``j``
  maps to pool block ``table[j]``. Unreserved entries carry the
  out-of-range sentinel ``num_blocks``. The sentinel rule: CLIPPED ON
  READ (decode attention clips every entry into the pool before it
  addresses memory, so a bad table reads another block's bytes and never
  faults), DROPPED ON WRITE (the prefill scatter and the decode step's
  one-slot write use ``mode="drop"``: an inactive lane, whose table is all
  sentinel, writes nothing), MASKED BY LENGTH (a lane attends positions
  ``[0, position]`` only, and a request's reservation always covers them,
  so no sentinel entry lies inside a sound lane's length);
- the host-side :class:`BlockAllocator` hands out blocks atomically
  (all-or-nothing) and admission reserves a request's WORST CASE
  (``ceil((prompt+max_new)/block_size)``, plus the prefill bucket's
  span) up front — conservative by design: a mid-decode request can
  then never deadlock on pool memory, so no preemption/eviction
  machinery is needed to stay safe, and "not enough blocks" is a clean
  queue-wait the admission TTFT estimate absorbs. The cost is bucket-
  granularity over-reservation, documented in docs/serving.md.

Decode reads the pool IN PLACE. The model's attention layer
(transformer/layer.py) picks its decode branch by the KIND of cache it is
handed: the engine hands every attention module the paged kind —
``key_pool`` / ``value_pool`` (the pool leaves themselves), ``block_table``
(lanes, max_blocks_per_lane) and ``cache_index`` (per-lane positions) —
and the layer writes each lane's new token into its one slot and attends
by table and length (``ops.paged_decode_attention``); nothing is gathered
into a contiguous window and nothing is scattered back. Prefill still runs
the model's own contiguous cache for one prompt and scatters its blocks
into the pool. :class:`CacheSpec` is the bridge and the one place that
says how the model's cache maps to the pool — it records, from one
``jax.eval_shape`` of a prefill, which cache leaves are K/V payload and
which are the scalar ``cache_index`` bookkeeping, builds the paged cache
dict and reads the updated pool back out of it, and refuses cache layouts
it does not understand (context-parallel ``prompt_len_local``, future
variables) rather than guessing.
"""

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["BlockAllocator", "CacheSpec", "blocks_needed"]


def blocks_needed(total_tokens: int, block_size: int) -> int:
    """ceil(total_tokens / block_size) — the reservation arithmetic."""
    return -(-int(total_tokens) // int(block_size))


class BlockAllocator:
    """Host-side free-list over the KV pool's ``num_blocks`` blocks.

    ``alloc(n)`` is atomic: it returns ``n`` distinct block ids or None
    (never a partial grant — a half-reserved request would be exactly
    the deadlock the conservative reservation exists to prevent).
    ``free(ids)`` returns blocks to the pool; double-frees and unknown
    ids are refused loudly (a double-free means two requests think they
    own one block — the corruption must not be silent). jax-free.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._allocated: set = set()
        #: high-water mark of simultaneously-booked blocks over the
        #: allocator's lifetime — the serving half of the HBM x-ray's
        #: footprint accounting (``kv_pool_peak_blocks`` bench twin)
        self.peak_used_blocks = 0

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - len(self._free)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> Optional[Tuple[int, ...]]:
        """``n`` distinct block ids, or None when the pool cannot cover
        the request (all-or-nothing)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            return None
        ids = tuple(self._free.pop() for _ in range(n))
        self._allocated.update(ids)
        self.peak_used_blocks = max(self.peak_used_blocks,
                                    self.used_blocks)
        return ids

    def free(self, ids) -> None:
        for b in ids:
            b = int(b)
            if b not in self._allocated:
                raise ValueError(
                    f"freeing block {b} that is not allocated — a "
                    f"double-free means two requests claimed one block"
                )
            self._allocated.discard(b)
            self._free.append(b)


@dataclasses.dataclass(frozen=True)
class CacheLeaf:
    """One leaf of the model's cache collection, classified."""

    path: Tuple[str, ...]        # nested-dict key path
    kind: str                    # "kv" | "index"
    shape: Tuple[int, ...]       # the PREFILL leaf shape (b=1 layout)
    dtype: Any


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """The bridge between the model's cache pytree and the block pool.

    Built once from an abstract prefill (:meth:`from_cache_shapes`);
    thereafter :meth:`pool_shapes` names the pool leaves (keyed by the
    joined cache path — a flat dict is the donated pytree), prefill turns
    the model's contiguous K/V into pool blocks (:meth:`kv_from_cache`,
    :meth:`to_blocks`), and decode hands the model the pool itself as a
    paged cache (:meth:`paged_cache`) and takes the updated pool back
    (:meth:`pool_from_cache`).
    """

    kv_leaves: Tuple[CacheLeaf, ...]
    index_leaves: Tuple[CacheLeaf, ...]

    @staticmethod
    def _classify(path: Tuple[str, ...], shape, dtype) -> CacheLeaf:
        name = path[-1]
        if name in ("cached_key", "cached_value"):
            if len(shape) != 4 or shape[0] != 1:
                raise ValueError(
                    f"cache leaf {'/'.join(path)} has shape {shape}; the "
                    f"serving pool understands the (1, h_kv, slots, "
                    f"head_dim) single-sequence prefill layout only"
                )
            return CacheLeaf(path, "kv", tuple(shape), dtype)
        if name == "cache_index":
            return CacheLeaf(path, "index", tuple(shape), dtype)
        raise ValueError(
            f"unrecognized cache variable {'/'.join(path)} — the serving "
            f"engine reuses the model's cache layout and refuses layouts "
            f"it does not understand (context-parallel decode caches "
            f"carry prompt_len_local; serve with cp disabled)"
        )

    @classmethod
    def from_cache_shapes(cls, cache_shapes: Dict[str, Any]) -> "CacheSpec":
        """Build from the ``{"cache": ...}`` ShapeDtypeStruct pytree of
        an abstract (``jax.eval_shape``) single-sequence prefill."""
        kv: List[CacheLeaf] = []
        idx: List[CacheLeaf] = []

        def walk(node, path):
            if isinstance(node, dict):
                for k in sorted(node):
                    walk(node[k], path + (str(k),))
                return
            leaf = cls._classify(path, tuple(node.shape), node.dtype)
            (kv if leaf.kind == "kv" else idx).append(leaf)

        walk(cache_shapes, ())
        if not kv:
            raise ValueError(
                "no cached_key/cached_value leaves found — does the model "
                "support cache_len= prefill? (models.generate contract)"
            )
        return cls(kv_leaves=tuple(kv), index_leaves=tuple(idx))

    @staticmethod
    def key(path: Tuple[str, ...]) -> str:
        return "/".join(path)

    #: a prefill K/V leaf's name -> the paged cache variable the attention
    #: layer reads the pool under (transformer/layer.py's paged branch)
    PAGED_NAMES = {"cached_key": "key_pool", "cached_value": "value_pool"}

    def pool_shapes(self, num_blocks: int,
                    block_size: int) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """``{pool_key: ((num_blocks, block_size, h_kv * hd), dtype)}``."""
        out = {}
        for leaf in self.kv_leaves:
            _, h_kv, _, hd = leaf.shape
            out[self.key(leaf.path)] = (
                (int(num_blocks), int(block_size), h_kv * hd), leaf.dtype
            )
        return out

    @staticmethod
    def to_blocks(leaf, block_size: int):
        """A prefill's contiguous K or V, (1, h_kv, P, hd), as P /
        block_size pool blocks (P / block_size, block_size, h_kv * hd)."""
        _, h_kv, P, hd = leaf.shape
        return leaf[0].transpose(1, 0, 2).reshape(
            P // block_size, block_size, h_kv * hd)

    def paged_cache(self, pool: Dict[str, Any], tables, positions) -> dict:
        """The cache dict of a decode step over the pool: beside every
        attention module's two pool leaves, the lanes' block tables and
        positions (the same two arrays for every layer)."""
        cache: dict = {}
        for leaf in self.kv_leaves:
            node = cache
            for k in leaf.path[:-1]:
                node = node.setdefault(k, {})
            node[self.PAGED_NAMES[leaf.path[-1]]] = pool[self.key(leaf.path)]
            node["block_table"] = tables
            node["cache_index"] = positions
        return cache

    def pool_from_cache(self, cache: dict) -> Dict[str, Any]:
        """The pool leaves of a (decode-updated) paged cache dict, keyed
        like :meth:`pool_shapes`."""
        return self._leaves_of(cache, self.PAGED_NAMES)

    def kv_from_cache(self, cache: dict) -> Dict[str, Any]:
        """The K/V leaves of a prefill's contiguous cache dict, keyed like
        :meth:`pool_shapes`."""
        return self._leaves_of(cache, {})

    def _leaves_of(self, cache: dict, rename: Dict[str, str]) -> Dict[str, Any]:
        out = {}
        for leaf in self.kv_leaves:
            node = cache
            for k in leaf.path[:-1]:
                node = node[k]
            name = leaf.path[-1]
            out[self.key(leaf.path)] = node[rename.get(name, name)]
        return out
