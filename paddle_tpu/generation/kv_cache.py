"""Static-shape KV cache for XLA-friendly autoregressive decoding.

Reference parity: PaddleNLP generation caches (paddlenlp/transformers/
generation_utils.py `past_key_values`) and the fused block-attention
cache layout of paddle/phi/kernels/fusion/gpu (block_multihead_attention).

TPU-native design: instead of concatenating K/V each step (dynamic shapes
— retrace/recompile every token), the cache is a preallocated
[B, max_len, n_kv_heads, head_dim] buffer per layer written in place with
`lax.dynamic_update_slice` at a traced position. The whole decode loop
then compiles to ONE XLA program (`lax.scan` over steps) with static
shapes, which is the canonical TPU serving pattern.

The serving side lives here too: `PagedKVPool` (refcounted page
allocator over the device-resident paged K/V arrays, with on-device
copy-on-write) and `PrefixCache` (hash-trie over page-aligned prompt
prefixes so repeated system prompts skip prefill — cf. vLLM automatic
prefix caching / SGLang RadixAttention), consumed by
inference.ContinuousBatchingPredictor (docs/SERVING.md). A model whose
layers are not all attention declares what each layer keeps
(`LayerCache`, from the model's `cache_layout()`): K/V pages for the
attention layers (or, for a latent-attention layer, ONE row a token
for all heads in place of K and V: `LatentCacheEntry`), and for the
recurrent ones a row a slot in `StatePool`, constant in the context's
length (`StateCacheEntry`).
"""
from __future__ import annotations

from typing import List, NamedTuple


class StaticCacheEntry(NamedTuple):
    """Per-layer cache entry: full K/V buffers plus the write position.

    `k`/`v` are Tensors (or traced arrays) of shape
    [batch, max_len, n_kv_heads, head_dim]; `pos` is a scalar int32
    Tensor — the slot where this step's keys/values are written.
    """
    k: object
    v: object
    pos: object


class StaticKVCache:
    """A list of per-layer StaticCacheEntry, passed as `past_key_values`."""

    def __init__(self, entries: List[StaticCacheEntry]):
        self.entries = entries

    def __len__(self):
        return len(self.entries)

    def __getitem__(self, i):
        return self.entries[i]

    def __iter__(self):
        return iter(self.entries)


def static_cache_update(entry: StaticCacheEntry, k, v):
    """Write K/V ([B, s, H, D] Tensors) into the static cache at
    entry.pos (lax.dynamic_update_slice) — THE cache-write contract,
    shared by every model family's attention."""
    import jax
    import jax.numpy as jnp
    from ..ops._dispatch import apply

    def upd(cache, new, p):
        z = jnp.int32(0)
        return jax.lax.dynamic_update_slice(
            cache, new.astype(cache.dtype),
            (z, p.astype(jnp.int32), z, z))

    k_new = apply(upd, entry.k, k, entry.pos, _name="kv_cache_update")
    v_new = apply(upd, entry.v, v, entry.pos, _name="kv_cache_update")
    return k_new, v_new, StaticCacheEntry(k_new, v_new, entry.pos)


class LayerCache(NamedTuple):
    """What one decoder layer keeps between steps, as its model's
    `cache_layout()` declares it. kind "kv": `shape` = (n_kv_heads,
    head_dim), paged; a layer whose attention selects its keys by a
    learned indexer also declares `index_dim`, the width of the one
    index key a token it keeps in a third paged array under the same
    page ids. kind "latent": `shape` = (width,), ONE row a token for
    all heads (multi-head latent attention: the compressed latent and
    the shared rotated key part), paged under the same page ids in one
    array INSTEAD of K and V; with `index_dim`, an index key a token
    beside the rows, as a "kv" layer's (the indexer selects among the
    latent rows). kind "state": `shape` = ((d_conv - 1,
    channels), (heads, head_dim, d_state)), one row a slot (a Mamba-2
    mixer's SSM state or a delta-rule mixer's [heads, d_k, d_v]
    matrix: the pool holds either as it is)."""
    kind: str
    shape: tuple
    index_dim: int = 0


class Drafter(NamedTuple):
    """What a model that drafts for itself declares to the serve loop
    (`model.drafter()`, beside `cache_layout()`): `depth` tokens drafted
    a tick by a multi-token-prediction module whose own layer keeps its
    rows in entry `layer` of `cache_layout()`. The trunk's forward
    passes that entry through; `model.draft(hidden, next_ids,
    position_ids, entry, valid)` advances it."""
    depth: int
    layer: int


class LayerCaches(list):
    """The per-layer caches a forward pass returns, with `counters`:
    small device vectors the model summed over its layers ({name:
    int32 array}), brought down with the step's tokens. A model with a
    drafter also gives `hidden`, the last layer's output before the
    final norm at every position of the step (what its drafter reads),
    and a prefill gives `draft`, the first drafted token of each row."""

    def __init__(self, caches, counters=None, hidden=None, draft=None):
        super().__init__(caches)
        self.counters = counters or {}
        self.hidden = hidden
        self.draft = draft


class StatePool:
    """Per-slot state of the recurrent layers, beside the pages: for
    each such layer a convolution window and a float32 state (`ssm`: a
    Mamba-2 mixer's [heads, head_dim, d_state], a delta-rule mixer's
    [heads, d_k, d_v]), one row a slot and one row more. A slot's row is written whole by its
    prefill, so a reused slot owes nothing to its last tenant; there is
    nothing to allocate, share or reclaim."""

    def __init__(self, n_layers, slots, conv_shape, ssm_shape,
                 conv_dtype="float32", device=None):
        import jax.numpy as jnp
        self.slots = int(slots)
        self.conv = [jnp.zeros((slots + 1,) + tuple(conv_shape), conv_dtype,
                               device=device) for _ in range(n_layers)]
        self.ssm = [jnp.zeros((slots + 1,) + tuple(ssm_shape), jnp.float32,
                              device=device) for _ in range(n_layers)]

    @property
    def nbytes(self):
        return int(sum(a.nbytes for a in self.conv + self.ssm))


class PagedKVPool:
    """Host-side page allocator over the device-resident paged KV arrays
    (reference parity: the block manager of PaddleNLP's serving /
    vLLM's BlockSpaceManager). Pages are shared by all slots; the free
    list and reference counts live on host, the page contents on device.

    Pages are refcounted so prompt prefixes can be shared across
    requests (PrefixCache): `alloc` hands out pages at refcount 1,
    `retain`/`release` adjust the count, and a page returns to the free
    list only when its count reaches zero. `copy_into` implements
    copy-on-write: a request that must append into a shared page first
    copies its contents into an exclusively-owned page on device.

    An optional `reclaimer` (the PrefixCache) is consulted when `alloc`
    runs short: cached-but-unused pages are dropped to satisfy the
    request, and `free_count` reports them as available. The pool keeps
    their count itself (`cache_hold`/`cache_drop` say which pages the
    trie holds, `retain`/`release` see a reference move between 1 and
    2), so `free_count` walks nothing.
    """

    def __init__(self, n_layers, num_pages, page_size, n_kv_heads,
                 head_dim, dtype="float32", mesh=None, device=None,
                 index_dim=0, latent_dim=0, latent_layers=()):
        import jax.numpy as jnp
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        shape = (num_pages, page_size, n_kv_heads, head_dim)
        # a latent layer (`latent_layers`: its places among the
        # `n_layers` paged ones) keeps ONE array, one row a token for
        # all heads, in place of K and V: it stands in `k` (the row is
        # the layer's key, and its first numbers the value) and `v`
        # holds None there. Same page ids, allocator, trash page and
        # copy-on-write; rows on whole 128-lane rows, zeros past
        # `latent_dim`, for the reason the index keys are
        latent_shape = (num_pages, page_size,
                        -(-int(latent_dim) // 128) * 128)
        self.latent_layers = frozenset(int(i) for i in latent_layers)
        # `device` commits an unsharded pool to one device (a router
        # replica's own); None leaves it on the default device
        self.k = [jnp.zeros(latent_shape if i in self.latent_layers
                            else shape, dtype, device=device)
                  for i in range(n_layers)]
        self.v = [None if i in self.latent_layers
                  else jnp.zeros(shape, dtype, device=device)
                  for i in range(n_layers)]
        # layers with an indexer: one index key a token, a third array a
        # layer under the SAME page ids, so the allocator, the trash
        # page and copy-on-write cover it with no table of its own. A
        # key lies on whole 128-lane rows, zeros past `index_dim`: the
        # TPU tiles the last axis to 128 lanes whatever it is told (a
        # [.., 64] bfloat16 array occupies the same bytes), and a row
        # the kernels can take as it lies spares a copy of the pool
        lanes = -(-int(index_dim) // 128) * 128
        self.index = [jnp.zeros((num_pages, page_size, lanes), dtype,
                                device=device)
                      for _ in range(n_layers if index_dim else 0)]
        # tensor-parallel serving: pages shard over the KV-head axis of
        # a 'model' mesh (the paged kernels are head-parallel by
        # construction, so every program variant composes). The host-
        # side bookkeeping — free list, refcounts, page ids — is
        # layout-blind and identical either way; only the device
        # placement of the page arrays changes.
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = str(dtype)
        self.kv_sharding = None
        self.topology = "single"
        if mesh is not None and mesh.shape.get("model", 1) > 1:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            tp = int(mesh.shape["model"])
            if n_kv_heads % tp:
                raise ValueError(
                    f"cannot shard {n_kv_heads} KV heads over "
                    f"model={tp} (head count must divide)")
            self.kv_sharding = NamedSharding(
                mesh, PartitionSpec(None, None, "model", None))
            if self.latent_layers:
                raise ValueError("latent pages have no head axis to shard "
                                 "over 'model'")
            self.k = [jax.device_put(a, self.kv_sharding) for a in self.k]
            self.v = [jax.device_put(a, self.kv_sharding) for a in self.v]
            self.topology = f"tp{tp}"
        self._free = list(range(num_pages))
        self._refs = {}
        self.reclaimer = None
        # page -> references the prefix trie holds on it, and how many
        # of those pages have the trie as their ONLY holder (refcount 1)
        self._cache_held = {}
        self._reclaimable = 0

    @property
    def latent(self):
        """The latent layers' page arrays, in layer order."""
        return [self.k[i] for i in sorted(self.latent_layers)]

    @property
    def free_count(self):
        """Pages obtainable right now: the free list plus cache-held
        pages the reclaimer would drop on demand (what
        `PrefixCache.reclaimable_count` finds by walking the trie,
        kept as a count)."""
        extra = self._reclaimable if self.reclaimer is not None else 0
        return len(self._free) + extra

    def alloc(self, n):
        """n page ids (each at refcount 1), or None if the pool can't
        satisfy the request even after reclaiming cached pages."""
        if n > len(self._free) and self.reclaimer is not None:
            self.reclaimer.reclaim(self, n - len(self._free))
        if n > len(self._free):
            return None
        got, self._free = self._free[:n], self._free[n:]
        for p in got:
            self._refs[p] = 1
        return got

    def retain(self, ids):
        for p in ids:
            c = self._refs.get(p, 0) + 1
            self._refs[p] = c
            if c == 2 and p in self._cache_held:
                self._reclaimable -= 1     # a request pins a cached page

    def release(self, ids):
        for p in ids:
            c = self._refs.get(p, 1) - 1
            if c <= 0:
                self._refs.pop(p, None)
                self._free.append(p)
            else:
                self._refs[p] = c
                if c == 1 and p in self._cache_held:
                    self._reclaimable += 1     # only the trie is left

    def cache_hold(self, pid):
        """The prefix trie takes a reference on page `pid`."""
        self.retain([pid])
        self._cache_held[pid] = self._cache_held.get(pid, 0) + 1
        if self._refs[pid] == 1:
            self._reclaimable += 1

    def cache_drop(self, pid):
        """The prefix trie gives up one reference on page `pid`."""
        if self._refs.get(pid) == 1:
            self._reclaimable -= 1
        held = self._cache_held.get(pid, 0) - 1
        if held > 0:
            self._cache_held[pid] = held
        else:
            self._cache_held.pop(pid, None)
        self.release([pid])

    def ref_count(self, pid):
        return self._refs.get(pid, 0)

    def copy_into(self, src, dst):
        """Device-side page copy (all layers), no host round-trip —
        the write half of copy-on-write. One jitted program updates
        every layer; with buffer donation (non-CPU backends) the cost
        is one page of traffic, not a pool copy per layer."""
        import jax
        import numpy as np
        if not hasattr(self, "_copy_jit"):
            def _copy(pools, s, d):
                return [[None if a is None else a.at[d].set(a[s])
                         for a in pool] for pool in pools]
            dn = (0,) if jax.default_backend() != "cpu" else ()
            self._copy_jit = jax.jit(_copy, donate_argnums=dn)
        self.k, self.v, self.index = self._copy_jit(
            [self.k, self.v, self.index], np.int32(src), np.int32(dst))

    # ------------------------------------------------ disaggregation --
    def export_span(self, prompt, page_ids, next_token=None):
        """Serialize the pages holding `prompt`'s K/V into a
        transferable :class:`KVPageSpan` (the prefill→decode handoff of
        docs/SERVING.md "Disaggregated prefill/decode"). `page_ids` is
        the request's own block-table prefix — ``ceil(len(prompt)/page)``
        entries; `next_token` is the greedy first token the prefill side
        resolved, carried so the decode side can resume without a
        suffix prefill.

        Transport is serialized host memory for now; the span payload
        is plain per-layer numpy, so an ICI/DMA device-to-device path
        can replace the gather/scatter endpoints without changing the
        interface. TP head-sharded pools export the UNSHARDED view (the
        host gather assembles shards); the import side reshards to its
        own layout and records a fallback when layouts differ.
        """
        import numpy as np
        page = self.page_size
        n = len(prompt)
        n_full = n // page
        partial_len = n % page
        want = n_full + (1 if partial_len else 0)
        if want == 0 or len(page_ids) < want:
            raise ValueError(
                f"export_span: need {want} pages for a {n}-token prompt, "
                f"got {len(page_ids)} page ids")
        sel = np.asarray(list(page_ids[:want]), dtype=np.int32)  # graft-lint: ok[GL102] host-side page-id list, no device transfer
        # host gather: np.array on a (possibly sharded) device array
        # fetches and assembles shards — the designed sync point of the
        # serialized-host transport.
        k_pages = [np.array(k[sel]) for k in self.k]   # graft-lint: ok[GL102] designed host-transfer gather of the KV handoff span
        v_pages = [np.array(v[sel]) for v in self.v]   # graft-lint: ok[GL102] designed host-transfer gather of the KV handoff span
        if partial_len:
            # zero the stale tail of the trailing partial page so the
            # checksum (and bitwise round-trip equality) is a function
            # of the prompt's K/V only, not of prior page tenants
            for a in k_pages:
                a[-1, partial_len:] = 0
            for a in v_pages:
                a[-1, partial_len:] = 0
        return KVPageSpan(
            prompt=tuple(int(t) for t in prompt),
            next_token=(None if next_token is None else int(next_token)),
            page_size=page, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, dtype=self.dtype,
            topology=self.topology, k_pages=k_pages, v_pages=v_pages)

    def import_span(self, span, prefix_cache=None):
        """Materialize a :class:`KVPageSpan` into this pool, deduping
        against pages already resident in `prefix_cache` (only missing
        pages are allocated and scattered). Returns a stats dict:
        ``page_ids`` (full table prefix covering the span's prompt, in
        order), ``imported``/``reused`` page counts, ``bytes`` actually
        transferred, and ``resharded`` (True when the span came from a
        different KV layout and was laid out anew on import — also
        recorded via kernels fallback telemetry).

        Raises ``ValueError`` on checksum mismatch (corrupted span) or
        geometry disagreement. When `prefix_cache` is given the
        imported pages are inserted into the trie (which then holds
        their references — the serve loop's full-prefix-hit path picks
        them up); without one the caller owns the returned refs.
        """
        import numpy as np
        if not span.verify():
            raise ValueError("KVPageSpan checksum mismatch (corrupted "
                             "or torn handoff payload)")
        if (span.page_size != self.page_size
                or span.n_kv_heads != self.n_kv_heads
                or span.head_dim != self.head_dim
                or span.dtype != self.dtype
                or len(span.k_pages) != len(self.k)):
            raise ValueError(
                "KVPageSpan geometry mismatch: span "
                f"(page={span.page_size}, heads={span.n_kv_heads}, "
                f"dim={span.head_dim}, dtype={span.dtype}, "
                f"layers={len(span.k_pages)}) vs pool "
                f"(page={self.page_size}, heads={self.n_kv_heads}, "
                f"dim={self.head_dim}, dtype={self.dtype}, "
                f"layers={len(self.k)})")
        resharded = span.topology != self.topology
        if resharded:
            # cross-layout handoff: the span was gathered from another
            # sharding; scattering below lays it out for THIS pool.
            # Recorded as a fallback so autotune/reports can see
            # reshard traffic on the handoff path.
            from ..kernels._common import note_fallback
            note_fallback("kv_span_import", "reshard")
        page = self.page_size
        prompt = span.prompt
        n = len(prompt)
        n_full = n // page
        partial_len = n % page
        total = n_full + (1 if partial_len else 0)
        reused = []
        if prefix_cache is not None:
            pages, covered, partial, _nt = prefix_cache.lookup(prompt)
            reused = list(pages)
            if covered == n or (partial is not None
                                and covered + partial[1] == n):
                # fully resident: nothing to transfer
                return {"page_ids": reused + (
                            [partial[0]] if partial is not None else []),
                        "imported": 0, "reused": total, "bytes": 0,
                        "resharded": resharded}
        missing = list(range(len(reused), total))
        ids = self.alloc(len(missing))
        if ids is None:
            raise MemoryError(
                f"import_span: pool cannot hold {len(missing)} pages "
                f"(free={self.free_count})")
        sel = np.asarray(missing, dtype=np.int32)  # graft-lint: ok[GL102] host-side page-index list, no device transfer
        dst = np.asarray(ids, dtype=np.int32)      # graft-lint: ok[GL102] host-side page-index list, no device transfer
        nbytes = 0
        import jax
        import jax.numpy as jnp
        for layer in range(len(self.k)):
            upd_k = np.ascontiguousarray(span.k_pages[layer][sel])
            upd_v = np.ascontiguousarray(span.v_pages[layer][sel])
            nbytes += upd_k.nbytes + upd_v.nbytes
            jk, jv = jnp.asarray(upd_k), jnp.asarray(upd_v)
            if self.kv_sharding is not None:
                # reshard-on-import: lay the replicated host pages out
                # on this pool's head-sharded mesh before the scatter
                from jax.sharding import NamedSharding, PartitionSpec
                upd_sh = NamedSharding(self.kv_sharding.mesh,
                                       PartitionSpec(None, None,
                                                     "model", None))
                jk = jax.device_put(jk, upd_sh)
                jv = jax.device_put(jv, upd_sh)
            self.k[layer] = self.k[layer].at[dst].set(
                jk.astype(self.k[layer].dtype))
            self.v[layer] = self.v[layer].at[dst].set(
                jv.astype(self.v[layer].dtype))
        all_ids = reused + ids
        if prefix_cache is not None:
            next_tokens = None
            if span.next_token is not None:
                next_tokens = [None] * (n - 1) + [span.next_token]
            prefix_cache.insert(prompt, all_ids, next_tokens, self)
            # the trie holds the surviving references; drop the alloc
            # refs so imported pages are reclaimable like any cached
            # prefix once unused
            self.release(ids)
        return {"page_ids": all_ids, "imported": len(ids),
                "reused": len(reused), "bytes": nbytes,
                "resharded": resharded}


class KVPageSpan:
    """One request's prefilled KV pages, serialized for transfer between
    replicas (prefill→decode handoff). Pages are keyed by the same
    content hashes as the PrefixCache trie (`prefix_page_keys`), so the
    import side dedups against already-resident prefixes instead of
    re-transferring them.

    The payload is per-layer numpy — `k_pages[l]`/`v_pages[l]` are
    [n_pages, page_size, n_kv_heads, head_dim] host arrays covering the
    prompt (trailing partial page zero-padded past its valid tokens).
    `checksum` is a SHA-256 over header + payload, verified on import
    (a corrupted span is rejected, never half-materialized).

    `trace` is an optional plain-dict TraceContext
    (observability.tracing.TraceContext.to_dict) stamped by the router
    at handoff so the decode side's spans join the request's trace.
    Like `topology`, it is transport metadata — NOT part of the
    checksum (the same KV payload re-handed with a different trace
    must still verify).
    """

    __slots__ = ("prompt", "next_token", "page_size", "n_kv_heads",
                 "head_dim", "dtype", "topology", "k_pages", "v_pages",
                 "checksum", "trace")

    def __init__(self, prompt, next_token, page_size, n_kv_heads,
                 head_dim, dtype, topology, k_pages, v_pages,
                 checksum=None, trace=None):
        self.prompt = tuple(prompt)
        self.next_token = next_token
        self.page_size = int(page_size)
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = str(dtype)
        self.topology = str(topology)
        self.k_pages = list(k_pages)
        self.v_pages = list(v_pages)
        self.trace = dict(trace) if trace else None
        self.checksum = (checksum if checksum is not None
                         else self.compute_checksum())

    @property
    def n_pages(self) -> int:
        return int(self.k_pages[0].shape[0]) if self.k_pages else 0

    @property
    def nbytes(self) -> int:
        return (sum(a.nbytes for a in self.k_pages)
                + sum(a.nbytes for a in self.v_pages))

    @property
    def keys(self):
        """The trie keys of the span's FULL pages (the dedup join key)."""
        return prefix_page_keys(self.prompt, self.page_size)

    def compute_checksum(self) -> str:
        import hashlib
        import numpy as np
        h = hashlib.sha256()
        h.update(repr((self.prompt, self.next_token, self.page_size,
                       self.n_kv_heads, self.head_dim,
                       self.dtype)).encode())
        for a in self.k_pages:
            h.update(np.ascontiguousarray(a).tobytes())
        for a in self.v_pages:
            h.update(np.ascontiguousarray(a).tobytes())
        return h.hexdigest()

    def verify(self) -> bool:
        return self.checksum == self.compute_checksum()


def prefix_page_keys(prompt, page_size):
    """The page-aligned prefix keys of `prompt`: one hashable key per
    FULL KV page (``ceil`` is wrong here — a trailing sub-page chunk is
    a *partial*, not a page key). This is THE shared key function:
    PrefixCache trie edges use exactly these keys, and the serving
    router (serving/router.py) hashes prompts the same way to route a
    session to the replica already holding its cached pages — the two
    must never diverge, or affinity routing would chase pages that the
    cache will not recognize."""
    page = int(page_size)
    return tuple(tuple(prompt[m:m + page])
                 for m in range(0, len(prompt) - page + 1, page))


class _PrefixNode:
    __slots__ = ("page", "next_token", "last_use", "children", "partials",
                 "parent", "key")

    def __init__(self, page=None, next_token=None, last_use=0,
                 parent=None, key=None):
        self.page = page
        self.next_token = next_token
        self.last_use = last_use
        self.children = {}   # full page-size token tuple -> _PrefixNode
        self.partials = {}   # sub-page token tuple -> [page, next_token, use]
        self.parent = parent  # the node whose `children[key]` this is
        self.key = key


class PrefixCache:
    """Hash-trie over page-aligned prompt prefixes (cf. vLLM automatic
    prefix caching / SGLang RadixAttention): each trie edge is one KV
    page worth of token ids, each node holds the physical page that
    caches that prefix's K/V plus the greedy next token after it.

    A node additionally stores *partial* trailing chunks (< page_size
    tokens) so prompts that are not page-multiples still share their
    final page; a request extending a partial chunk copies the page
    first (copy-on-write at the divergence page — the pool refcount
    stays intact for the cached reader).

    The trie retains one pool reference per cached page; pages whose
    only reference is the trie are reclaimable on allocation pressure
    (LRU leaf-first) and are reported as free by the pool. Only the
    trie's tips can be dropped (a node with nothing under it, or a
    partial chunk), so the trie keeps them in two sets and `reclaim`
    looks at those, not at every node: unshared prompts make chains of
    tens of pages with one tip each.
    """

    def __init__(self, page_size):
        self.page = int(page_size)
        self._root = _PrefixNode()
        self._clock = 0
        self._tips = set()            # nodes with no child and no partial
        self._with_partials = set()   # nodes that hold partial chunks

    def _bump(self):
        self._clock += 1
        return self._clock

    # ------------------------------------------------------------- read --
    def lookup(self, prompt):
        """Longest cached page-aligned prefix of `prompt`.

        Returns (pages, covered, partial, next_token): `pages` are the
        full shared page ids covering `covered - (partial and its len)`
        ... specifically full pages cover the first len(pages)*page
        tokens; `partial`, when not None, is (page_id, n_tokens) for a
        shared sub-page chunk extending the covered span (the caller
        must copy-on-write that page before appending); `next_token` is
        the cached greedy continuation when the WHOLE prompt is covered
        (else None)."""
        node = self._root
        pages = []
        m = 0
        n = len(prompt)
        for key in prefix_page_keys(prompt, self.page):
            child = node.children.get(key)
            if child is None:
                break
            child.last_use = self._bump()
            pages.append(child.page)
            m += self.page
            node = child
        next_token = node.next_token if (m == n and m > 0) else None
        partial = None
        if m < n:
            rem = tuple(prompt[m:])
            best = None
            for toks, rec in node.partials.items():
                if (len(toks) <= len(rem) and rem[:len(toks)] == toks
                        and (best is None or len(toks) > len(best[0]))):
                    best = (toks, rec)
            if best is not None:
                toks, rec = best
                rec[2] = self._bump()
                partial = (rec[0], len(toks))
                if m + len(toks) == n and rec[1] is not None:
                    next_token = rec[1]
        return pages, m, partial, next_token

    # ------------------------------------------------------------ write --
    def insert(self, prompt, page_ids, next_tokens, pool):
        """Record a freshly prefilled prompt. `page_ids`: the pages
        holding the prompt's K/V in order (ceil(len/page) entries, the
        request's own table prefix). `next_tokens[i]` is the greedy
        token after prompt position i (None where unknown, e.g. the
        already-cached prefix of a suffix prefill). Existing nodes are
        left untouched; new nodes retain their page in the pool."""
        node = self._root
        m, i, n = 0, 0, len(prompt)
        for chunk in prefix_page_keys(prompt, self.page):
            child = node.children.get(chunk)
            if child is None:
                nt = next_tokens[m + self.page - 1] if next_tokens else None
                child = _PrefixNode(page_ids[i], nt, self._bump(),
                                    node, chunk)
                pool.cache_hold(page_ids[i])
                node.children[chunk] = child
                self._tips.discard(node)
                self._tips.add(child)
            m += self.page
            i += 1
            node = child
        if m < n:
            rem = tuple(prompt[m:])
            if rem not in node.partials:
                nt = next_tokens[n - 1] if next_tokens else None
                node.partials[rem] = [page_ids[i], nt, self._bump()]
                pool.cache_hold(page_ids[i])
                self._tips.discard(node)
                self._with_partials.add(node)

    # ---------------------------------------------------------- reclaim --
    def _droppable(self, pool):
        """(last_use, kind, parent node, key) for every entry whose page
        the pool would actually free (the trie holds the only
        reference): the tips of the trie, from the sets kept for it."""
        out = []
        for node in self._with_partials:
            for toks, rec in node.partials.items():
                if pool.ref_count(rec[0]) == 1:
                    out.append((rec[2], "partial", node, toks))
        for node in self._tips:
            if pool.ref_count(node.page) == 1:
                out.append((node.last_use, "leaf", node.parent, node.key))
        return out

    def _dropped_under(self, parent):
        """An entry under `parent` went: it may be a tip itself now."""
        if not parent.partials:
            self._with_partials.discard(parent)
            if not parent.children and parent is not self._root:
                self._tips.add(parent)

    def reclaimable_count(self, pool):
        """Pages the trie holds that no request is using, by one linear
        walk: the oracle of the count the pool keeps for `free_count`
        (tests compare the two; nothing on a serving path walks).
        Slightly optimistic: a ref-1 interior node above a
        pinned descendant counts here but cannot actually be freed
        until the descendant's user evicts — `alloc` handles that by
        re-checking after `reclaim`, and once the pool is idle the
        count is exact (the leak-accounting case)."""
        count = 0

        def walk(node):
            nonlocal count
            for rec in node.partials.values():
                if pool.ref_count(rec[0]) == 1:
                    count += 1
            for child in node.children.values():
                if pool.ref_count(child.page) == 1:
                    count += 1
                walk(child)

        walk(self._root)
        return count

    def reclaim(self, pool, need):
        """Drop least-recently-used unpinned leaves until `need` pages
        were freed (or nothing droppable remains). Returns pages freed."""
        freed = 0
        while freed < need:
            cands = self._droppable(pool)
            if not cands:
                break
            cands.sort(key=lambda c: c[0])
            take = cands[:max(need - freed, 1)]
            for _, kind, parent, key in take:
                if kind == "partial":
                    rec = parent.partials.pop(key)
                    pool.cache_drop(rec[0])
                else:
                    child = parent.children.pop(key)
                    self._tips.discard(child)
                    pool.cache_drop(child.page)
                self._dropped_under(parent)
                freed += 1
                if freed >= need:
                    break
        if freed:
            # page-eviction telemetry: cached-but-idle pages dropped
            # under allocation pressure. A sustained rate means the
            # pool is undersized for the working set — the signal
            # tools/autotune.py turns into a num_pages proposal.
            from ..observability import metrics as _obsm
            _obsm.counter("serving.page_evictions").inc(freed)
        return freed

    def clear(self, pool):
        """Release every cached page (used by tests and pool teardown)."""

        def walk(node):
            for rec in node.partials.values():
                pool.cache_drop(rec[0])
            for child in node.children.values():
                walk(child)
                pool.cache_drop(child.page)

        walk(self._root)
        self._root = _PrefixNode()
        self._tips.clear()
        self._with_partials.clear()


class PagedCacheEntry(NamedTuple):
    """Per-layer paged KV cache (reference parity: the block KV layout of
    paddle/phi/kernels/fusion/gpu block_multihead_attention / vLLM).

    `k_pages`/`v_pages`: [num_pages, page_size, n_kv_heads, head_dim];
    `block_table`: [B, pages_per_seq] int32 page ids per slot;
    `context_lens`: [B] int32 tokens already cached per slot (BEFORE the
    token being decoded). `ragged_meta` (optional): host-built metadata
    from kernels.paged_attention.build_ragged_meta, read by a step with
    `q_lens` only (a single-token decode step attends by block table).

    `q_lens` (optional, [B] int32): per-slot QUERY SPAN lengths for the
    MIXED prefill+decode step — slot b's forward carries q_lens[b]
    tokens (a prefill chunk, or 1 for a decode tick) starting at
    absolute position context_lens[b]. When set, attention dispatches
    to `paged_cache_mixed_update_attend` (span K/V scatter + the
    variable-query ragged kernel) and `ragged_meta`, if present, must
    be built for the post-write lengths context_lens + q_lens.

    `index_pages` (optional): [num_pages, page_size, lanes], the index
    keys of a layer whose attention selects its keys
    (`LayerCache.index_dim`, zero-padded to the pool's lanes), under
    the same page ids; such a layer steps through
    `paged_cache_sparse_update_attend`.

    `live` (optional, [B] bool): the slots that carry a request. The
    one-token decode contracts of a layer with an indexer, or over
    latent pages, attend over `attend_lens`: nothing for a slot that
    carries none (the serve loop's idle slots, whose table is all
    trash), so that the slot-walk kernels neither fetch nor contract a
    block for it. Without it, and under `paged_cache_update_attend`
    and `paged_cache_latent_span_update_attend`, which do not read it
    (PERF.md section 7), every slot attends over what it holds.
    """
    k_pages: object
    v_pages: object
    block_table: object
    context_lens: object
    ragged_meta: object = None
    q_lens: object = None
    index_pages: object = None
    live: object = None


class LatentCacheEntry(NamedTuple):
    """A latent-attention layer's cache for one decode step. `pages`:
    [num_pages, page_size, lanes], one row a token for all heads (the
    layer's `LayerCache.shape[0]` numbers, then zeros to whole 128-lane
    rows); `block_table` and `context_lens` as in `PagedCacheEntry`.
    The layer steps through `paged_cache_latent_update_attend`, or,
    where it declares an `index_dim`, with `index_pages` as in
    `PagedCacheEntry`, through
    `paged_cache_sparse_latent_update_attend`. `live` as in
    `PagedCacheEntry`."""
    pages: object
    block_table: object
    context_lens: object
    index_pages: object = None
    live: object = None


class StateCacheEntry(NamedTuple):
    """A recurrent layer's cache for one decode step: the whole state
    pool of that layer, updated in place. `conv`: [slots + 1, d_conv - 1,
    channels], the last inputs of the causal convolution in the
    activations' dtype; `ssm`: [slots + 1, heads, head_dim, d_state]
    float32. Row b is slot b's; the last row is nobody's (a prefill's
    dummy rows write there, as K/V's do on the trash page). A decode
    step advances every row by one token: an empty slot's row holds
    don't-care values until the next prefill writes it whole."""
    conv: object
    ssm: object


class PagedKVCache:
    """A list of per-layer cache entries (`PagedCacheEntry`, or
    `StateCacheEntry` for a recurrent layer), passed as
    `past_key_values`. `active` ([B] bool, optional) says which rows of
    the step carry a request, for a model that counts what its tokens
    do (expert routing)."""

    def __init__(self, entries: List[PagedCacheEntry], active=None):
        self.entries = entries
        self.active = active

    def __len__(self):
        return len(self.entries)

    def __getitem__(self, i):
        return self.entries[i]

    def __iter__(self):
        return iter(self.entries)


def attend_lens(cl, n, live=None):
    """The keys or rows each slot attends over in a step that writes
    `n` tokens a slot at `cl`: `cl + n`, and 0 for a slot that carries
    no request (`live`, a [B] bool; None: every slot carries one). At a
    length of 0 the slot-walk kernels start no DMA and run no block:
    zeros out, a row of -inf scores, an empty selection. A length of 1
    or more costs a whole block, however little of it is live. The WRITE
    still goes by `cl`: an idle slot's token lands on its table's page,
    the trash page."""
    import jax.numpy as jnp
    return cl + n if live is None else jnp.where(live, cl + n, 0)


def paged_cache_update_attend(entry: PagedCacheEntry, q, k, v, scale=None):
    """Decode-step contract for the paged cache: write this step's K/V
    (one token per slot) into each slot's current page position, then
    attend the query token against the slot's pages with the paged
    Pallas kernel. q: [B, 1, H, D]; k/v: [B, 1, Hkv, D] → (out
    [B, 1, H, D], updated entry). Gradients are not defined (serving
    path)."""
    import jax.numpy as jnp
    from ..ops._dispatch import apply
    from ..kernels.paged_attention import paged_attention

    if entry.q_lens is not None:
        # mixed prefill+decode step: variable-length query spans
        return paged_cache_mixed_update_attend(entry, q, k, v, scale)

    def fn(kp, vp, bt, cl, qv, kv, vv):
        bsz = qv.shape[0]
        page = kp.shape[1]
        rows = jnp.arange(bsz)
        pidx = bt[rows, (cl // page).astype(jnp.int32)]
        off = (cl % page).astype(jnp.int32)
        kp2 = kp.at[pidx, off].set(kv[:, 0].astype(kp.dtype))
        vp2 = vp.at[pidx, off].set(vv[:, 0].astype(vp.dtype))
        out = paged_attention(qv[:, 0], kp2, vp2, bt, cl + 1, scale)
        return out[:, None].astype(qv.dtype), kp2, vp2

    out, kp2, vp2 = apply(fn, entry.k_pages, entry.v_pages,
                          entry.block_table, entry.context_lens, q, k, v,
                          _name="paged_attention_decode")
    new_entry = PagedCacheEntry(kp2, vp2, entry.block_table,
                                entry.context_lens, entry.ragged_meta)
    return out, new_entry


def paged_cache_mixed_update_attend(entry: PagedCacheEntry, q, k, v,
                                    scale=None):
    """MIXED-step contract for the paged cache: each slot carries a
    query span of entry.q_lens[b] tokens (a prefill chunk, or 1 for a
    decode tick) starting at absolute position entry.context_lens[b].
    The span's K/V is scattered into the slot's pages IN-GRAPH, then
    the span attends causally over the pages with the variable-query
    ragged kernel (kernels.paged_attention.paged_attention_ragged_varq)
    — one compiled step serves a batch mixing mid-prefill and
    mid-decode requests. q: [B, Qb, H, D]; k/v: [B, Qb, Hkv, D] →
    (out [B, Qb, H, D], updated entry). Padding span positions (i >=
    q_lens[b]) write nothing (the scatter keeps the old page contents)
    and read back zeros. Gradients are not defined (serving path)."""
    import jax.numpy as jnp
    from ..ops._dispatch import apply
    from ..kernels.paged_attention import (paged_attention_varq,
                                           paged_attention_ragged_varq)

    meta = entry.ragged_meta

    def fn(kp, vp, bt, cl, ql, qv, kv, vv, *meta_arrs):
        qb = qv.shape[1]
        page = kp.shape[1]
        i = jnp.arange(qb, dtype=jnp.int32)[None, :]
        pos = cl[:, None].astype(jnp.int32) + i            # [B, Qb]
        writing = i < ql[:, None].astype(jnp.int32)        # [B, Qb]
        pslot = jnp.clip(pos // page, 0, bt.shape[1] - 1)
        # padding span positions write NOTHING: their destination page
        # is forced out of bounds and the scatter drops them. (Writing
        # their own gathered contents back instead would race: a
        # padding position past the END of a fully-allocated table
        # clips into the slot's last real page, and duplicate scatter
        # indices carrying different values — stale gather vs this
        # step's real K/V — have an unspecified winner.)
        dst_page = jnp.where(writing,
                             jnp.take_along_axis(bt, pslot, axis=1),
                             jnp.int32(kp.shape[0]))       # [B, Qb]
        dst_off = (pos % page).astype(jnp.int32)
        kp2 = kp.at[dst_page, dst_off].set(kv.astype(kp.dtype),
                                           mode="drop")
        vp2 = vp.at[dst_page, dst_off].set(vv.astype(vp.dtype),
                                           mode="drop")
        kv_lens = cl.astype(jnp.int32) + ql.astype(jnp.int32)
        if meta_arrs:
            mk = dict(zip(("seq", "page", "ordinal", "first", "last",
                           "valid"), meta_arrs))
            out = paged_attention_ragged_varq(qv, kp2, vp2, kv_lens, ql,
                                              mk, scale, block_tables=bt)
        else:
            out = paged_attention_varq(qv, kp2, vp2, bt, kv_lens, ql,
                                       scale)
        return out.astype(qv.dtype), kp2, vp2

    extra = () if meta is None else tuple(
        meta[k] for k in ("seq", "page", "ordinal", "first", "last",
                          "valid"))
    out, kp2, vp2 = apply(fn, entry.k_pages, entry.v_pages,
                          entry.block_table, entry.context_lens,
                          entry.q_lens, q, k, v, *extra,
                          _name="paged_attention_mixed")
    new_entry = PagedCacheEntry(kp2, vp2, entry.block_table,
                                entry.context_lens, entry.ragged_meta,
                                entry.q_lens)
    return out, new_entry


def paged_cache_sparse_update_attend(entry: PagedCacheEntry, q, k, v, qi, w,
                                     ki, topk, scale=None):
    """Decode-step contract of a layer with an indexer: write this
    step's K, V and index key (one token a slot) at each slot's current
    page position, score the slot's index keys for the query, select
    the `topk` best exactly and attend over those alone
    (kernels.paged_attention.paged_sparse_attention). q [B, 1, H, D];
    k/v [B, 1, Hkv, D]; qi [B, 1, J, Di]; w [B, 1, J]; ki [B, 1, Di] →
    (out [B, 1, H, D], updated entry, keys selected a slot [B] int32).
    Gradients are not defined (serving path)."""
    import jax.numpy as jnp
    from ..ops._dispatch import apply
    from ..kernels.paged_attention import (index_key_rows,
                                           paged_sparse_attention)

    def fn(kp, vp, ip, bt, cl, qv, kv, vv, qiv, wv, kiv, live):
        page = kp.shape[1]
        rows = jnp.arange(qv.shape[0])
        at = (bt[rows, (cl // page).astype(jnp.int32)],
              (cl % page).astype(jnp.int32))
        kp2 = kp.at[at].set(kv[:, 0].astype(kp.dtype))
        vp2 = vp.at[at].set(vv[:, 0].astype(vp.dtype))
        ip2 = ip.at[at].set(index_key_rows(kiv[:, 0], ip))
        out, keep = paged_sparse_attention(
            qv[:, 0], kp2, vp2, ip2, qiv[:, 0], wv[:, 0],
            bt, attend_lens(cl, 1, live), topk, scale)
        return (out[:, None].astype(qv.dtype), kp2, vp2, ip2,
                jnp.sum(keep, axis=1, dtype=jnp.int32))

    out, kp2, vp2, ip2, n_sel = apply(
        fn, entry.k_pages, entry.v_pages, entry.index_pages,
        entry.block_table, entry.context_lens, q, k, v, qi, w, ki,
        entry.live, _name="paged_sparse_attention_decode")
    return out, entry._replace(k_pages=kp2, v_pages=vp2,
                               index_pages=ip2), n_sel


def paged_cache_latent_update_attend(entry: LatentCacheEntry, q, row,
                                     scale=None):
    """Decode-step contract of a latent-attention layer: write this
    step's row (one token a slot) at each slot's current page position,
    then attend the absorbed query over the slot's live rows
    (kernels.latent_attention.paged_latent_attention). q [B, 1, H,
    width]; row [B, 1, width] -> (out [B, 1, H, lanes]: the softmax-
    weighted sum of whole rows, of which the caller keeps the latent's
    part, updated entry). Gradients are not defined (serving path)."""
    import jax.numpy as jnp
    from ..ops._dispatch import apply
    from ..kernels.latent_attention import (latent_rows,
                                            paged_latent_attention)

    def fn(pages, bt, cl, qv, rv, live):
        page = pages.shape[1]
        rows = jnp.arange(qv.shape[0])
        at = (bt[rows, (cl // page).astype(jnp.int32)],
              (cl % page).astype(jnp.int32))
        pages2 = pages.at[at].set(latent_rows(rv[:, 0], pages))
        out = paged_latent_attention(qv[:, 0], pages2, bt,
                                     attend_lens(cl, 1, live), scale)
        return out[:, None].astype(qv.dtype), pages2

    out, pages2 = apply(fn, entry.pages, entry.block_table,
                        entry.context_lens, q, row, entry.live,
                        _name="paged_latent_attention_decode")
    return out, entry._replace(pages=pages2)


def paged_cache_latent_span_update_attend(entry: LatentCacheEntry, q, row,
                                          scale=None):
    """Span contract of a latent-attention layer (a speculative verify,
    the draft pass after it): write the span's S rows a slot at
    positions `context_lens` .. `context_lens + S - 1`, then attend each
    of its S absorbed queries over the slot's rows up to its own
    (causal within the span; `paged_latent_attention` with `span`: the
    live rows are read once for all S). q [B, S, H, width]; row [B, S,
    width] -> (out [B, S, H, lanes], updated entry). Every slot carries
    a whole span. A position that is not kept afterwards needs no
    restoring: every reader of latent pages goes by the slot's length,
    and the next step's write at that position comes before any read of
    it."""
    import jax.numpy as jnp
    from ..ops._dispatch import apply
    from ..kernels.latent_attention import (latent_rows,
                                            paged_latent_attention)

    def fn(pages, bt, cl, qv, rv):
        page = pages.shape[1]
        b, s, h, _ = qv.shape
        pos = cl[:, None].astype(jnp.int32) \
            + jnp.arange(s, dtype=jnp.int32)[None, :]
        at = (jnp.take_along_axis(
            bt, jnp.clip(pos // page, 0, bt.shape[1] - 1), axis=1),
            pos % page)
        pages2 = pages.at[at].set(latent_rows(rv, pages))
        out = paged_latent_attention(qv.reshape(b, s * h, -1), pages2, bt,
                                     cl + s, scale, span=s)
        return out.reshape(b, s, h, -1).astype(qv.dtype), pages2

    out, pages2 = apply(fn, entry.pages, entry.block_table,
                        entry.context_lens, q, row,
                        _name="paged_latent_attention_span")
    return out, entry._replace(pages=pages2)


def paged_cache_sparse_latent_update_attend(entry: LatentCacheEntry, q, row,
                                            qi, w, ki, topk, scale=None):
    """Decode-step contract of a latent-attention layer with an indexer:
    write this step's row and index key (one token a slot) at each
    slot's current page position, score the slot's index keys for the
    query, select the `topk` best exactly and attend the absorbed query
    over those rows alone (kernels.latent_attention.
    paged_sparse_latent_attention). q [B, 1, H, width]; row [B, 1,
    width]; qi [B, 1, J, Di]; w [B, 1, J]; ki [B, 1, Di] -> (out [B, 1,
    H, lanes], updated entry, rows selected a slot [B] int32).
    Gradients are not defined (serving path)."""
    import jax.numpy as jnp
    from ..ops._dispatch import apply
    from ..kernels.latent_attention import (latent_rows,
                                            paged_sparse_latent_attention)

    def fn(pages, ip, bt, cl, qv, rv, qiv, wv, kiv, live):
        page = pages.shape[1]
        rows = jnp.arange(qv.shape[0])
        at = (bt[rows, (cl // page).astype(jnp.int32)],
              (cl % page).astype(jnp.int32))
        pages2 = pages.at[at].set(latent_rows(rv[:, 0], pages))
        ip2 = ip.at[at].set(latent_rows(kiv[:, 0], ip))
        out, keep = paged_sparse_latent_attention(
            qv[:, 0], pages2, ip2, qiv[:, 0], wv[:, 0], bt,
            attend_lens(cl, 1, live), topk, scale)
        return (out[:, None].astype(qv.dtype), pages2, ip2,
                jnp.sum(keep, axis=1, dtype=jnp.int32))

    out, pages2, ip2, n_sel = apply(
        fn, entry.pages, entry.index_pages, entry.block_table,
        entry.context_lens, q, row, qi, w, ki, entry.live,
        _name="paged_sparse_latent_attention_decode")
    return out, entry._replace(pages=pages2, index_pages=ip2), n_sel
