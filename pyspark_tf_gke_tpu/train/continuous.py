"""Slot-based continuous batching: the serving plane's request engine.

The reference's serving story is one-at-a-time prediction over a saved
model (``/root/reference/workloads/raw-tf/test-model.py:13-56``). A real
serving plane cannot afford that: decode is HBM-bound, so throughput
comes from keeping every KV-cache slot busy — and requests arrive and
finish at different times, so a whole-batch ``generate`` (everyone
enters and exits together, the batch lives as long as its longest
member) leaves slots idle exactly when load is highest.

This engine is the TPU-idiomatic version of vLLM/TGI-style continuous
batching, built for XLA's compilation model instead of CUDA kernels:

- **Static shapes everywhere.** A fixed pool of ``num_slots`` KV-cache
  rows; prompts prefill through a small set of length buckets; decode is
  ONE compiled program per (model, chunk) regardless of which requests
  occupy which slots. No recompiles at serve time after warmup.
- **Per-row cache positions** (``models/causal_lm.py`` ``slot_decode``):
  each batch row writes K/V at its own fill level and masks attention
  against it, so row b can be 900 tokens into its answer while row b+1
  is on token 3 of a fresh request.
- **Admission at chunk boundaries.** The host loop runs a jitted
  ``lax.scan`` of ``chunk`` decode steps, then admits queued requests
  into freed slots (prefill writes the slot's cache rows directly).
  Through a remote-dispatch link the chunk amortizes per-dispatch
  latency; on a local TPU host it amortizes Python.
- **Right-padded bucketed prefill is exact**: causal attention means a
  real token's K/V and logits never see the padding AFTER it, and pad
  rows in the cache beyond a slot's fill level are masked by the
  per-row validity test (``k_pos <= fill``) until real decode tokens
  overwrite them one by one.

Greedy decoding (the deterministic serving path — parity-tested
token-for-token against ``models.causal_lm.generate``). Weight-only
int8 params and int8 KV cache both ride along: prefill dequantizes
inside its jit, the decode chunk uses the same in-loop barriered
dequant as ``_decode``, and the per-row cache write quantizes per row.

Single-process engine (one host driving one chip or a tp-sharded mesh
via module-level jits); the multi-host announce/replay serving wire
(``train/serving.py``) stays the cross-process surface.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pyspark_tf_gke_tpu.chaos.inject import chaos_fire
from pyspark_tf_gke_tpu.models.causal_lm import CausalLM
from pyspark_tf_gke_tpu.obs.metrics import platform_families
from pyspark_tf_gke_tpu.obs.stepstats import StepStatsRing, flops_per_token
from pyspark_tf_gke_tpu.obs.trace import annotate_request_shape
from pyspark_tf_gke_tpu.utils.logging import get_logger

logger = get_logger("train.continuous")

PAD_BUCKETS = (32, 64, 128, 256, 512, 1024)

# Smallest chunk the budget-aligned adaptive scheduler will dispatch:
# floors the jit-cache size (adaptive sizes are powers of two between
# this and the engine's ``chunk``) and bounds the overshoot on a
# sub-minimum remainder.
_MIN_ADAPTIVE_CHUNK = 8


def right_pad(tokens: np.ndarray, width: int,
              pad_id: int) -> np.ndarray:
    """[1, width] int32 row: tokens then pad (the prefill/extend input
    shape)."""
    row = np.full((1, width), pad_id, np.int32)
    row[0, :tokens.size] = tokens
    return row


def bucket_length(n: int, buckets: Sequence[int] = PAD_BUCKETS) -> int:
    """Smallest bucket >= n (compile-count control: one prefill program
    per bucket, not per prompt length)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds largest bucket {buckets[-1]}")


@dataclass
class _Request:
    rid: int
    prompt: np.ndarray            # [S_true] int32
    max_new_tokens: int
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    # multi-tenant fairness: every request belongs to a tenant (the
    # serving front defaults absent ids to "default"); the DWRR
    # admission scheduler arbitrates between tenants' subqueues and
    # the front's quota buckets charge/refund per tenant
    tenant: str = "default"
    # time.monotonic() at submit — /loadz queue_delay_ms (the HPA
    # latency signal) is the age of the OLDEST queued request
    enqueued_at: float = 0.0
    # streaming: called with each newly decoded token group, on the
    # engine's driver thread (keep it cheap — enqueue and return)
    on_tokens: Optional[callable] = None
    # sampling lane (temperature 0 = greedy; per-request PRNG seed)
    temperature: float = 0.0
    top_p: Optional[float] = None
    seed: int = 0
    # absolute time.monotonic() deadline (None = no deadline); past it
    # the request is expired at the next chunk boundary — queued ones
    # never admit, in-slot ones free their KV slot immediately
    deadline: Optional[float] = None
    expired: bool = False
    # time.monotonic() of the last token delivery — the per-request
    # time-between-tokens (serve_tbt_ms) clock; None until the first
    # tokens land (the first gap is TTFT, not TBT)
    last_emit: Optional[float] = None
    # speculative decoding tallies (spec engines only): draft tokens
    # proposed/accepted for THIS request while it still had budget —
    # the per-request accept-rate span event's source
    spec_proposed: int = 0
    spec_accepted: int = 0
    # request-attached trace span (obs/trace.py, or None): the engine
    # annotates the request's OWN span — queue wait, admission route,
    # prefill pieces, first token, token deliveries — so the timeline
    # lands on the trace the HTTP layer opened without the engine ever
    # knowing about transports. Every annotation is guarded on None:
    # direct callers pay one attribute check per event site.
    span: Optional[object] = None


def _prefill_padded(model: CausalLM, params, padded_ids, true_len):
    """Prefill on a right-padded [1, S_bucket] prompt. Returns the full
    cache and the logits at the LAST REAL token (index true_len-1 —
    ``_prefill``'s logits[:, -1] would read a pad position). Causality
    makes the padding invisible to every real position. Exactly the
    batch-1 case of ``_prefill_padded_batch`` — delegated so the two
    cannot drift."""
    return _prefill_padded_batch(model, params, padded_ids,
                                 jnp.asarray(true_len)[None])


@functools.partial(jax.jit, static_argnames=("model",))
def _prefill_padded_batch(model: CausalLM, params, padded_ids, true_lens):
    """Batched right-padded prefill: ``[k, S_bucket]`` prompts with
    per-row true lengths, ONE weight-streaming forward. The batch-1
    admission loop pays the full HBM weight read per request — on the
    round-5 hardware trail that made slot refills the engine's dominant
    overhead vs whole-batch serving (32 batch-1 prefills vs 4 batch-8
    ones; prefill is bandwidth-bound, so batch-1 costs nearly as much
    as batch-8). Returns the k-row cache tree and the logits at each
    row's last real token."""
    from pyspark_tf_gke_tpu.ops.quant import dequantize_tree

    logits, mutated = model.apply(
        {"params": dequantize_tree(params)}, padded_ids, prefill=True,
        mutable=["cache"])
    last = jnp.take_along_axis(
        logits, (true_lens - 1)[:, None, None], axis=1)[:, 0]
    return mutated["cache"], last


@functools.partial(jax.jit, static_argnames=("model",))
def _extend_prefix(model: CausalLM, params, cache1, padded_rem, fill,
                   rem_len):
    """Extend a batch-1 prefix cache (fill level ``fill``) with the
    right-padded remainder tokens in ONE multi-token slot-decode
    forward: K/V for all remainder positions are written at
    fill..fill+s-1 and the causal offset mask keeps every real token
    blind to the padding after it (same argument as the padded
    prefill). Returns (extended cache, logits at the last REAL
    remainder token)."""
    from pyspark_tf_gke_tpu.ops.quant import dequantize_tree

    s_b = padded_rem.shape[1]
    positions = (fill + jnp.arange(s_b))[None, :]
    logits, mutated = model.apply(
        {"params": dequantize_tree(params), "cache": cache1}, padded_rem,
        decode=True, slot_decode=True, positions=positions,
        mutable=["cache"])
    last = jnp.take_along_axis(
        logits, (rem_len - 1)[None, None, None], axis=1)[:, 0]
    return mutated["cache"], last


class PrefixCache:
    """LRU of prefilled prompt PREFIXES (the shared-system-prompt
    serving pattern): each entry holds a batch-1 cache tree + the
    last-token logits at its fill level. ``lookup`` returns the longest
    entry that prefixes the prompt; admission inserts it into the slot
    and only the remainder pays prefill compute. Each entry costs one
    slot's worth of KV memory — size ``capacity`` accordingly."""

    def __init__(self, capacity: int = 4):
        if capacity < 1:
            raise ValueError("prefix cache capacity must be >= 1")
        self.capacity = capacity
        self._entries = {}  # key tuple -> (cache_tree, last_logits)
        self._order: List[tuple] = []  # LRU, most recent LAST
        self.hits = self.misses = 0

    def put(self, key_tokens, cache1, logits1) -> None:
        key = tuple(int(t) for t in key_tokens)
        if key in self._entries:
            self._order.remove(key)
        elif len(self._entries) >= self.capacity:
            evict = self._order.pop(0)
            del self._entries[evict]
        self._entries[key] = (cache1, logits1)
        self._order.append(key)

    def lookup(self, prompt: np.ndarray, peek: bool = False):
        """Best cached entry by LONGEST COMMON TOKEN PREFIX with the
        prompt — not exact key-prefix match, because BPE tokenizers are
        not prefix-stable: encode(system + user) can merge a token
        across the boundary, so the warmed sequence and the prompt
        diverge one token early. Matching the common prefix reuses
        every row up to the divergence and recomputes only the rest.
        Returns (usable_fill, cache_tree, last_logits_or_None) or None;
        ``last_logits`` is only returned when the WHOLE entry matched
        and equals the whole prompt's prefix fill (else the extension
        recomputes the logits anyway)."""
        toks = np.asarray(prompt, np.int64)
        best, best_common = None, 0
        for key in self._entries:
            k = np.asarray(key, np.int64)
            n = min(k.size, toks.size)
            neq = np.nonzero(k[:n] != toks[:n])[0]
            common = int(neq[0]) if neq.size else n
            if common > best_common:
                best, best_common = key, common
        # A prompt that is a STRICT prefix of an entry (common == prompt
        # length < entry length) would need logits at a fill level the
        # entry doesn't store — decline; everything else either matched
        # exactly (stored logits apply) or has a remainder whose
        # extension recomputes them.
        if best is None or best_common == 0 or (
                best_common == toks.size and best_common != len(best)):
            if not peek:
                self.misses += 1
            return None
        if not peek:
            self.hits += 1
            self._order.remove(best)
            self._order.append(best)  # LRU touch
        cache1, logits1 = self._entries[best]
        exact = best_common == len(best) == toks.size
        return best_common, cache1, (logits1 if exact else None)

    @property
    def stats(self) -> dict:
        return {"entries": len(self._entries), "capacity": self.capacity,
                "hits": self.hits, "misses": self.misses}


class _RadixNode:
    """One KV page in the radix prefix cache: ``tokens`` is the page's
    token content (``page_size`` long for interior/full pages, shorter
    for a TAIL page holding a partially-filled final page — always a
    leaf). Children are keyed by their token tuple, but LOOKUP scans
    children for the longest common prefix rather than dict-probing:
    two siblings may share an in-page prefix after divergent inserts
    ("efgh" and "efxy"), and a tail node matches any prompt that
    extends its tokens."""

    __slots__ = ("tokens", "page", "children", "parent", "last_used")

    def __init__(self, tokens: tuple, page: Optional[int], parent):
        self.tokens = tokens
        self.page = page
        self.parent = parent
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.last_used = 0


class RadixPrefixCache:
    """SGLang-style trie index over the PAGED KV pool (the engine owns
    the pages; this class owns only the token->page index): completed
    prompts' pages stay resident, a new prompt matches its longest
    cached prefix at page granularity and SHARES those pages
    copy-on-write, so prefill compute and pool traffic are ∝ the
    unique suffix only.

    Division of labor with the engine: the trie never touches device
    state or refcounts. ``match``/``insert``/``evict`` return page-id
    lists and the ENGINE moves the refcounts (+1 for every page the
    trie adopts, -1 for every page it releases) — one owner for the
    page lifecycle, so the refcount invariants are checkable in one
    place. Eviction is LRU over leaf nodes whose page has no slot
    reference (``busy`` predicate), leaf-first so a cached path is
    always contiguous from the root."""

    def __init__(self, page_size: int, capacity_pages: int):
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        if capacity_pages < 1:
            raise ValueError("capacity_pages must be >= 1")
        self.page_size = int(page_size)
        self.capacity = int(capacity_pages)
        self._root = _RadixNode((), None, None)
        self._tick = 0
        self.resident_pages = 0
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0
        self.evictions = 0
        # last-N admission outcomes: the hit-rate signal the router
        # scores spill allowance on must track CURRENT absorption, not
        # the lifetime ratio — a cache that went cold (eviction, mix
        # shift) would otherwise keep advertising its warm past
        self._recent: Deque[int] = deque(maxlen=64)

    @staticmethod
    def _common(a, b) -> int:
        n = min(len(a), len(b))
        i = 0
        while i < n and a[i] == b[i]:
            i += 1
        return i

    def _touch(self, node: _RadixNode) -> None:
        # the whole matched path was used: eviction is leaf-only, but
        # a deep leaf must keep its ancestors young for when IT is
        # evicted and they become leaves
        self._tick += 1
        while node is not None and node.page is not None:
            node.last_used = self._tick
            node = node.parent

    def match(self, prompt, limit: Optional[int] = None,
              peek: bool = False, count: bool = True):
        """Longest cached prefix of ``prompt``. Returns
        ``(matched_tokens, full_page_ids, cow)`` where ``cow`` is
        ``(src_page, rows)`` when the match ends INSIDE a page — the
        admission must clone those rows into a fresh page before its
        suffix can append there (copy-on-write; the full pages are
        shared read-only, the slot never writes below the match
        boundary). ``limit`` caps the match — default
        ``len(prompt) - 1``, because at least one suffix token must be
        computed to produce the carried decode logits (the trie stores
        pages, not logits). ``peek`` skips stats and LRU touching;
        ``count=False`` touches the LRU but leaves the hit/miss stats
        to an explicit ``note()`` — for callers whose effective match
        may still shrink (COW degrade) or that aren't admissions at
        all (warm no-ops): the hit rate is a ROUTING signal, so only
        real admission outcomes may feed it."""
        toks = tuple(int(t) for t in prompt)
        limit = len(toks) - 1 if limit is None else min(limit, len(toks))
        node = self._root
        t = 0
        pages: List[int] = []
        cow = None
        last = None
        while t < limit:
            rem = toks[t:]
            best, best_c = None, 0
            for child in node.children.values():
                c = self._common(child.tokens, rem)
                if c > best_c:
                    best, best_c = child, c
            if best is None:
                break
            best_c = min(best_c, limit - t)
            if best_c <= 0:
                break
            last = best
            if best_c == len(best.tokens) == self.page_size:
                pages.append(best.page)
                t += self.page_size
                node = best
                continue
            # partial in-page match: a tail node, a mid-page
            # divergence, or the limit cap — the walk ends here
            cow = (best.page, best_c)
            t += best_c
            break
        if not peek:
            if last is not None:
                # ONE root-ward walk from the deepest matched node
                # marks the whole path (O(depth), not O(depth^2));
                # leaf-first eviction makes intra-path order moot
                self._touch(last)
            if count:
                self.note(t)
        return t, pages, cow

    def note(self, matched: int) -> None:
        """Record one ADMISSION outcome: the cumulative hit counters
        plus the recent-outcome window behind ``recent_hit_rate``."""
        if matched > 0:
            self.hits += 1
            self.hit_tokens += int(matched)
            self._recent.append(1)
        else:
            self.misses += 1
            self._recent.append(0)

    @property
    def recent_hit_rate(self) -> float:
        """Hit rate over the last up-to-64 admissions — what ``/loadz``
        exports for the router's spill allowance. Windowed, not
        lifetime: a cache that went cold (eviction, traffic-mix shift)
        stops advertising its warm past within one window."""
        if not self._recent:
            return 0.0
        return sum(self._recent) / len(self._recent)

    def insert(self, tokens, pages):
        """Index ``tokens`` (chunked per page) over their physical
        ``pages`` (block-table row order). Chunks an existing node
        already covers are NOT re-adopted (the duplicate page simply
        loses its slot ref when the caller releases it); a partial
        tail node that is a strict prefix of a longer chunk is
        UPGRADED in place to the new, fuller page — that is how a
        cached conversation prefix grows turn by turn. Returns
        ``(adopted, released)`` page-id lists for the engine's
        refcount moves."""
        toks = tuple(int(t) for t in tokens)
        ps = self.page_size
        adopted: List[int] = []
        released: List[int] = []
        node = self._root
        self._tick += 1
        for i in range(0, len(toks), ps):
            chunk = toks[i:i + ps]
            page = int(pages[i // ps])
            nxt = None
            for child in node.children.values():
                c = self._common(child.tokens, chunk)
                if c == len(chunk) and len(child.tokens) >= len(chunk):
                    nxt = child  # already covered (possibly by a
                    break        # longer tail) — keep the cached page
                if c == len(child.tokens) and c < len(chunk):
                    # the cached tail is a strict prefix of our chunk:
                    # upgrade the node to the fuller page (identical
                    # token prefix -> identical KV rows; slots still
                    # reading the old page keep it alive by refcount)
                    del node.children[child.tokens]
                    released.append(child.page)
                    child.tokens = chunk
                    child.page = page
                    node.children[chunk] = child
                    adopted.append(page)
                    nxt = child
                    break
            if nxt is None:
                nxt = _RadixNode(chunk, page, node)
                node.children[chunk] = nxt
                adopted.append(page)
                self.resident_pages += 1
            nxt.last_used = self._tick
            if len(nxt.tokens) < ps or len(chunk) < ps:
                break  # a tail page ends the path
            node = nxt
        return adopted, released

    def evict(self, n_pages: int, busy) -> List[int]:
        """Drop up to ``n_pages`` least-recently-used LEAF pages whose
        page ``busy(page)`` reports free of slot references; returns
        the released page ids (the caller unrefs them back to the
        pool). Interior nodes become eligible as their children go —
        O(nodes) per eviction, fine at page-pool scale."""
        released: List[int] = []
        while len(released) < n_pages:
            victim = None
            stack = [self._root]
            while stack:
                node = stack.pop()
                for child in node.children.values():
                    if child.children:
                        stack.append(child)
                    elif not busy(child.page) and (
                            victim is None
                            or child.last_used < victim.last_used):
                        victim = child
            if victim is None:
                break  # everything left is pinned by live slots
            del victim.parent.children[victim.tokens]
            released.append(victim.page)
            self.resident_pages -= 1
            self.evictions += 1
        return released

    def indexed_pages(self) -> List[int]:
        """Every page the trie currently references (invariant checks:
        each must hold exactly one trie refcount)."""
        out: List[int] = []
        stack = [self._root]
        while stack:
            node = stack.pop()
            for child in node.children.values():
                out.append(child.page)
                stack.append(child)
        return out

    @property
    def stats(self) -> dict:
        return {"kind": "radix", "resident_pages": self.resident_pages,
                "capacity_pages": self.capacity, "hits": self.hits,
                "misses": self.misses, "hit_tokens": self.hit_tokens,
                "evictions": self.evictions,
                "recent_hit_rate": round(self.recent_hit_rate, 4)}


def _request_cost(req: "_Request") -> int:
    """A request's token footprint for fair-share accounting: prompt +
    full generation budget — the same upper bound bounded admission and
    the quota buckets charge (refunds reconcile unused budget later;
    the scheduler must arbitrate on the worst case it admits)."""
    return int(req.prompt.size) + int(req.max_new_tokens)


class DwrrScheduler:
    """Deficit-weighted round robin over per-tenant subqueues.

    Each tenant's subqueue is its arrival-ordered subsequence of the
    engine's admission queue (FIFO or LPT within a tenant — whatever
    the engine's ``schedule`` produced). Every rotation visit banks
    ``quantum * weight`` tokens of deficit; a tenant may admit its
    head-of-line request when its deficit covers the request's token
    cost (prompt + budget), paying the cost down on admission. Over a
    saturated queue the admitted-token shares converge to the weight
    ratio regardless of request sizes — the classic DWRR guarantee —
    while an idle tenant's unused deficit is dropped the moment its
    subqueue empties (no banking credit while absent, so a returning
    tenant cannot burst past its share).

    Pure host-side bookkeeping (no device state): the engine consults
    :meth:`pick` only once it has actually seen two distinct tenants —
    a single-tenant engine never enters this class and keeps the exact
    pre-fairness FIFO/LPT admission order (the FIFO-equivalent fast
    path)."""

    def __init__(self, weights: Optional[Dict[str, float]] = None,
                 quantum: int = 256):
        if quantum < 1:
            raise ValueError(f"quantum must be >= 1, got {quantum}")
        self.weights: Dict[str, float] = {}
        for name, w in (weights or {}).items():
            w = float(w)
            if w <= 0:
                raise ValueError(
                    f"tenant {name!r} weight must be > 0, got {w}")
            self.weights[name] = w
        self.quantum = int(quantum)
        self._deficit: Dict[str, float] = {}
        self._rr: Deque[str] = deque()  # rotation over queued tenants
        # cumulative admitted token cost per tenant (stats + the
        # share-convergence tests' observable)
        self.admitted_tokens: Dict[str, int] = {}

    def weight(self, tenant: str) -> float:
        """Configured weight; unknown tenants fall back to the ``*``
        wildcard entry, then 1.0 — an unconfigured tenant competes at
        baseline weight instead of being refused."""
        w = self.weights.get(tenant)
        if w is None:
            w = self.weights.get("*", 1.0)
        return float(w)

    def pick(self, queue: List["_Request"]) -> int:
        """Index into ``queue`` of the request to admit next. The
        rotation/deficit state persists across calls; tenants that
        left the queue are dropped (deficit reset — no banking)."""
        heads: Dict[str, int] = {}
        for i, req in enumerate(queue):
            if req.tenant not in heads:
                heads[req.tenant] = i
        if len(heads) <= 1:
            return 0  # one tenant queued: its own order stands
        present = set(heads)
        for t in list(self._deficit):
            if t not in present:
                del self._deficit[t]
        if any(t not in present for t in self._rr):
            self._rr = deque(t for t in self._rr if t in present)
        for t in heads:  # first-appearance order joins at the back
            if t not in self._rr:
                self._rr.append(t)
        # rotate, banking quanta, until a head-of-line is affordable;
        # bounded: each full rotation banks quantum*weight for every
        # tenant and costs are bounded by max_seq_len, so the guard is
        # never the exit in practice — it exists so a pathological
        # weight/quantum config degrades to round-robin, not a wedge
        for _ in range(10000):
            t = self._rr[0]
            cost = _request_cost(queue[heads[t]])
            if self._deficit.get(t, 0.0) >= cost:
                return heads[t]
            self._deficit[t] = (self._deficit.get(t, 0.0)
                                + self.quantum * self.weight(t))
            self._rr.rotate(-1)
        return heads[self._rr[0]]

    def charge(self, req: "_Request") -> None:
        """Pay one admitted request's cost down from its tenant's
        deficit and tally it (the share the convergence tests
        measure)."""
        t = req.tenant
        cost = _request_cost(req)
        self._deficit[t] = self._deficit.get(t, 0.0) - cost
        self.admitted_tokens[t] = self.admitted_tokens.get(t, 0) + cost


def _seed_key_data(seed):
    """[2] uint32 key data for the slot lane, with the impl PINNED to
    threefry2x32: _decode_chunk wraps with that impl explicitly, and the
    default-impl PRNGKey would hand back (4,)-shaped rbg data on
    configs that set jax_default_prng_impl=rbg (common on TPU).

    Seeds in [0, 2**32) — every seed the serving stack generates —
    take a pure-numpy fast path: threefry key data for such a seed is
    exactly ``[0, seed]`` under x64 on AND off (verified bit-identical
    against ``jax.random.key``), and building it on the host instead
    of through three eager device ops keeps admissions off the
    dispatch queue (measured ~0.14 ms/row on the CPU bench box —
    admission host cost is what the double-buffered loop must hide).
    Out-of-range seeds keep the jax path, whose truncation semantics
    depend on the x64 flag and are not worth reimplementing."""
    s = int(seed)
    if 0 <= s < 2**32:
        return np.array([0, s], np.uint32)
    return jax.random.key_data(
        jax.random.key(s, impl="threefry2x32")).astype(jnp.uint32)


class SlotState(NamedTuple):
    """The slot pool's device arrays (a pytree — flows through jits).
    Sampling lanes ride per slot: ``temps`` 0 = greedy for that row,
    ``topps`` 1 = no nucleus filter, ``keys`` a per-slot PRNG key each
    sampling row folds forward every step."""

    cache: Any
    positions: jnp.ndarray     # [B] int32 fill levels
    last_logits: jnp.ndarray   # [B, V] carried logits
    live: jnp.ndarray          # [B] bool
    temps: jnp.ndarray         # [B] f32
    topps: jnp.ndarray         # [B] f32
    keys: jnp.ndarray          # [B, 2] uint32


@jax.jit
def _clear_live(state: SlotState, slot):
    return state._replace(live=state.live.at[slot].set(False))


# -- paged KV cache (models/causal_lm.py CausalLMConfig.kv_num_pages) --------
#
# Slot mode stores K/V in one global page pool per layer plus a per-slot
# block table; the ENGINE owns page allocation (host-side free list,
# admit/free boundaries only — no mid-decode allocation, so no shape
# recompiles). Prefill still runs on the dense batch-1 layout (it is
# compute-bound and transient); the insert ops below scatter its rows
# into the slot's pages. A slot's block-table row is reset to the
# OUT-OF-RANGE sentinel on free, so rows of freed/dead slots can never
# write into pages reallocated to another request.


def _map_paged_layers(pool_tree, fn, dense_tree=None):
    """Rebuild a paged cache tree: ``fn`` is applied to every subtree
    holding the paged leaves (``k_pages``/``block_table``/...), paired
    with the same-path subtree of ``dense_tree`` when given (the dense
    prefill cache has ``k``/``v``/``index`` at identical paths — both
    come from the same attention modules)."""
    def walk(pool, dense):
        if hasattr(pool, "keys"):
            if "k_pages" in pool:
                return fn(pool) if dense is None else fn(pool, dense)
            return {key: walk(pool[key],
                              None if dense is None else dense[key])
                    for key in pool}
        return pool
    return walk(pool_tree, dense_tree)


@functools.partial(jax.jit, static_argnames=("model", "num_slots"))
def _paged_zeros_state(model: CausalLM, params, *,
                       num_slots: int) -> SlotState:
    """Fresh paged slot-pool state. The paged cache tree's shapes come
    from the model config, not from a prefill template, so it is built
    by one throwaway slot-decode forward whose cache writes all drop
    (block tables initialize to the sentinel)."""
    from pyspark_tf_gke_tpu.ops.quant import dequantize_tree

    b = num_slots
    tok = jnp.zeros((b, 1), jnp.int32)
    pos = jnp.zeros((b, 1), jnp.int32)
    _, mutated = model.apply(
        {"params": dequantize_tree(params)}, tok, decode=True,
        slot_decode=True, positions=pos, mutable=["cache"])
    return SlotState(
        cache=mutated["cache"],
        positions=jnp.zeros((b,), jnp.int32),
        last_logits=jnp.zeros((b, model.cfg.vocab_size), jnp.float32),
        live=jnp.zeros((b,), bool),
        temps=jnp.zeros((b,), jnp.float32),
        topps=jnp.ones((b,), jnp.float32),
        keys=jnp.zeros((b, 2), jnp.uint32))


@functools.partial(jax.jit, static_argnames=("n_rows",))
def _insert_slot_paged(state: SlotState, cache1, logits1, slot, fill,
                       pages, temp, topp, key, *, n_rows: int) -> SlotState:
    """Paged ``_insert_slot``: scatter the dense batch-1 prefill's first
    ``n_rows`` cache rows (the padded bucket — ``n_rows`` static, so
    one program per bucket) into the slot's allocated pages and point
    its block-table row at them. ``pages`` is the sentinel-padded
    ``[max_pages_per_slot]`` allocation; only its first
    ``n_rows / page_size`` entries receive prefill rows."""
    def layer(pool, dense):
        ps = pool["k_pages"].shape[1]
        nc = n_rows // ps
        idx = pages[:nc]

        def scat(pool_leaf, dense_leaf):
            rows = dense_leaf[0, :n_rows]
            chunks = rows.reshape((nc, ps) + rows.shape[1:])
            return pool_leaf.at[idx].set(
                chunks.astype(pool_leaf.dtype), mode="drop")

        out = dict(pool)
        out["k_pages"] = scat(pool["k_pages"], dense["k"])
        out["v_pages"] = scat(pool["v_pages"], dense["v"])
        if "k_scale_pages" in pool:
            out["k_scale_pages"] = scat(pool["k_scale_pages"],
                                        dense["k_scale"])
            out["v_scale_pages"] = scat(pool["v_scale_pages"],
                                        dense["v_scale"])
        out["block_table"] = pool["block_table"].at[slot].set(pages)
        out["index"] = jnp.maximum(pool["index"], dense["index"])
        return out

    cache = _map_paged_layers(state.cache, layer, cache1)
    return SlotState(
        cache=cache,
        positions=state.positions.at[slot].set(fill),
        last_logits=state.last_logits.at[slot].set(logits1[0]),
        live=state.live.at[slot].set(True),
        temps=state.temps.at[slot].set(temp),
        topps=state.topps.at[slot].set(topp),
        keys=state.keys.at[slot].set(key))


@functools.partial(jax.jit, static_argnames=("n_rows",))
def _insert_slots_batch_paged(state: SlotState, caches, logits, slots,
                              fills, pages_b, temps, topps, keys, *,
                              n_rows: int) -> SlotState:
    """Paged ``_insert_slots_batch``: one scatter lands every admitted
    row's prefill pages AND block-table rows. Shape-padding rows carry
    the out-of-bounds slot sentinel and all-sentinel page rows, so
    both scatters drop them."""
    def layer(pool, dense):
        ps = pool["k_pages"].shape[1]
        nc = n_rows // ps
        idx = pages_b[:, :nc].reshape(-1)

        def scat(pool_leaf, dense_leaf):
            rows = dense_leaf[:, :n_rows]
            chunks = rows.reshape(
                (rows.shape[0] * nc, ps) + rows.shape[2:])
            return pool_leaf.at[idx].set(
                chunks.astype(pool_leaf.dtype), mode="drop")

        out = dict(pool)
        out["k_pages"] = scat(pool["k_pages"], dense["k"])
        out["v_pages"] = scat(pool["v_pages"], dense["v"])
        if "k_scale_pages" in pool:
            out["k_scale_pages"] = scat(pool["k_scale_pages"],
                                        dense["k_scale"])
            out["v_scale_pages"] = scat(pool["v_scale_pages"],
                                        dense["v_scale"])
        out["block_table"] = pool["block_table"].at[slots].set(
            pages_b, mode="drop")
        out["index"] = jnp.maximum(pool["index"], dense["index"])
        return out

    cache = _map_paged_layers(state.cache, layer, caches)
    return SlotState(
        cache=cache,
        positions=state.positions.at[slots].set(fills, mode="drop"),
        last_logits=state.last_logits.at[slots].set(logits, mode="drop"),
        live=state.live.at[slots].set(True, mode="drop"),
        temps=state.temps.at[slots].set(temps, mode="drop"),
        topps=state.topps.at[slots].set(topps, mode="drop"),
        keys=state.keys.at[slots].set(keys, mode="drop"))


@functools.partial(jax.jit, static_argnames=("model",))
def _paged_prefill_chunk(model: CausalLM, params, state: SlotState,
                         padded, fill, true_len, row):
    """One chunked-prefill piece written STRAIGHT into the page pool
    (no dense staging cache, no scatter): a batch-1 multi-token
    slot-decode forward whose cache view aliases the shared pool
    leaves but substitutes ``row`` (the admission's sentinel-padded
    page allocation) for the block table — the SLOT STATE's own table
    row stays at the sentinel until activation, so interleaved decode
    chunks' dead-row writes for the reserved slot drop instead of
    corrupting the half-written prompt. Returns ``(state with updated
    pool leaves, logits at the piece's last REAL token)``. Width is
    static: one compiled program per piece width."""
    from pyspark_tf_gke_tpu.ops.quant import dequantize_tree

    def view(pool):
        out = dict(pool)
        out["block_table"] = row[None]
        return out

    cache1 = _map_paged_layers(state.cache, view)
    w = padded.shape[1]
    positions = (fill + jnp.arange(w))[None, :]
    logits, mutated = model.apply(
        {"params": dequantize_tree(params), "cache": cache1}, padded,
        decode=True, slot_decode=True, positions=positions,
        mutable=["cache"])

    def merge(pool, new):
        out = dict(pool)
        for key in ("k_pages", "v_pages", "k_scale_pages",
                    "v_scale_pages"):
            if key in pool:
                out[key] = new[key]
        out["index"] = jnp.maximum(pool["index"], new["index"])
        return out

    cache = _map_paged_layers(state.cache, merge, mutated["cache"])
    last = jnp.take_along_axis(
        logits, (true_len - 1)[None, None, None], axis=1)[:, 0]
    return state._replace(cache=cache), last


@jax.jit
def _activate_slot_paged(state: SlotState, slot, row, fill, logits1,
                         temp, topp, key) -> SlotState:
    """Chunked-prefill admission complete: point the slot's block-table
    row at the admission's pages (every piece already lives in them)
    and flip the slot live with its fill level, carried logits and
    sampling lane — the paged analog of ``_insert_slot`` with no cache
    rows to move."""
    def layer(pool):
        out = dict(pool)
        out["block_table"] = pool["block_table"].at[slot].set(row)
        return out

    return SlotState(
        cache=_map_paged_layers(state.cache, layer),
        positions=state.positions.at[slot].set(fill),
        last_logits=state.last_logits.at[slot].set(logits1[0]),
        live=state.live.at[slot].set(True),
        temps=state.temps.at[slot].set(temp),
        topps=state.topps.at[slot].set(topp),
        keys=state.keys.at[slot].set(key))


@jax.jit
def _copy_page(state: SlotState, src, dst):
    """Copy-on-write clone of one KV page (every layer's K/V leaves,
    int8 scale pages included): the radix prefix cache shares FULL
    pages read-only, but a match that ends inside a partially-filled
    tail page must clone it before the new slot can append its suffix
    rows there — the source page may be read concurrently by the trie
    and other slots. Whole-page copy (static shape, one compiled
    program for any src/dst pair); rows past the matched fill are
    garbage the suffix prefill overwrites or the fill mask hides."""
    def layer(pool):
        out = dict(pool)
        for key in ("k_pages", "v_pages", "k_scale_pages",
                    "v_scale_pages"):
            if key in pool:
                out[key] = pool[key].at[dst].set(pool[key][src],
                                                 mode="drop")
        return out

    return state._replace(cache=_map_paged_layers(state.cache, layer))


# the paged leaves that travel in a KV-page transfer, in WIRE ORDER —
# export, import, and the OP_KV_XFER replay all iterate this tuple, so
# the per-layer payload dicts line up across processes and replicas
_KV_XFER_KEYS = ("k_pages", "v_pages", "k_scale_pages", "v_scale_pages")


@jax.jit
def _gather_pages(state: SlotState, idx):
    """Gather the rows of pages ``idx`` from every layer's pool leaves
    (K/V pages, int8 scale pages included) — the prefill side of a
    disaggregated KV handoff. Returns one dict per paged layer in tree
    walk order. Out-of-range (sentinel-padded) indices clamp; the
    caller slices the real rows off the host copy."""
    out = []

    def layer(pool):
        out.append({key: pool[key][idx] for key in _KV_XFER_KEYS
                    if key in pool})
        return pool

    _map_paged_layers(state.cache, layer)
    return out


@jax.jit
def _install_pages(state: SlotState, idx, blobs):
    """Scatter transferred KV page rows into the pool at physical
    indices ``idx`` (one dict per paged layer, float32 on the wire —
    cast back to each leaf's pool dtype; sentinel-padded indices
    drop) — the decode side of a disaggregated KV handoff."""
    it = iter(blobs)

    def layer(pool):
        rec = next(it)
        out = dict(pool)
        for key in _KV_XFER_KEYS:
            if key in pool:
                out[key] = pool[key].at[idx].set(
                    rec[key].astype(pool[key].dtype), mode="drop")
        return out

    return state._replace(cache=_map_paged_layers(state.cache, layer))


@jax.jit
def _clear_live_paged(state: SlotState, slot):
    """Paged free: drop the live flag AND reset the slot's block-table
    row to the sentinel, so in-flight dead-row replays (decode-ahead)
    scatter nowhere instead of into pages the engine is about to hand
    to another request."""
    def layer(pool):
        out = dict(pool)
        n = pool["k_pages"].shape[0]
        mp = pool["block_table"].shape[1]
        out["block_table"] = pool["block_table"].at[slot].set(
            jnp.full((mp,), n, jnp.int32))
        return out

    return state._replace(
        cache=_map_paged_layers(state.cache, layer),
        live=state.live.at[slot].set(False))


@functools.partial(jax.jit, static_argnames=("num_slots", "vocab"))
def _zeros_state(cache1, *, num_slots: int, vocab: int) -> SlotState:
    """Fresh slot-pool state shaped after one prefill's cache tree."""
    b = num_slots
    cache = jax.tree.map(
        lambda row: (jnp.zeros_like(row) if row.ndim == 0
                     else jnp.zeros((b,) + row.shape[1:], row.dtype)),
        cache1)
    return SlotState(
        cache=cache,
        positions=jnp.zeros((b,), jnp.int32),
        last_logits=jnp.zeros((b, vocab), jnp.float32),
        live=jnp.zeros((b,), bool),
        temps=jnp.zeros((b,), jnp.float32),
        topps=jnp.ones((b,), jnp.float32),
        keys=jnp.zeros((b, 2), jnp.uint32))


@jax.jit
def _insert_slots_batch(state: SlotState, caches, logits, slots, fills,
                        temps, topps, keys) -> SlotState:
    """Batched ``_insert_slot``: scatter a batched prefill's rows into
    the slot pool in ONE compiled program. The first cut looped batch-1
    inserts over sliced rows — hundreds of tiny slice/insert dispatches
    whose submission overhead UNDID the batched prefill's win (1774 ->
    1197 tok/s in the 2026-08 trail, at ~70 ms dispatch latency; not
    measured on a local chip). Every operand is
    padded to the power-of-two batch ``k_pad`` by the caller and
    ``slots`` is a traced [k_pad] index vector whose pad entries hold
    the OUT-OF-BOUNDS sentinel ``num_slots`` — jnp scatter drops
    out-of-bounds updates, so pad rows never land and the program count
    stays one per k_pad shape (a static real-k argument would have
    compiled one program per group size 2..num_slots, paid inside the
    first measured serving run)."""
    cache = jax.tree.map(
        lambda big, rows: (jnp.maximum(big, rows) if rows.ndim == 0
                           else big.at[slots].set(rows, mode="drop")),
        state.cache, caches)
    return SlotState(
        cache=cache,
        positions=state.positions.at[slots].set(fills, mode="drop"),
        last_logits=state.last_logits.at[slots].set(logits, mode="drop"),
        live=state.live.at[slots].set(True, mode="drop"),
        temps=state.temps.at[slots].set(temps, mode="drop"),
        topps=state.topps.at[slots].set(topps, mode="drop"),
        keys=state.keys.at[slots].set(keys, mode="drop"))


@jax.jit
def _insert_slot(state: SlotState, cache1, logits1, slot, fill,
                 temp, topp, key) -> SlotState:
    """Drop a prefilled request into slot ``slot`` (traced scalar — one
    compiled program serves every slot): cache rows, fill level, carried
    logits, live flag, sampling lane."""
    # Scalar leaves are the per-layer `index` fill counters — unused by
    # slot mode (per-row positions are the authority) but kept
    # conservative (max) so any non-slot reader of the cache var sees a
    # safe fill level.
    cache = jax.tree.map(
        lambda big, row: (jnp.maximum(big, row) if row.ndim == 0
                          else big.at[slot].set(row[0])),
        state.cache, cache1)
    return SlotState(
        cache=cache,
        positions=state.positions.at[slot].set(fill),
        last_logits=state.last_logits.at[slot].set(logits1[0]),
        live=state.live.at[slot].set(True),
        temps=state.temps.at[slot].set(temp),
        topps=state.topps.at[slot].set(topp),
        keys=state.keys.at[slot].set(key))


def _pick_tokens(logits, temps, topps, keys, *, sampling: bool,
                 mesh=None):
    """[B] next tokens from [B, V] logits: greedy rows argmax; sampling
    rows categorical over their own scaled, nucleus-filtered
    distribution with their OWN (already-folded) key — reusing the
    parity oracle's _filter_logits (its top_p comparison broadcasts,
    so a [B, 1] per-row mass works; topp=1 keeps everything). Shared
    by the plain decode chunk and the speculative rounds so the two
    lanes cannot drift."""
    from pyspark_tf_gke_tpu.models.causal_lm import _filter_logits

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if not sampling:
        # static: a pure-greedy pool compiles WITHOUT the per-step
        # [B, V] sort/softmax/cumsum/categorical (the dominant
        # serving path pays one argmax, as before sampling existed)
        return greedy
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    if mesh is not None:
        # replicate the tiny [B, V] working set first: the nucleus
        # sort/cumsum over a tp-sharded vocab axis would otherwise
        # compile NEW cross-process collective patterns, and the
        # per-row categorical brings nothing worth sharding — the
        # replicated math keeps the sampled chunk collective-free
        # beyond what the greedy program already does (a fresh
        # communicator mid-serving deadlocked the 2-process wire).
        from jax.sharding import NamedSharding, PartitionSpec

        scaled = jax.lax.with_sharding_constraint(
            scaled, NamedSharding(mesh, PartitionSpec()))
    filtered = _filter_logits(scaled, None, topps[:, None])
    sampled = jax.vmap(jax.random.categorical)(keys, filtered)
    return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)


def _fold_slot_keys(keys_data, n: int):
    """Fold every slot's threefry key forward by ``n`` and return
    ``(new key data [B, 2], key objects [B])`` — the per-use PRNG
    discipline of the sampling lanes."""
    keys = jax.vmap(
        lambda kd: jax.random.fold_in(
            jax.random.wrap_key_data(kd, impl="threefry2x32"), n))(
                keys_data)
    return jax.vmap(jax.random.key_data)(keys), keys


@functools.partial(
    jax.jit, static_argnames=("model", "chunk", "eos_token_id", "pad_id",
                              "sampling", "mesh"))
def _decode_chunk(model: CausalLM, params, state: SlotState, *,
                  chunk: int, eos_token_id: Optional[int],
                  pad_id: int, sampling: bool = False, mesh=None):
    """``chunk`` decode steps for ALL slots in one dispatch.

    Mirrors ``causal_lm._decode``'s emit-then-step order exactly (the
    parity oracle): emit token t from the carried logits, then run the
    model at each row's own position to produce logits t+1. Rows that
    are dead (free slot) or that hit eos keep computing — static shapes
    — but their positions freeze (no cache growth past the fill level)
    and their emitted tokens are ``pad_id``.

    Per-slot sampling: a row with ``temps > 0`` draws from its scaled,
    top-p-filtered distribution with ITS OWN key (folded forward each
    step); temp-0 rows take the argmax, and their token stream is
    bit-identical to an all-greedy chunk (the sampling lanes touch
    nothing they read)."""
    from pyspark_tf_gke_tpu.ops.quant import (dequantize_embeddings,
                                              inloop_dequantize,
                                              is_quantized)

    quantized = is_quantized(params)
    p = dequantize_embeddings(params) if quantized else params

    def pick(logits, temps, topps, keys):
        return _pick_tokens(logits, temps, topps, keys,
                            sampling=sampling, mesh=mesh)

    def step(carry, _):
        st = carry
        if sampling:
            keys = jax.vmap(
                lambda k: jax.random.fold_in(k, 1))(
                    jax.random.wrap_key_data(st.keys, impl="threefry2x32"))
            keys_data = jax.vmap(jax.random.key_data)(keys)
        else:
            keys, keys_data = None, st.keys
        tok = pick(st.last_logits, st.temps, st.topps, keys)
        # Emit BEFORE the eos latch drops `live`: the eos token itself
        # belongs to the output (generate pads WITH eos after it; the
        # host loop truncates inclusively on it).
        live = st.live
        emitted = jnp.where(live, tok, pad_id)
        if eos_token_id is not None:
            live = live & (tok != eos_token_id)
        # Dead rows replay their FROZEN position with a pad token:
        # static shape, no position growth (positions only advance
        # while live). NOT position 0: with radix prefix sharing, page
        # 0 of a slot's block table can be a page SHARED with other
        # slots and the cache — a pad-KV write there would corrupt
        # every reader. The frozen position is one past the row's last
        # real token, always inside its OWN (never-shared) allocation
        # and beyond the extent the prefix cache adopts at free time.
        step_tok = jnp.where(live, tok, pad_id)
        step_pos = st.positions
        logits, mutated = model.apply(
            {"params": inloop_dequantize(p) if quantized else p,
             "cache": st.cache},
            step_tok[:, None], decode=True, slot_decode=True,
            positions=step_pos[:, None], mutable=["cache"])
        st = st._replace(
            cache=mutated["cache"],
            positions=jnp.where(live, st.positions + 1, st.positions),
            last_logits=logits[:, 0],
            live=live,
            keys=keys_data)
        return st, emitted

    state, toks = jax.lax.scan(step, state, None, length=chunk)
    return state, toks.T  # [B, chunk]


# -- self-draft speculative decoding (in-slot draft/verify) -------------------
#
# Per slot, a cheap DRAFT model (a small companion bundle, or the target
# itself — "self-draft" — when none is configured) proposes
# ``spec_tokens`` continuation tokens, then ONE multi-query verify
# forward of the target scores all k+1 positions through the SAME
# chunked slot-decode path chunked prefill uses (paged engines: the
# ``paged_attention_chunk`` kernel — verify IS the S>1 chunk program, no
# new kernel). Accepted tokens advance each slot's fill counter;
# rejected ones roll back by simply NOT advancing it — pages are
# append-only and the position mask hides rows past the fill, so
# rollback is free and the garbage rows are overwritten by the next
# round's writes at the same positions. The acceptance rule lives in
# ``models/speculative.py`` (greedy exact; sampled lanes use the
# standard rejection rule) — ONE implementation shared with the
# standalone ``spec`` workload.
#
# The draft runs a DENSE slot cache of its own (``[num_slots,
# draft_max_seq, ...]`` rows sharing the target's per-slot fill
# counters): drafts are cheap and transient, and a paged draft pool
# would double the page-accounting surface for no bandwidth win. Draft
# contents NEVER affect correctness — a cold/garbage draft row just
# proposes tokens the verify rejects.


@functools.partial(jax.jit, static_argnames=("model", "num_slots"))
def _draft_zeros_cache(model: CausalLM, params, *, num_slots: int):
    """Fresh dense draft slot cache, built by one throwaway slot-decode
    forward (the same template trick as ``_paged_zeros_state``) and
    zeroed."""
    from pyspark_tf_gke_tpu.ops.quant import dequantize_tree

    tok = jnp.zeros((num_slots, 1), jnp.int32)
    pos = jnp.zeros((num_slots, 1), jnp.int32)
    _, mutated = model.apply(
        {"params": dequantize_tree(params)}, tok, decode=True,
        slot_decode=True, positions=pos, mutable=["cache"])
    return jax.tree.map(jnp.zeros_like, mutated["cache"])


@jax.jit
def _insert_draft_row(dcache, cache1, slot):
    """Drop a batch-1 draft prefill's cache rows into draft slot
    ``slot`` (the draft-side analog of ``_insert_slot``'s cache move;
    dense prefill caches are full ``max_seq_len`` rows, so shapes line
    up by construction)."""
    return jax.tree.map(
        lambda big, row: (jnp.maximum(big, row) if row.ndim == 0
                          else big.at[slot].set(row[0])),
        dcache, cache1)


@jax.jit
def _insert_draft_rows_batch(dcache, caches, slots):
    """Batched draft-row insert (rides the batched-admission fast
    path); pad rows carry the out-of-bounds slot sentinel and drop."""
    return jax.tree.map(
        lambda big, rows: (jnp.maximum(big, rows) if rows.ndim == 0
                           else big.at[slots].set(rows, mode="drop")),
        dcache, caches)


@functools.partial(
    jax.jit, static_argnames=("model", "draft_model", "rounds", "k",
                              "eos_token_id", "pad_id", "sampling",
                              "mesh"))
def _spec_chunk(model: CausalLM, params, draft_model: CausalLM,
                draft_params, state: SlotState, dcache, *, rounds: int,
                k: int, eos_token_id: Optional[int], pad_id: int,
                sampling: bool = False, mesh=None):
    """``rounds`` speculative draft/verify rounds for ALL slots in one
    dispatch — the spec-mode replacement for ``_decode_chunk``.

    Structure (per round, batched over slots): the carried PENDING
    token (emitted last round/entry, not yet fed) seeds a draft scan of
    k+1 single-token draft forwards proposing d_1..d_k (the final
    proposal is fed too, so the draft cache never gaps on a fully
    accepted round), then ONE (k+1)-wide verify forward of the target
    feeds [pending, d_1..d_k] at positions fill..fill+k — writing their
    K/V and scoring every position through the chunked slot-decode
    path. ``accept_and_correct`` (models/speculative.py) yields the
    accepted length and the correction/bonus token; the round emits
    [d_1..d_a, correction] (1..k+1 tokens), advances fill by exactly
    the emitted count (rejected rows beyond stay invisible — rollback
    is the fill counter), and eos anywhere in the window truncates it
    and drops the row live flag, mirroring the plain chunk's
    emit-then-latch order.

    Entry emits one token from the carried logits (exactly a plain
    step's emit) to seed the first pending; exit feeds the final
    pending token through target AND draft (one single-token step) so
    ``last_logits``/``positions`` leave in the plain chunk's invariant
    — spec and non-spec chunks interleave freely and admissions see an
    unchanged contract.

    Returns ``(state, dcache, packed)`` where ``packed`` is ONE int32
    array ``[rounds·(k+1) + 3·rounds + 2, B]`` stacking the per-round
    emission windows, their valid lengths (the host-side compaction
    gate — window tails past it are pad), the accepted/proposed counts
    (the accept-rate plane) and the entry-token/final-live rows — one
    device→host transfer (one gather on multi-process meshes) per
    collect instead of six. ``_unpack_spec`` is the host-side
    inverse."""
    from pyspark_tf_gke_tpu.models.speculative import (accept_and_correct,
                                                       emit_window)
    from pyspark_tf_gke_tpu.ops.quant import (dequantize_embeddings,
                                              inloop_dequantize,
                                              is_quantized)

    t_quant = is_quantized(params)
    p_t = dequantize_embeddings(params) if t_quant else params
    d_quant = is_quantized(draft_params)
    p_d = dequantize_embeddings(draft_params) if d_quant else draft_params
    b = state.live.shape[0]
    width = k + 1
    iota_w = jnp.arange(width, dtype=jnp.int32)

    def tparams():
        return inloop_dequantize(p_t) if t_quant else p_t

    def dparams():
        return inloop_dequantize(p_d) if d_quant else p_d

    # entry: emit one token from the carried logits (the plain chunk's
    # emit-then-step order — the eos token itself belongs to the output)
    keys_data = state.keys
    if sampling:
        keys_data, keys = _fold_slot_keys(keys_data, 1)
    else:
        keys = None
    t0 = _pick_tokens(state.last_logits, state.temps, state.topps, keys,
                      sampling=sampling, mesh=mesh)
    live0 = state.live
    entry_tok = jnp.where(live0, t0, pad_id)
    live = live0
    if eos_token_id is not None:
        live = live & (t0 != eos_token_id)
    pending = jnp.where(live, t0, pad_id)

    def round_fn(carry, _):
        cache, dc, positions, live, pending, keys_data = carry

        # 1. draft: k+1 cheap single-token forwards propose d_1..d_k
        #    (feeding pending first, then each proposal — including
        #    d_k, whose K/V a fully-accepted round needs next time)
        def dstep(dcarry, j):
            dc, cur, kd = dcarry
            feed = jnp.where(live, cur, pad_id)
            logits, mutated = draft_model.apply(
                {"params": dparams(), "cache": dc}, feed[:, None],
                decode=True, slot_decode=True,
                positions=(positions + j)[:, None], mutable=["cache"])
            lg = logits[:, 0]
            if sampling:
                kd, kk = _fold_slot_keys(kd, 3)
            else:
                kk = None
            nxt = _pick_tokens(lg, state.temps, state.topps, kk,
                               sampling=sampling, mesh=mesh)
            return (mutated["cache"], nxt, kd), (nxt, lg)

        (dc, d_last, dkd), (draft_toks, draft_logits) = jax.lax.scan(
            dstep, (dc, pending, keys_data),
            jnp.arange(k, dtype=jnp.int32))
        if sampling:
            keys_data = dkd
        drafts = draft_toks.T                              # [B, k]
        dlogits = jnp.moveaxis(draft_logits, 0, 1)         # [B, k, V]
        # feed the final proposal d_k too (cache rows only — nobody
        # reads these logits, and return_hidden skips the lm_head)
        _, mutated = draft_model.apply(
            {"params": dparams(), "cache": dc},
            jnp.where(live, d_last, pad_id)[:, None], decode=True,
            slot_decode=True, positions=(positions + k)[:, None],
            return_hidden=True, mutable=["cache"])
        dc = mutated["cache"]

        # 2. verify: ONE (k+1)-wide chunk forward writes K/V for
        #    [pending, d_1..d_k] at fill..fill+k and scores every
        #    position (paged: the paged_attention_chunk S>1 program;
        #    dead rows feed pad at frozen consecutive positions —
        #    their writes drop via the sentinel table / land past the
        #    fill mask)
        vchunk = jnp.concatenate([pending[:, None], drafts], axis=1)
        vchunk = jnp.where(live[:, None], vchunk, pad_id)
        pos_v = positions[:, None] + iota_w[None, :]
        logits_v, mutated = model.apply(
            {"params": tparams(), "cache": cache}, vchunk, decode=True,
            slot_decode=True, positions=pos_v, mutable=["cache"])
        cache = mutated["cache"]

        # 3. accept + correct (THE shared rule)
        if sampling:
            keys_data, akeys = _fold_slot_keys(keys_data, 4)
            adata = jax.vmap(jax.random.key_data)(akeys)
            a, correction = accept_and_correct(
                drafts, dlogits, logits_v, temps=state.temps,
                topps=state.topps, keys=adata, mesh=mesh)
        else:
            a, correction = accept_and_correct(drafts, dlogits, logits_v)

        # 4. emit window + eos latch + fill advance (= rollback)
        window = emit_window(drafts, correction, a)        # [B, k+1]
        if eos_token_id is not None:
            is_eos = (window == eos_token_id) & (iota_w[None]
                                                 <= a[:, None])
            any_eos = jnp.any(is_eos, axis=1)
            eos_idx = jnp.argmax(is_eos, axis=1)
            vlen = jnp.where(any_eos, eos_idx + 1, a + 1)
            newlive = live & jnp.logical_not(any_eos)
        else:
            vlen = a + 1
            newlive = live
        vlen = jnp.where(live, vlen, 0)
        emitted = jnp.where(iota_w[None] < vlen[:, None], window, pad_id)
        # fed-valid rows this round = pending + the accepted drafts
        # before any eos — exactly the emitted count (the correction is
        # emitted-not-fed, eos is emitted-not-fed; both balance out)
        positions = positions + vlen
        proposed = jnp.where(live, k, 0).astype(jnp.int32)
        accepted = jnp.where(live, a, 0).astype(jnp.int32)
        pending = jnp.where(newlive, correction, pad_id)
        return ((cache, dc, positions, newlive, pending, keys_data),
                (emitted, vlen, accepted, proposed))

    init = (state.cache, dcache, state.positions, live, pending,
            keys_data)
    ((cache, dcache, positions, live, pending, keys_data),
     (windows, wlens, accepted, proposed)) = jax.lax.scan(
        round_fn, init, None, length=rounds)

    # exit: feed the final pending token through target AND draft so the
    # carried state leaves in the plain chunk's invariant (last_logits
    # predicts the next unemitted token; every emitted token is fed)
    step_tok = jnp.where(live, pending, pad_id)
    logits, mutated = model.apply(
        {"params": tparams(), "cache": cache}, step_tok[:, None],
        decode=True, slot_decode=True, positions=positions[:, None],
        mutable=["cache"])
    _, dmut = draft_model.apply(
        {"params": dparams(), "cache": dcache}, step_tok[:, None],
        decode=True, slot_decode=True, positions=positions[:, None],
        return_hidden=True, mutable=["cache"])
    state = state._replace(
        cache=mutated["cache"],
        positions=jnp.where(live, positions + 1, positions),
        last_logits=logits[:, 0],
        live=live,
        keys=keys_data)
    packed = jnp.concatenate([
        windows.transpose(0, 2, 1).reshape(rounds * width, b),
        wlens, accepted, proposed,
        entry_tok[None].astype(jnp.int32),
        state.live.astype(jnp.int32)[None]], axis=0)
    return state, dmut["cache"], packed


def _unpack_spec(packed: np.ndarray, k: int):
    """Host-side inverse of ``_spec_chunk``'s packed output: returns
    ``(entry_tok [B], windows [rounds, k+1, B], wlens [rounds, B],
    accepted [rounds, B], proposed [rounds, B], live [B] bool)``."""
    width = k + 1
    rounds = (packed.shape[0] - 2) // (width + 3)
    wrows = rounds * width
    windows = packed[:wrows].reshape(rounds, width, -1)
    wlens = packed[wrows:wrows + rounds]
    accepted = packed[wrows + rounds:wrows + 2 * rounds]
    proposed = packed[wrows + 2 * rounds:wrows + 3 * rounds]
    return (packed[-2], windows, wlens, accepted, proposed,
            packed[-1] > 0)


class SlotDeviceState:
    """The engine's DEVICE half: the slot arrays plus the three
    replayable ops that mutate them (admit / chunk / free). Split from
    the host-side bookkeeping so multi-host serving can run the exact
    same op sequence on every process: process 0's engine announces
    each op over the serving wire and the workers' ``serve_worker_loop``
    replays it into their own ``SlotDeviceState`` — identical inputs in
    identical order is the whole SPMD contract.

    The chunk op ends with ``as_host_array`` gathers on the emitted
    tokens and live flags. That is a collective on multi-process meshes,
    so it is INSIDE the replayed op (every process participates), not a
    process-0 afterthought."""

    def __init__(self, model: CausalLM, params, num_slots: int,
                 mesh=None, draft_model: Optional[CausalLM] = None,
                 draft_params=None, spec_tokens: int = 0):
        self.model, self.params = model, params
        self.num_slots = num_slots
        self.mesh = mesh
        self.paged = bool(getattr(model.cfg, "paged_kv", False))
        self.state: Optional[SlotState] = None
        # speculative decoding: the draft pair + its dense slot cache.
        # No draft configured -> SELF-draft (the target proposes for
        # itself through a dense shadow cache — zero-config correctness
        # mode; a small companion bundle is the perf configuration).
        # Resolution is LAZY so worker replicas built before any spec
        # op (spec_tokens unknown until the first spec chunk header)
        # stay cheap.
        self.spec_tokens = int(spec_tokens)
        self.draft_model, self.draft_params = draft_model, draft_params
        self._draft_resolved = False
        self.draft_cache = None
        if draft_model is not None or self.spec_tokens:
            self._resolve_draft()

    def _resolve_draft(self) -> None:
        if self.draft_model is None:
            self.draft_model, self.draft_params = self.model, self.params
        if getattr(self.draft_model.cfg, "paged_kv", False):
            # the draft always runs the dense slot-cache layout: cheap,
            # transient, and never part of the page-pool accounting
            import dataclasses as _dc

            self.draft_model = CausalLM(
                _dc.replace(self.draft_model.cfg, kv_num_pages=None),
                self.draft_model.mesh)
        self._draft_resolved = True

    def _ensure_draft_cache(self) -> None:
        if not self._draft_resolved:
            self._resolve_draft()
        if self.draft_cache is None:
            self.draft_cache = _draft_zeros_cache(
                self.draft_model, self.draft_params,
                num_slots=self.num_slots)

    def draft_prefill_row(self, padded: np.ndarray, true_len: int,
                          slot: int) -> None:
        """Prefill the DRAFT model on the full (right-padded) prompt
        and drop its cache rows into draft slot ``slot`` — the draft's
        half of an admission (replayed on workers via the OP_CB_ADMIT
        draft payload). ``padded`` width must fit the draft's
        max_seq_len (the engine skips the call for prompts that
        don't — a cold draft row only costs acceptance, never
        correctness)."""
        with self._mesh_ctx():
            self._ensure_draft_cache()
            cache1, _ = _prefill_padded(
                self.draft_model, self.draft_params, jnp.asarray(padded),
                jnp.asarray(true_len, jnp.int32))
            self.draft_cache = _insert_draft_row(
                self.draft_cache, cache1, jnp.asarray(slot, jnp.int32))

    def draft_prefill_rows_batch(self, padded: np.ndarray, true_lens,
                                 slots) -> None:
        """Batched draft prefill for the batched-admission fast path
        (single-host only, like the target-side batch admit)."""
        k, k_pad = len(slots), padded.shape[0]
        slot_idx = np.full((k_pad,), self.num_slots, np.int32)
        slot_idx[:k] = slots
        with self._mesh_ctx():
            self._ensure_draft_cache()
            caches, _ = _prefill_padded_batch(
                self.draft_model, self.draft_params, jnp.asarray(padded),
                jnp.asarray(true_lens, jnp.int32))
            self.draft_cache = _insert_draft_rows_batch(
                self.draft_cache, caches, jnp.asarray(slot_idx))

    def spec_chunk_async(self, rounds: int, eos_token_id: Optional[int],
                         pad_id: int, sampling: bool = False,
                         k: Optional[int] = None):
        """Dispatch one speculative chunk (``rounds`` draft/verify
        rounds over all slots) WITHOUT reading back: returns a 1-tuple
        holding the PACKED int32 result array (``_unpack_spec`` is the
        host-side inverse) — the spec analog of :meth:`chunk_async`.
        ``k`` overrides the construction-time spec width (worker
        replicas learn it from each chunk header)."""
        with self._mesh_ctx():
            self._ensure_draft_cache()
            self.state, self.draft_cache, packed = _spec_chunk(
                self.model, self.params, self.draft_model,
                self.draft_params, self.state, self.draft_cache,
                rounds=rounds,
                k=int(k) if k is not None else self.spec_tokens,
                eos_token_id=eos_token_id, pad_id=pad_id,
                sampling=sampling, mesh=self.mesh)
            return (packed,)

    def fetch_tuple(self, arrays):
        """Materialize a dispatched chunk's device arrays on the host
        (any arity — a plain chunk is (tokens, live), a spec chunk ONE
        packed array; gathered on multi-process meshes so every
        process reads them)."""
        from pyspark_tf_gke_tpu.parallel.distributed import as_host_array

        with self._mesh_ctx():
            return tuple(np.asarray(as_host_array(a)) for a in arrays)

    def spec_chunk(self, rounds: int, eos_token_id: Optional[int],
                   pad_id: int, sampling: bool = False,
                   k: Optional[int] = None):
        """Dispatch + immediate readback (unpipelined spec path)."""
        return self.fetch_tuple(self.spec_chunk_async(
            rounds, eos_token_id, pad_id, sampling=sampling, k=k))

    def _mesh_ctx(self):
        return self.mesh if self.mesh is not None else (
            contextlib.nullcontext())

    def _init_state(self, cache1):
        # Inside a jit (under the caller's mesh context) so the zeros
        # come out as GLOBAL arrays on multi-process meshes — eager
        # jnp.zeros would commit to local devices and refuse to mix
        # with the mesh-spanning prefill outputs.
        if self.paged:
            # paged shapes come from the model config, not the dense
            # prefill template
            return _paged_zeros_state(self.model, self.params,
                                      num_slots=self.num_slots)
        return _zeros_state(cache1, num_slots=self.num_slots,
                            vocab=self.model.cfg.vocab_size)

    def insert(self, cache1, logits1, slot: int, fill: int,
               temperature: float = 0.0, top_p: float = 1.0,
               seed: int = 0, pages=None, n_rows: Optional[int] = None
               ) -> None:
        """Drop a prefilled/extended batch-1 tree into ``slot`` at
        ``fill`` with its sampling lane (temperature 0 = greedy).
        Paged mode additionally needs the slot's page allocation
        (``pages``, sentinel-padded) and the dense row count to
        scatter (``n_rows``, the padded bucket width)."""
        with self._mesh_ctx():
            if self.state is None:
                self.state = self._init_state(cache1)
            if self.paged:
                if pages is None or n_rows is None:
                    raise ValueError(
                        "paged insert needs pages + n_rows (the "
                        "engine allocates pages at admission)")
                self.state = _insert_slot_paged(
                    self.state, cache1, logits1,
                    np.int32(slot), np.int32(fill),
                    np.asarray(pages, np.int32),
                    np.float32(temperature), np.float32(top_p),
                    _seed_key_data(seed), n_rows=int(n_rows))
                return
            self.state = _insert_slot(
                self.state, cache1, logits1,
                np.int32(slot), np.int32(fill),
                np.float32(temperature), np.float32(top_p),
                _seed_key_data(seed))

    def admit_padded(self, padded: np.ndarray, true_len: int,
                     slot: int, temperature: float = 0.0,
                     top_p: float = 1.0, seed: int = 0,
                     pages=None) -> None:
        """Prefill a right-padded [1, S_bucket] prompt and insert it
        into ``slot`` at fill level ``true_len`` (``pages``: the
        slot's page allocation, paged mode only)."""
        with self._mesh_ctx():
            cache1, logits1 = _prefill_padded(
                self.model, self.params, np.asarray(padded),
                np.int32(true_len))
        self.insert(cache1, logits1, slot, true_len,
                    temperature=temperature, top_p=top_p, seed=seed,
                    pages=pages, n_rows=padded.shape[1])

    def admit_padded_batch(self, padded: np.ndarray, true_lens,
                           slots, samplings, pages=None) -> None:
        """ONE batched prefill + ONE batched slot scatter admits
        ``len(slots)`` requests; rows past ``len(slots)`` are shape
        padding (computed, never inserted — their scatter index is the
        out-of-bounds sentinel). Two async device ops total — no
        readback, no RTT, no per-row dispatch chatter."""
        k, k_pad = len(slots), padded.shape[0]
        slot_idx = np.full((k_pad,), self.num_slots, np.int32)
        slot_idx[:k] = slots  # pad rows -> OOB sentinel, dropped
        temps = np.zeros((k_pad,), np.float32)
        topps = np.ones((k_pad,), np.float32)
        temps[:k] = [s[0] for s in samplings]
        topps[:k] = [s[1] for s in samplings]
        # keys assemble on the HOST when every row takes
        # _seed_key_data's numpy fast path (the common case — serving
        # seeds are uint32): zero eager device ops, one transfer at
        # the jit boundary below. A row with an out-of-range seed
        # comes back as a device array, and the whole stack falls back
        # to jnp (np.asarray on it would be a synchronous
        # device->host readback per row — k+1 blocking syncs that the
        # solo admit path never pays; at ~70 ms per sync batched
        # admission LOST its own win to them — not measured on a
        # local chip).
        key_rows = ([_seed_key_data(s[2]) for s in samplings]
                    + [np.zeros((2,), np.uint32)] * (k_pad - k))
        if all(isinstance(r, np.ndarray) for r in key_rows):
            keys = np.stack(key_rows)
        else:
            keys = jnp.stack([jnp.asarray(r) for r in key_rows])
        true_lens = np.asarray(true_lens, np.int32)
        # numpy args flow straight into the jitted callees — the jit
        # boundary moves them host->device in one C++ pass, cheaper
        # than a Python-level eager device_put per array
        with self._mesh_ctx():
            caches, logits = _prefill_padded_batch(
                self.model, self.params, np.asarray(padded), true_lens)
            if self.state is None:
                # _zeros_state only reads shape[1:] per leaf, so the
                # k-row tree is as good a template as a batch-1 one
                self.state = self._init_state(caches)
            if self.paged:
                if pages is None:
                    raise ValueError(
                        "paged batch insert needs per-row pages")
                self.state = _insert_slots_batch_paged(
                    self.state, caches, logits, slot_idx, true_lens,
                    np.asarray(pages, np.int32),
                    temps, topps, keys, n_rows=padded.shape[1])
            else:
                self.state = _insert_slots_batch(
                    self.state, caches, logits, slot_idx, true_lens,
                    temps, topps, keys)

    def prefill_chunk(self, padded: np.ndarray, fill: int,
                      true_len: int, row):
        """Write one chunked-prefill piece straight into the page pool
        through ``row`` (paged models only). The slot's own table row
        keeps the sentinel until :meth:`activate_slot`. Returns the
        piece's last-real-token logits as a DEVICE array (no readback
        — only the final piece's logits are ever consumed, by the
        activation)."""
        if not self.paged:
            raise ValueError(
                "prefill_chunk writes into the paged pool; dense "
                "engines stage chunked prefill on batch-1 trees")
        with self._mesh_ctx():
            if self.state is None:
                self.state = self._init_state(None)  # paged shapes come
                #   from the model config, not a prefill template
            self.state, logits1 = _paged_prefill_chunk(
                self.model, self.params, self.state, np.asarray(padded),
                np.int32(fill), np.int32(true_len), np.int32(row))
            return logits1

    def activate_slot(self, slot: int, fill: int, logits1, row,
                      temperature: float = 0.0, top_p: float = 1.0,
                      seed: int = 0) -> None:
        """Flip a chunk-admitted slot live: block-table row, fill
        level, carried logits, sampling lane (paged models only)."""
        with self._mesh_ctx():
            self.state = _activate_slot_paged(
                self.state, np.int32(slot), np.int32(row),
                np.int32(fill), logits1,
                np.float32(temperature), np.float32(top_p),
                _seed_key_data(seed))

    def copy_page(self, src: int, dst: int) -> None:
        """Clone page ``src`` into page ``dst`` across every layer's
        pool leaves (the radix cache's copy-on-write; paged models
        only). Replayed on workers via the OP_CB_ADMIT cow payload."""
        with self._mesh_ctx():
            if self.state is None:
                self.state = self._init_state(None)
            self.state = _copy_page(
                self.state, np.int32(src), np.int32(dst))

    def read_pages(self, pages) -> List[dict]:
        """Gather physical pages ``pages`` to the host: one dict per
        paged layer (k_pages/v_pages [+ scale pages]) with the page
        rows in request order (paged models only) — the export half
        of a disaggregated KV handoff. The index vector is padded to
        a power of two so the gather compiles one program per size
        class, not per transfer."""
        if not self.paged:
            raise ValueError(
                "read_pages needs the paged cache layout")
        from pyspark_tf_gke_tpu.parallel.distributed import as_host_array

        n = len(pages)
        cap = 1 << max(0, (n - 1).bit_length())
        idx = np.zeros((cap,), np.int32)
        idx[:n] = pages  # pad rows re-read page 0; sliced off below
        with self._mesh_ctx():
            if self.state is None:
                self.state = self._init_state(None)
            gathered = _gather_pages(self.state, idx)
            return [{key: np.asarray(as_host_array(leaf))[:n]
                     for key, leaf in rec.items()} for rec in gathered]

    def write_pages(self, pages, blobs) -> None:
        """Install transferred KV page rows at physical indices
        ``pages`` (paged models only) — the import half of a
        disaggregated KV handoff, replayed on workers via OP_KV_XFER.
        ``blobs`` is one dict per paged layer with ``len(pages)``
        leading rows per leaf. Padded to a power of two (sentinel
        indices drop) to bound compiled-program count."""
        if not self.paged:
            raise ValueError(
                "write_pages needs the paged cache layout")
        n = len(pages)
        cap = 1 << max(0, (n - 1).bit_length())
        idx = np.full((cap,), self.model.cfg.kv_num_pages, np.int32)
        idx[:n] = pages
        padded = []
        for rec in blobs:
            out = {}
            for key, leaf in rec.items():
                leaf = np.asarray(leaf)
                if leaf.shape[0] < cap:
                    leaf = np.concatenate(
                        [leaf, np.zeros((cap - leaf.shape[0],)
                                        + leaf.shape[1:], leaf.dtype)])
                out[key] = leaf
            padded.append(out)
        with self._mesh_ctx():
            if self.state is None:
                self.state = self._init_state(None)
            self.state = _install_pages(self.state, idx, padded)

    def chunk_async(self, chunk: int, eos_token_id: Optional[int],
                    pad_id: int, sampling: bool = False):
        """Dispatch one decode chunk over all slots (``sampling``
        static: the pure-greedy pool compiles without the sampling
        math) WITHOUT reading the result back: returns device arrays
        (tokens [B, chunk], live [B]). The caller chooses when to pay
        the device->host sync — the decode-ahead pipeline defers it one
        chunk so the readback latency overlaps the next chunk's
        compute."""
        with self._mesh_ctx():
            self.state, toks = _decode_chunk(
                self.model, self.params, self.state, chunk=chunk,
                eos_token_id=eos_token_id, pad_id=pad_id,
                sampling=sampling, mesh=self.mesh)
            return toks, self.state.live

    def fetch(self, toks, live):
        """Materialize a dispatched chunk's results on the host —
        gathered on multi-process meshes so every process can read
        them (the two-array plain-chunk case of :meth:`fetch_tuple`)."""
        return self.fetch_tuple((toks, live))

    def chunk(self, chunk: int, eos_token_id: Optional[int],
              pad_id: int, sampling: bool = False):
        """Dispatch + immediate readback (the unpipelined path)."""
        return self.fetch(*self.chunk_async(chunk, eos_token_id, pad_id,
                                            sampling=sampling))

    def free(self, slot: int) -> None:
        """Drop a slot's live flag (request finished or cancelled)."""
        if self.state is None:
            return
        with self._mesh_ctx():
            # jitted (not eager .at) so the update runs SPMD on global
            # multi-process arrays like every other replayed op; paged
            # mode also resets the slot's block-table row to the
            # sentinel (its pages are about to return to the pool)
            clear = _clear_live_paged if self.paged else _clear_live
            self.state = clear(self.state, np.int32(slot))


def _array_leaves(x):
    """Flatten a dispatched chunk's result pytree (arrays, tuples of
    arrays) into its array leaves — stdlib recursion, no jax tree
    utils, so host-array results (announce gathers) walk the same."""
    if isinstance(x, (tuple, list)):
        for y in x:
            yield from _array_leaves(y)
    elif x is not None:
        yield x


class _InflightStep:
    """One dispatched-but-unsettled chunk: the engine's explicit
    pipeline-stage state object. Carries the result handles (device
    arrays until the settle fetches them; host arrays on the
    unpipelined announce path), the slot->request SNAPSHOT the chunk
    was computed over (scheduling for the NEXT step mutates
    ``engine._slots`` freely — the settle walks this snapshot, never
    the live table), and the dispatch/retire timestamps that feed the
    device-busy interval derivation (obs/stepstats.py measurement
    model).

    ``kind`` vocabulary: ``dev`` / ``spec_dev`` hold un-fetched device
    arrays; ``host`` / ``spec_host`` hold already-gathered host arrays
    (the unpipelined announce path blocks at dispatch).

    ``t_dispatch`` is stamped at ENTRY to the dispatch call: the async
    runtime begins executing while the call is still wrapping outputs,
    so an after-return stamp undercuts the interval by however long
    the call took — on a contended 1-vCPU host the device can finish
    most of a chunk inside a slow dispatch call, collapsing its busy
    window to near zero (measured). The call-entry stamp over-counts
    by at most the pure-host prefix of one dispatch call, which is
    bounded and small; the after-return stamp under-counts by an
    unbounded contention-dependent amount. ``t_retire`` is stamped at
    the first moment the results were OBSERVED ready: a non-blocking
    ``is_ready`` poll at a step top
    (:meth:`ContinuousEngine.poll_retire`), or the fetch return when
    the data was needed while still computing. None until then."""

    __slots__ = ("kind", "a", "b", "snapshot", "size",
                 "t_dispatch", "t_retire")

    def __init__(self, kind, a, b, snapshot, size, t_dispatch):
        self.kind = kind
        self.a = a                  # tokens / packed spec results
        self.b = b                  # live flags (None for spec kinds)
        self.snapshot = snapshot    # slot -> _Request at dispatch
        self.size = size            # max tokens emitted per slot
        self.t_dispatch = float(t_dispatch)
        self.t_retire: Optional[float] = None

    def poll_ready(self) -> bool:
        """Non-blocking: True iff every result array reports ready.
        Host-kind results (no ``is_ready``) are ready by construction;
        local-only, so safe under announce (no collective)."""
        for x in _array_leaves((self.a, self.b)):
            ready = getattr(x, "is_ready", None)
            if ready is not None and not ready():
                return False
        return True


class ContinuousEngine:
    """Admit requests any time; every free KV slot is refilled at the
    next chunk boundary. ``submit`` queues, ``run_until_drained`` (or
    repeated ``step``) decodes; finished requests come back as
    ``(rid, token_list)``.

    ``announce=True`` (multi-host serving, process 0 only): every
    device op is announced over the serving wire BEFORE it runs, under
    the announce lock, so worker processes replay the identical op
    stream — see ``train/serving.py`` OP_CB_*."""

    def __init__(self, model: CausalLM, params, num_slots: int = 8,
                 chunk: int = 8, eos_token_id: Optional[int] = None,
                 pad_id: int = 0,
                 buckets: Sequence[int] = PAD_BUCKETS,
                 mesh=None, announce: bool = False,
                 prefix_cache_size: int = 0,
                 prefill_chunk: int = 0,
                 step_token_budget: int = 0,
                 pipeline_depth: int = 0,
                 adaptive_chunk: bool = False,
                 batch_admit: bool = True,
                 schedule: str = "fifo",
                 tenant_weights: Optional[Dict[str, float]] = None,
                 spec_tokens: int = 0,
                 draft_model: Optional[CausalLM] = None,
                 draft_params=None,
                 obs=None,
                 stepstats: Optional[StepStatsRing] = None,
                 peak_flops: float = 0.0):
        if num_slots < 1 or chunk < 1:
            raise ValueError("num_slots and chunk must be >= 1")
        if schedule not in ("fifo", "longest"):
            raise ValueError(
                f"schedule must be 'fifo' or 'longest', got {schedule!r}")
        # "longest" = LPT (longest-processing-time-first) admission: the
        # queue stays sorted by remaining budget, so the long requests
        # anchor the slot pool early and the short ones pack the gaps.
        # Classic makespan result; on the round-5 trail the FIFO tail —
        # one long request decoding alone while 7 slots idle — was the
        # engine's largest remaining loss vs whole-batch. Throughput
        # policy: short requests wait longer (keep "fifo" when
        # first-come latency matters more than chip utilization).
        self.schedule = schedule
        if pipeline_depth < 0:
            raise ValueError("pipeline_depth must be >= 0")
        # pipeline_depth=N ("decode-ahead"): keep up to N dispatched
        # chunks un-collected, so the device->host readback and the
        # host's scheduling work overlap the next chunks' compute (the
        # readback's share of the cycle on a local chip is not
        # measured). Token content per request is
        # unchanged — each slot's rows depend only on its own prompt —
        # but eos frees and admissions take effect up to N chunks later
        # (bounded extra compute, discarded by the host budget clamp).
        # Depth 1 hides one readback behind one chunk's compute; deeper
        # helps when a single chunk's compute is SHORTER than one
        # readback (small chunks, few live slots). Multi-host (announce)
        # composes at depth 1: the chunk is announced deferred=1
        # (dispatch only) and the gathers run at a separately announced
        # OP_CB_COLLECT. Depth >= 2 is single-host only — the worker
        # replay caps its deferred-chunk window at 2 outstanding
        # (serving.py OP_CB_CHUNK), so a deeper stream would desync and
        # kill replicas.
        if pipeline_depth > 1 and announce:
            raise ValueError(
                "pipeline_depth >= 2 is single-host only (the announce "
                "replay's deferred-chunk window is depth-1 sized)")
        self.pipeline_depth = pipeline_depth
        # adaptive_chunk ("budget-aligned chunking"): size each dispatch
        # to the MINIMUM remaining token budget over the active slots
        # (bucketed to powers of two >= _MIN_ADAPTIVE_CHUNK so the jit
        # cache stays small), so a slot whose request ends at its budget
        # frees at the earliest collectable boundary instead of decoding
        # dead rows for the rest of a fixed chunk. The round-5 hardware
        # trail motivated this: at chunk 64 x depth 2 a finished request
        # wastes up to (depth+1) x chunk slot-steps before its
        # replacement admits — more than the decode-ahead saves in RTT.
        # eos-terminated requests still finish early inside a chunk
        # (budget is an upper bound); the alignment is exact for
        # budget-terminated ones.
        self.adaptive_chunk = bool(adaptive_chunk)
        # batch_admit=False disables the batched-admission fast path —
        # the A/B lever for measuring what it buys on a given link
        self.batch_admit = bool(batch_admit)
        self._n_batch_admits = 0   # requests admitted via batched ops
        self._n_solo_admits = 0    # requests admitted one at a time
        self._n_dispatched_steps = 0  # decode steps dispatched (sum of
        #   chunk sizes) — the exact device-work count, immune to link
        #   noise
        from collections import deque

        # dispatched-but-unsettled chunks, oldest first (_InflightStep)
        self._inflight_q: Deque[_InflightStep] = deque()
        # admission dispatches whose device-busy interval is still
        # open: prefill + insert work is async and never collected, so
        # without these trackers every prefill's compute would be
        # measured as device IDLE. Each entry polls the post-admission
        # slot-pool state (the insert's output tree — ready only once
        # the whole prefill->insert chain ran). Bounded: a dropped
        # tracker only under-counts busy, and busy is a floor.
        self._admit_q: Deque[_InflightStep] = deque(maxlen=32)
        if prefill_chunk and prefill_chunk < 32:
            raise ValueError(
                f"prefill_chunk must be 0 (off) or >= 32, got "
                f"{prefill_chunk} (tiny pieces spend more dispatches "
                "than they save)")
        paged = bool(getattr(model.cfg, "paged_kv", False))
        if prefill_chunk and announce and not paged:
            # the DENSE piecewise extends are not on the OP_CB_* wire
            # (batch-1 staging trees live only on process 0); the paged
            # route IS — chunk progress rides OP_CB_ADMIT
            raise ValueError(
                "dense chunked prefill is single-host only (announce "
                "mode); the paged engine replays chunk progress over "
                "the wire")
        self.prefill_chunk = prefill_chunk
        if step_token_budget < 0:
            raise ValueError(
                f"step_token_budget must be >= 0, got {step_token_budget}")
        # step_token_budget ("Sarathi-style" iteration budget): cap the
        # work one engine step dispatches at ~this many tokens, split
        # between ONE prefill piece (chunked admission, up to
        # prefill_chunk tokens) and the decode chunk (live_slots x
        # steps tokens) — so a 4k-token arrival costs every streaming
        # slot a bounded stall per step instead of a whole-prompt
        # prefill. Decode steps are bucketed to powers of two (jit
        # cache: log2(chunk) programs), floored at 1 so the engine
        # always makes progress. 0 = off (fixed decode chunk).
        self.step_token_budget = int(step_token_budget)
        if prefix_cache_size and announce and not paged:
            # the DENSE prefix entries and the extend op are not on the
            # OP_CB_* wire (worker replicas would need the LRU too) —
            # single-host only. The PAGED radix cache IS on the wire:
            # cache-hit admissions replay as OP_CB_ADMIT pieces with a
            # nonzero fill (+ the COW page copy), so worker replicas
            # install identical block tables.
            raise ValueError(
                "dense prefix caching is single-host only (announce "
                "mode); the paged radix cache replays over the wire")
        self.prefix_cache = (PrefixCache(prefix_cache_size)
                             if prefix_cache_size and not paged else None)
        self.model, self.params = model, params
        # tp serving: ``params`` should already be placed
        # (shard_params_for_serving); entering the mesh context around
        # the jits lets the model's logical constraints resolve, exactly
        # as serve_generate does.
        self.mesh = mesh
        self.announce = announce
        self.num_slots, self.chunk = num_slots, chunk
        self.eos_token_id, self.pad_id = eos_token_id, pad_id
        # Default ladder adapts to the model: every standard bucket that
        # fits, plus max_seq_len itself as the top bucket — so any
        # prompt the model can serve (prompt + >=1 new token fits) has a
        # bucket, and a tiny-context model still gets one. An explicit
        # ``buckets`` argument is honored as given.
        s_max = model.cfg.max_seq_len
        if buckets is PAD_BUCKETS:
            buckets = tuple(b for b in PAD_BUCKETS if b < s_max) + (s_max,)
        self.buckets = tuple(b for b in buckets if b <= s_max)
        if not self.buckets:
            raise ValueError(
                f"no prompt bucket fits max_seq_len {s_max}")
        # -- paged KV cache: the engine owns the page pool ------------------
        self.paged = bool(getattr(model.cfg, "paged_kv", False))
        self._free_pages: List[int] = []
        self._slot_pages: Dict[int, List[int]] = {}
        # page -> refcount: slots and in-flight admissions hold one ref
        # per page they reference, the radix trie holds one per page it
        # indexes. A page is in ``_free_pages`` iff its refcount is 0 —
        # page lifetime is refcount-owned, not slot-owned, so the SAME
        # physical page can back the shared prefix of many requests.
        self._page_refs: Dict[int, int] = {}
        self.radix: Optional[RadixPrefixCache] = None
        self._peak_pages_in_use = 0
        self._n_page_alloc_failures = 0
        if self.paged:
            ps = model.cfg.kv_page_size
            if s_max % ps:
                raise ValueError(
                    f"kv_page_size {ps} must divide max_seq_len {s_max}")
            if prefix_cache_size:
                # engine-level RADIX prefix cache over the page pool:
                # completed prompts stay resident as refcounted pages
                # indexed by a token trie; admissions share the longest
                # match copy-on-write and prefill only the suffix.
                # ``prefix_cache_size`` caps the trie's resident pages
                # (clamped to the pool; LRU-evicted under pool
                # pressure either way) — NOT dense-LRU entry count.
                self.radix = RadixPrefixCache(
                    ps, min(int(prefix_cache_size),
                            model.cfg.kv_num_pages))
            # prefill rows scatter whole pages, so every admissible
            # bucket must be page-aligned
            self.buckets = tuple(b for b in self.buckets if b % ps == 0)
            if not self.buckets:
                raise ValueError(
                    f"no prompt bucket is a multiple of kv_page_size {ps}")
            self._free_pages = list(range(model.cfg.kv_num_pages))
            itemsize = 1 if model.cfg.kv_cache_quant else jnp.dtype(
                model.cfg.dtype).itemsize
            per_page = 2 * ps * model.cfg.kv_heads * model.cfg.head_dim * (
                itemsize)                                   # K + V pages
            if model.cfg.kv_cache_quant:
                per_page += 2 * ps * model.cfg.kv_heads * 4  # f32 scales
            self._page_bytes_per_layer = per_page
        self._rid = itertools.count()
        self._queue: List[_Request] = []
        # -- multi-tenant fairness: DWRR over per-tenant subqueues ----------
        # The scheduler is consulted only once TWO distinct tenants have
        # actually submitted (``_fair_active``): a single-tenant engine —
        # including every pre-tenancy caller — admits in the exact
        # FIFO/LPT order it always did, at zero extra cost per step (the
        # FIFO-equivalent fast path).
        self._fair = DwrrScheduler(tenant_weights)
        self._first_tenant: Optional[str] = None
        self._fair_active = False
        self._slots: Dict[int, _Request] = {}
        # piecewise admission in flight (chunked prefill): at most one,
        # holding its reserved slot + the partially-built cache tree
        self._admitting: Optional[dict] = None
        self._n_finished = 0  # counter, not a list: a
        # long-lived server must not retain every prompt it ever served
        self._n_deadline_expired = 0
        # -- self-draft speculation: k draft proposals per slot-round,
        # ONE multi-query verify chunk, accepted tokens advance the
        # fill, rejected ones roll it back (see _spec_chunk) -----------
        if spec_tokens < 0:
            raise ValueError(
                f"spec_tokens must be >= 0, got {spec_tokens}")
        self.spec_tokens = int(spec_tokens)
        self._spec = self.spec_tokens > 0
        if (draft_model is not None
                and draft_model.cfg.vocab_size != model.cfg.vocab_size):
            raise ValueError(
                f"draft vocab {draft_model.cfg.vocab_size} != target "
                f"vocab {model.cfg.vocab_size}: the models must share "
                f"a tokenizer")
        self._self_draft = self._spec and draft_model is None
        self._n_spec_proposed = 0
        self._n_spec_accepted = 0
        self._n_spec_rounds = 0
        # windowed accept-rate (last 64 collected spec chunks): the
        # /loadz `spec_accept_rate` signal — a pool gone cold stops
        # advertising its warm past, like the radix hit-rate window
        self._spec_window: Deque = deque(maxlen=64)
        self._device = SlotDeviceState(
            model, params, num_slots, mesh,
            draft_model=draft_model if self._spec else None,
            draft_params=draft_params if self._spec else None,
            spec_tokens=self.spec_tokens)
        # shared metrics plane: slot occupancy + useful-token counters
        # (useful tokens per second, scrapable live). One
        # lock op per CHUNK, not per token — hot-path safe. ``obs``
        # threads an injected registry's handles through (BundleServer
        # passes its own); default is the process registry.
        self._obs = obs if obs is not None else platform_families()
        self._obs["serve_slots_total"].set(num_slots)
        # step telemetry (obs/stepstats.py): one record per step() —
        # phase-exclusive timing + batch composition — into a bounded
        # ring exposed as GET /stepz. The serving front passes ITS
        # ring so history survives engine rebuilds; direct callers
        # (tests) get a private default-size one. peak_flops
        # arms the windowed serve_mfu gauge (0 = disabled — the CPU
        # default; FLOPs/token is estimated from the model config).
        self.stepstats = (stepstats if stepstats is not None
                          else StepStatsRing())
        self.stepstats.bind(self._obs,
                            flops_per_token=flops_per_token(model.cfg),
                            peak_flops=peak_flops)
        self._step_rec = None  # the in-flight step's record (set only
        #   inside step(); _dispatch_chunk/_collect annotate through it)
        self._n_prefill_chunks = 0  # pieces processed (all admissions)
        self._n_prefill_tokens = 0  # prompt tokens actually COMPUTED
        #   by prefill forwards (pieces, buckets, extensions) — the
        #   prefix cache's whole point is keeping this ∝ unique-suffix
        #   tokens; smoke_check reads it from stats
        self._step_prefill_tokens = 0  # this step's piece tokens (the
        #   budget split's prefill half; reset at each step() top)
        self._obs["serve_prefill_inflight"].set(0)
        if self.paged:
            self._obs["serve_kv_pages_total"].set(model.cfg.kv_num_pages)
            self._update_page_gauges()

    # -- submission ------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int,
               on_tokens=None, temperature: float = 0.0,
               top_p: Optional[float] = None, seed: int = 0,
               deadline_s: Optional[float] = None,
               tenant: str = "default", span=None) -> int:
        if temperature and temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        if top_p is not None and not 0 < top_p <= 1:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new_tokens > self.model.cfg.max_seq_len:
            raise ValueError(
                f"prompt {prompt.size} + {max_new_tokens} new tokens "
                f"exceeds max_seq_len {self.model.cfg.max_seq_len}")
        chunked_route = bool(self.prefill_chunk
                             and prompt.size > self.prefill_chunk)
        if not chunked_route:
            # raises if no bucket fits; chunked-route prompts never
            # touch a bucket (pieces are prefill_chunk-wide, and the
            # dense remainder paths quantize to 32-multiples), so
            # their only bound is max_seq_len, checked above
            sb = bucket_length(prompt.size, self.buckets)
        if self.paged:
            if chunked_route:
                # chunked route: pieces write real tokens only — no
                # padded-bucket scatter, so the bound is the true
                # token extent, not the bucket's
                need = -(-(prompt.size + max_new_tokens)
                         // self.model.cfg.kv_page_size)
            else:
                need = self._pages_needed(sb, prompt.size,
                                          max_new_tokens)
            total = self.model.cfg.kv_num_pages
            if need > total:
                # with the whole pool free this request still couldn't
                # admit — queueing it would livelock run_until_drained
                raise ValueError(
                    f"request needs {need} KV pages but the pool has "
                    f"{total} (page_size "
                    f"{self.model.cfg.kv_page_size})")
        tenant = str(tenant) or "default"
        if self._first_tenant is None:
            self._first_tenant = tenant
        elif not self._fair_active and tenant != self._first_tenant:
            self._fair_active = True  # two distinct tenants seen: the
            #   DWRR picker (and its queue scan) engages from here on
        # request SHAPE onto the trace (the replay-extraction
        # contract; idempotent with the serve front's earlier stamp —
        # direct engine callers get it from here)
        annotate_request_shape(span, tenant=tenant,
                               prompt_tokens=int(prompt.size),
                               max_new_tokens=max_new_tokens,
                               deadline_s=deadline_s)
        req = _Request(next(self._rid), prompt, max_new_tokens,
                       on_tokens=on_tokens, temperature=float(temperature),
                       top_p=top_p, seed=int(seed), tenant=tenant,
                       enqueued_at=time.monotonic(),
                       deadline=(time.monotonic() + float(deadline_s)
                                 if deadline_s is not None else None),
                       span=span)
        if self.schedule == "longest":
            # insertion point keeps the queue budget-descending; ties
            # stay FIFO (stable insert after equal budgets)
            i = 0
            while (i < len(self._queue)
                   and self._queue[i].max_new_tokens >= max_new_tokens):
                i += 1
            self._queue.insert(i, req)
        else:
            self._queue.append(req)
        return req.rid

    def warm_prefix(self, prefix_ids) -> int:
        """Prefill ``prefix_ids`` once and cache the result; later
        requests whose prompt starts with it skip that prefill. Returns
        the prefix length. The prefix must leave room for at least one
        more token (a full-context prefix could never be extended).
        Paged engines route to the radix cache (the prefix lands
        straight in trie-owned pages); dense engines keep the batch-1
        LRU."""
        if self.radix is not None:
            return self._warm_prefix_paged(prefix_ids)
        if self.prefix_cache is None:
            raise ValueError("engine built without prefix_cache_size")
        prefix = np.asarray(prefix_ids, np.int32).reshape(-1)
        if prefix.size == 0:
            raise ValueError("empty prefix")
        if prefix.size >= self.model.cfg.max_seq_len:
            raise ValueError(
                f"prefix {prefix.size} leaves no room under max_seq_len "
                f"{self.model.cfg.max_seq_len}")
        sb = bucket_length(prefix.size, self.buckets)
        padded = right_pad(prefix, sb, self.pad_id)
        with self._device._mesh_ctx():
            cache1, logits1 = _prefill_padded(
                self.model, self.params, jnp.asarray(padded),
                jnp.asarray(prefix.size, jnp.int32))
        self._n_prefill_tokens += int(prefix.size)
        self.prefix_cache.put(prefix, cache1, logits1)
        return int(prefix.size)

    def _warm_prefix_paged(self, prefix_ids) -> int:
        """Paged ``warm_prefix``: prefill the prefix STRAIGHT into
        trie-owned pages (no slot involved) and index it, so later
        prompts starting with it admit at the match boundary. Restarts
        from the last fully-cached page when part of the prefix is
        already resident. Announce mode replays the pieces on every
        worker (OP_CB_ADMIT, never final — no slot is activated), so
        replica pools warm identically."""
        prefix = np.asarray(prefix_ids, np.int32).reshape(-1)
        if prefix.size == 0:
            raise ValueError("empty prefix")
        cfg = self.model.cfg
        if prefix.size >= cfg.max_seq_len:
            raise ValueError(
                f"prefix {prefix.size} leaves no room under max_seq_len "
                f"{cfg.max_seq_len}")
        ps = cfg.kv_page_size
        matched, shared, _cow = self.radix.match(
            prefix, limit=int(prefix.size), peek=True)
        if matched >= prefix.size:
            # every prefix token is already derivable from cached
            # pages (possibly ending inside a fuller page): future
            # prompts will match through them — warming adds nothing.
            # Touch the path (LRU) WITHOUT counting: a warm no-op is
            # not an admission, and repeated warms (rebuild replay,
            # periodic POST /v1/warm) must not inflate the hit rate
            # the router scores spill allowance on.
            self.radix.match(prefix, limit=int(prefix.size),
                             count=False)
            return int(prefix.size)
        fill0 = len(shared) * ps  # restart at the last FULL cached
        #   page; a partial tail match re-prefills into a fresh page
        #   that the insert below UPGRADES the tail node to
        need = -(-int(prefix.size) // ps) - len(shared)
        self._ref_pages(shared)  # pin through the pieces below
        taken = self._take_pages(need)
        if taken is None:
            self._unref_pages(shared)
            raise ValueError(
                f"KV page pool cannot hold the prefix ({need} pages "
                f"needed, {len(self._free_pages)} free after eviction)")
        row = np.full((cfg.max_pages_per_slot,), cfg.kv_num_pages,
                      np.int32)
        row[:len(shared)] = shared
        row[len(shared):len(shared) + need] = taken
        fill = fill0
        try:
            while fill < prefix.size:
                if self.prefill_chunk:
                    w = min(self.prefill_chunk, cfg.max_seq_len - fill)
                else:
                    rem = int(prefix.size) - fill
                    w = min(-(-rem // 32) * 32, cfg.max_seq_len - fill)
                piece = prefix[fill:fill + w]
                padded = right_pad(piece, w, self.pad_id)
                f0 = fill
                self._announced(
                    lambda wire, padded=padded, piece=piece, f0=f0:
                        wire.announce_cb_admit(
                            self.num_slots, padded, piece.size, 0,
                            self.eos_token_id, self.pad_id, pages=row,
                            chunk_fill=f0),
                    lambda padded=padded, piece=piece, f0=f0:
                        self._device.prefill_chunk(
                            padded, f0, piece.size, row))
                self._n_prefill_tokens += int(piece.size)
                fill += int(piece.size)
        except BaseException:
            self._unref_pages(list(shared) + taken)
            raise
        # trie refs keep the pages; the warm's own holds drop with them
        self._adopt_into_trie(prefix, list(shared) + taken,
                              holds=list(shared) + taken)
        return int(prefix.size)

    # -- disaggregated prefill/decode: KV-page handoff --------------------
    def export_prefix_pages(self, prefix_ids) -> Optional[dict]:
        """Prefill side of a disaggregated KV handoff: read the
        radix-cached pages covering ``prefix_ids`` back to the host.
        Only FULL cached pages travel (the importer's admissions
        re-prefill any tail remainder — same rule as a local radix
        hit). The pages are pinned (+1 ref) across the device gather
        so pool pressure cannot recycle them mid-read. Returns None
        when not even one full page of the prefix is cached (caller
        should warm first), else ``{token_ids, page_size, layers}``
        with one host-array dict per paged layer."""
        if self.radix is None:
            raise ValueError(
                "KV export needs the paged radix cache "
                "(prefix_cache_size > 0 on a paged model)")
        prefix = np.asarray(prefix_ids, np.int32).reshape(-1)
        if prefix.size == 0:
            raise ValueError("empty prefix")
        ps = self.model.cfg.kv_page_size
        _matched, shared, _cow = self.radix.match(
            prefix, limit=int(prefix.size), peek=True)
        if not shared:
            return None
        self._ref_pages(shared)
        try:
            layers = self._device.read_pages(shared)
        finally:
            self._unref_pages(shared)
        export = {
            "token_ids": [int(t) for t in prefix[:len(shared) * ps]],
            "page_size": int(ps),
            "layers": layers,
        }
        self._obs["serve_kv_xfer_export_total"].inc()
        self._obs["serve_kv_xfer_export_pages_total"].inc(len(shared))
        return export

    def import_prefix_pages(self, token_ids, layers) -> int:
        """Decode side of a disaggregated KV handoff: install the
        transferred page rows into this pool and adopt them into the
        radix trie, so ONE transfer warms every follower of the
        prefix — the importing request and all later same-prefix
        admissions hit locally. Refcount discipline mirrors
        ``_warm_prefix_paged`` (shared pages pinned through the
        install, fresh pages taken at refcount 1, everything handed
        to ``_adopt_into_trie`` with matching holds), so the chaos
        refcount audit holds on both sides of a transfer. Announce
        mode replays the page writes on every worker (OP_KV_XFER).
        Returns the number of prefix tokens now derivable from cached
        pages."""
        if self.radix is None:
            raise ValueError(
                "KV import needs the paged radix cache "
                "(prefix_cache_size > 0 on a paged model)")
        prefix = np.asarray(token_ids, np.int32).reshape(-1)
        cfg = self.model.cfg
        ps = cfg.kv_page_size
        # full pages only, and leave room for >= 1 new token (a
        # full-context prefix could never be extended)
        n = min(int(prefix.size), cfg.max_seq_len - 1) // ps
        if n <= 0:
            raise ValueError(
                f"KV transfer smaller than one page "
                f"(page_size {ps}, got {prefix.size} tokens)")
        prefix = prefix[:n * ps]
        _matched, shared, _cow = self.radix.match(
            prefix, limit=int(prefix.size), peek=True)
        if len(shared) >= n:
            # already resident: touch the path (LRU) without counting
            # — an idempotent re-import is not an admission
            self.radix.match(prefix, limit=int(prefix.size),
                             count=False)
            return int(prefix.size)
        need = n - len(shared)
        self._ref_pages(shared)  # pin through the install below
        taken = self._take_pages(need)
        if taken is None:
            self._unref_pages(shared)
            self._obs["serve_kv_xfer_failures_total"].inc()
            raise ValueError(
                f"KV page pool cannot hold the transfer ({need} pages "
                f"needed, {len(self._free_pages)} free after eviction)")
        # install only the rows BEYOND the locally-cached pages — the
        # resident prefix pages are reused, not overwritten
        blobs = [{key: np.asarray(leaf)[len(shared):n]
                  for key, leaf in rec.items()} for rec in layers]
        try:
            self._announced(
                lambda wire: wire.announce_kv_xfer(
                    self.num_slots, taken, blobs),
                lambda: self._device.write_pages(taken, blobs))
        except BaseException:
            self._unref_pages(list(shared) + taken)
            self._obs["serve_kv_xfer_failures_total"].inc()
            raise
        self._adopt_into_trie(prefix, list(shared) + taken,
                              holds=list(shared) + taken)
        self._obs["serve_kv_xfer_import_total"].inc()
        self._obs["serve_kv_xfer_import_pages_total"].inc(need)
        return int(prefix.size)

    def cancel(self, rid: int) -> bool:
        """Drop a request (abandoned client / front-side timeout): a
        queued request is removed; an active one frees its KV slot
        immediately so it stops burning decode steps. Returns True if
        the request was found. The request's span gets its terminal
        verdict HERE (outcome="cancelled") — cancellation is a state
        transition like completion/expiry, and the exactly-one-terminal
        invariant (chaos/invariants.py) counts it."""
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                del self._queue[i]
                self._trace_terminal(req, "cancelled")
                return True
        for slot, req in list(self._slots.items()):
            if req.rid == rid:
                req.done = True  # an in-flight decode-ahead snapshot
                #                  must skip it at collect time
                del self._slots[slot]
                self._free_slot(slot)
                self._trace_terminal(req, "cancelled")
                return True
        if (self._admitting is not None
                and self._admitting["req"].rid == rid):
            # mid-admission: drop the partial tree (paged: return the
            # held pages); the reserved slot was never inserted/
            # activated, so nothing live to free on device
            req = self._admitting["req"]
            self._drop_admitting()
            self._trace_terminal(req, "cancelled")
            return True
        return False

    @staticmethod
    def _trace_terminal(req: _Request, outcome: str) -> None:
        """Terminal span verdict for non-delivery state transitions
        (cancel, rebuild-forced error): one emitter, None-guarded."""
        if req.span is not None:
            req.span.event("terminal", rid=req.rid, outcome=outcome,
                           new_tokens=len(req.tokens))

    # -- internals -------------------------------------------------------
    def _announced(self, announce_thunk, device_thunk):
        """THE multi-host invariant, in one place: announce the op and
        run its device work under one hold of the announce lock (the
        workers execute ops in announce order, so process 0's device
        work must happen in that same order); single-host skips
        straight to the device work."""
        if not self.announce:
            return device_thunk()
        from pyspark_tf_gke_tpu.train import serving

        with serving.mh_lock():
            announce_thunk(serving)
            return device_thunk()

    # -- page-pool bookkeeping (paged mode; host-side, process 0 only —
    # workers replay the announced allocations verbatim) ------------------
    def _pages_needed(self, s_bucket: int, true_len: int,
                      max_new: int) -> int:
        """Pages covering BOTH the padded prefill scatter (``s_bucket``
        rows land in pages) and the request's maximum token extent."""
        ps = self.model.cfg.kv_page_size
        return -(-max(int(s_bucket), int(true_len) + int(max_new)) // ps)

    def _update_page_gauges(self) -> None:
        used = self.model.cfg.kv_num_pages - len(self._free_pages)
        self._peak_pages_in_use = max(self._peak_pages_in_use, used)
        self._obs["serve_kv_pages_in_use"].set(used)
        self._obs["serve_kv_cache_bytes_per_layer"].set(
            used * self._page_bytes_per_layer)

    def _ref_pages(self, pages) -> None:
        """+1 refcount on every page (a slot, admission, or the trie
        took a reference)."""
        for p in pages:
            self._page_refs[p] = self._page_refs.get(p, 0) + 1

    def _unref_pages(self, pages) -> None:
        """-1 refcount; pages reaching zero return to the free list.
        Raises on a double free — the refcount invariant every
        admit/cancel/deadline/drain/eviction path must uphold."""
        for p in pages:
            left = self._page_refs.get(p, 0) - 1
            if left > 0:
                self._page_refs[p] = left
            elif left == 0:
                del self._page_refs[p]
                self._free_pages.append(p)
            else:
                raise RuntimeError(
                    f"KV page {p} unreferenced while already free "
                    "(double free)")
        self._update_page_gauges()

    def _adopt_into_trie(self, tokens, pages,
                         holds: Optional[List[int]] = None) -> None:
        """Index ``tokens`` over ``pages`` and move the refcounts in
        ONE place (the finish path and the warm path must never
        drift): +1 per page the trie adopts, -1 per page it releases,
        then the caller's own ``holds`` drop and the resident-page cap
        is enforced."""
        adopted, released = self.radix.insert(tokens, pages)
        if adopted:
            self._ref_pages(adopted)
        if released:
            self._unref_pages(released)
        if holds:
            self._unref_pages(holds)
        self._enforce_cache_cap()
        self._obs["serve_prefix_cache_pages"].set(
            self.radix.resident_pages)

    def _evict_cache_pages(self, n: int) -> int:
        """LRU-evict up to ``n`` trie-resident pages with no slot
        reference back to the free list (pool pressure / resident
        cap). Returns how many actually freed."""
        released = self.radix.evict(
            n, busy=lambda p: self._page_refs.get(p, 0) > 1)
        if released:
            self._obs["serve_prefix_cache_evictions_total"].inc(
                len(released))
            self._unref_pages(released)
            self._obs["serve_prefix_cache_pages"].set(
                self.radix.resident_pages)
        return len(released)

    def _enforce_cache_cap(self) -> None:
        over = (self.radix.resident_pages - self.radix.capacity
                if self.radix is not None else 0)
        if over > 0:
            self._evict_cache_pages(over)

    def _take_pages(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` fresh pages (refcount 1 each); under pressure the
        radix cache's coldest resident pages are evicted first — cache
        residency never starves a live admission. None when even that
        cannot cover ``n``."""
        if n > len(self._free_pages) and self.radix is not None:
            self._evict_cache_pages(n - len(self._free_pages))
        if n > len(self._free_pages):
            return None
        taken = [self._free_pages.pop() for _ in range(n)]
        for p in taken:
            self._page_refs[p] = 1
        self._update_page_gauges()
        return taken

    def _alloc_pages(self, n: int):
        """``(row, taken)`` — the sentinel-padded ``[max_pages_per_slot]``
        block-table row and the allocated page list — or None when the
        pool (after cache eviction) cannot cover ``n`` (the request
        stays queued; the counter increments once per failed admission
        attempt)."""
        taken = self._take_pages(n)
        if taken is None:
            self._n_page_alloc_failures += 1
            self._obs["serve_kv_page_alloc_failures_total"].inc()
            return None
        cfg = self.model.cfg
        row = np.full((cfg.max_pages_per_slot,), cfg.kv_num_pages,
                      np.int32)
        row[:n] = taken
        return row, taken

    def _note_pages(self, slot: int, taken: List[int]) -> None:
        self._slot_pages[slot] = taken
        self._update_page_gauges()

    def _release_pages(self, slot: int) -> None:
        taken = self._slot_pages.pop(slot, None)
        if taken:
            self._unref_pages(taken)

    def _free_slot(self, slot: int) -> None:
        self._announced(
            lambda wire: wire.announce_cb_free(self.num_slots, slot),
            lambda: self._device.free(slot))
        if self.paged:
            self._release_pages(slot)

    def _draft_payload(self, req: _Request):
        """``(padded [1, w], true_len)`` for the admission's draft
        prefill, or None when speculation is off or the prompt cannot
        fit the draft's context (the slot then runs on a COLD draft
        row: proposals are garbage the verify rejects — slower, never
        wrong). Width discipline mirrors the dense extend paths:
        engine buckets first, then 32-multiples, bounded by the
        draft's max_seq_len."""
        if not self._spec:
            return None
        d_max = self._device.draft_model.cfg.max_seq_len
        n = int(req.prompt.size)
        if n >= d_max:
            return None
        cands = [x for x in self.buckets if n <= x <= d_max]
        w = min(cands) if cands else min(-(-n // 32) * 32, d_max)
        return right_pad(req.prompt, w, self.pad_id), n

    def _draft_admit(self, slot: int, req: _Request) -> None:
        """Draft prefill for admission routes that are single-host by
        construction (dense prefix-hit / dense chunked / batch admit
        fallback) — announce-mode routes ride the OP_CB_ADMIT draft
        payload instead."""
        dp = self._draft_payload(req)
        if dp is not None:
            self._device.draft_prefill_row(dp[0], dp[1], slot)

    def _try_admit(self, slot: int, req: _Request) -> bool:
        """Admit ``req`` into ``slot`` — immediately, via the prefix
        cache, or by STARTING a piecewise (chunked-prefill) admission.
        Returns False only when the request needs piecewise admission
        and one is already in flight, or (paged mode) the page pool
        cannot cover it yet (FIFO holds; the request stays queued)."""
        if self.paged:
            # ONE trie walk decides the route AND seeds the admission
            # (count=False: stats wait for the final post-COW outcome;
            # the LRU touch is wanted — a queued hit keeps its path
            # warm while it waits). Safe to hand the result through:
            # nothing between here and _start_paged_admission can
            # evict (eviction only runs inside page allocation).
            m = (self.radix.match(req.prompt, count=False)
                 if self.radix is not None else (0, [], None))
            if m[0] or (self.prefill_chunk
                        and req.prompt.size - m[0]
                        > self.prefill_chunk):
                # piecewise route: chunked prefill for long prompts
                # AND every radix-cache hit (the hit installs shared
                # pages and starts the pieces at the match boundary;
                # an unchunked engine runs the whole suffix as one
                # piece)
                if self._admitting is not None:
                    return False  # one piecewise admission at a time
                self._start_paged_admission(slot, req, m)
                return True
            sb = bucket_length(req.prompt.size, self.buckets)
            alloc = self._alloc_pages(self._pages_needed(
                sb, req.prompt.size, req.max_new_tokens))
            if alloc is None:
                return False  # pool exhausted — admit at a later chunk
                #               boundary, after frees return pages
            row, taken = alloc
            padded = right_pad(req.prompt, sb, self.pad_id)
            sampling = (float(req.temperature),
                        float(req.top_p if req.top_p is not None else 1.0),
                        int(req.seed))
            dp = self._draft_payload(req)

            def device_admit():
                self._device.admit_padded(
                    padded, req.prompt.size, slot, *sampling, pages=row)
                if dp is not None:
                    self._device.draft_prefill_row(dp[0], dp[1], slot)

            try:
                # chaos: crash BETWEEN page allocation and the prefill
                # landing — the refcount-discipline audit point (the
                # except below must hand every held page back)
                chaos_fire("engine.admit", rid=req.rid)
                self._announced(
                    lambda wire: wire.announce_cb_admit(
                        self.num_slots, padded, req.prompt.size, slot,
                        self.eos_token_id, self.pad_id, sampling=sampling,
                        pages=row, draft=dp),
                    device_admit)
            except BaseException:
                # a failed admit must not leak its pages: the caller may
                # catch and keep driving this engine, and leaked pages
                # would shrink the pool below submit()'s livelock bound
                self._unref_pages(taken)
                raise
            self._n_prefill_tokens += int(req.prompt.size)
            self._note_pages(slot, taken)
            self._slots[slot] = req
            self._trace_admit(req, slot, "paged")
            if self.radix is not None:
                # this path only runs when the peek matched nothing
                # (hits route piecewise): a MISS must land in the
                # recent window too, or /loadz's hit rate would stay
                # pinned at its last warm reading while cold prompts
                # re-prefill from token 0
                self.radix.note(0)
            return True
        if (self._admitting is not None and self.prefill_chunk
                and req.prompt.size > self.prefill_chunk):
            # piecewise admission busy and this prompt MIGHT need one:
            # peek (no stats/LRU churn on every retried step) to see if
            # a prefix hit shrinks it under the threshold
            hit = (self.prefix_cache.lookup(req.prompt, peek=True)
                   if self.prefix_cache is not None else None)
            if (req.prompt.size - (hit[0] if hit is not None else 0)
                    > self.prefill_chunk):
                return False
        hit = (self.prefix_cache.lookup(req.prompt)
               if self.prefix_cache is not None else None)
        rem_size = req.prompt.size - (hit[0] if hit is not None else 0)
        if self.prefill_chunk and rem_size > self.prefill_chunk:
            if self._admitting is not None:
                return False
            # chunked prefill: long prompts admit one bounded piece per
            # step, decode chunks interleave between pieces — a 1024-
            # token arrival must not stall every streaming slot for a
            # full prefill dispatch
            if hit is not None:
                # a hit that still needs pieces for its remainder is a
                # hit all the same — the exported counters must agree
                # with the LRU's own stats
                self._obs["serve_prefix_cache_hits_total"].inc()
                self._obs["serve_prefix_cache_hit_tokens_total"].inc(
                    hit[0])
            self._admitting = {
                "slot": slot, "req": req,
                "fill": hit[0] if hit is not None else 0,
                "cache1": hit[1] if hit is not None else None,
            }
            self._trace_admit(req, slot, "chunked",
                              prefix_hit_tokens=(hit[0] if hit is not None
                                                 else 0))
            self._advance_admission()
            return True
        if hit is not None:
            self._obs["serve_prefix_cache_hits_total"].inc()
            self._obs["serve_prefix_cache_hit_tokens_total"].inc(hit[0])
            self._trace_admit(req, slot, "prefix",
                              prefix_hit_tokens=hit[0])
            self._admit_from_prefix(slot, req, *hit)
            self._draft_admit(slot, req)  # single-host path (guarded)
            self._slots[slot] = req
            return True
        sb = bucket_length(req.prompt.size, self.buckets)
        padded = right_pad(req.prompt, sb, self.pad_id)
        sampling = (float(req.temperature),
                    float(req.top_p if req.top_p is not None else 1.0),
                    int(req.seed))
        dp = self._draft_payload(req)

        def device_admit():
            self._device.admit_padded(
                padded, req.prompt.size, slot, *sampling)
            if dp is not None:
                self._device.draft_prefill_row(dp[0], dp[1], slot)

        self._announced(
            lambda wire: wire.announce_cb_admit(
                self.num_slots, padded, req.prompt.size, slot,
                self.eos_token_id, self.pad_id, sampling=sampling,
                draft=dp),
            device_admit)
        self._n_prefill_tokens += int(req.prompt.size)
        self._slots[slot] = req
        self._trace_admit(req, slot, "dense")
        return True

    def _trace_admit(self, req: _Request, slot: int, route: str,
                     **fields) -> None:
        """Span events at the moment a request wins a KV slot: the
        measured queue wait (submit → admission — the span-level answer
        to 'was it queued behind a prefill chunk?') and the admission
        route with its prefix-cache verdict. One None check for
        untraced requests."""
        sp = req.span
        if sp is None:
            return
        sp.event("queue_wait", rid=req.rid,
                 ms=round((time.monotonic() - req.enqueued_at) * 1000.0,
                          3))
        sp.event("admission", rid=req.rid, slot=slot, route=route,
                 **fields)

    def _admit_from_prefix(self, slot: int, req: _Request, fill: int,
                           cache1, logits1) -> None:
        """Admission on a prefix-cache hit: only the prompt REMAINDER
        pays a forward (one multi-token slot-decode extension of the
        cached batch-1 tree), then the extended tree drops into the
        slot. Single-host only (guarded in __init__)."""
        rem = req.prompt[fill:]
        if rem.size == 0 and logits1 is None:
            raise AssertionError(
                "prefix lookup returned an empty remainder without "
                "stored logits — lookup contract violated")
        if rem.size:
            # the remainder bucket must fit BOTH the remainder and the
            # room left above ``fill`` — a write past max_seq_len would
            # be clamped by dynamic_update_slice and land at the wrong
            # positions (submit() guarantees rem fits the room). Shape
            # discipline: prefer the engine buckets, then 32-multiples
            # (bounds distinct _extend_prefix programs), exact room
            # only as the last resort near the context limit.
            s_max = self.model.cfg.max_seq_len
            room = s_max - fill
            candidates = [b for b in self.buckets
                          if rem.size <= b <= room]
            if candidates:
                sb = min(candidates)
            else:
                quant = -(-int(rem.size) // 32) * 32
                sb = quant if quant <= room else room
            padded = np.full((1, sb), self.pad_id, np.int32)
            padded[0, :rem.size] = rem
            with self._device._mesh_ctx():
                cache1, logits1 = _extend_prefix(
                    self.model, self.params, cache1, jnp.asarray(padded),
                    jnp.asarray(fill, jnp.int32),
                    jnp.asarray(rem.size, jnp.int32))
            self._n_prefill_tokens += int(rem.size)
        if self._device.state is None:
            self._device.state = self._device._init_state(cache1)
        with self._device._mesh_ctx():
            self._device.state = _insert_slot(
                self._device.state, cache1, logits1,
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(req.prompt.size, jnp.int32),
                jnp.asarray(req.temperature, jnp.float32),
                jnp.asarray(req.top_p if req.top_p is not None else 1.0,
                            jnp.float32),
                _seed_key_data(req.seed))

    def _advance_admission(self) -> None:
        """One piece of the in-flight chunked prefill: width is ALWAYS
        ``prefill_chunk`` (one compiled prefill + one compiled extend,
        regardless of prompt length); the final piece inserts the
        finished tree into the reserved slot. Tokens processed land in
        ``_step_prefill_tokens`` (via ``_note_prefill_piece``) — the
        step-budget accounting, which must also see pieces run from
        ``_try_admit`` inside ``_admit_waiting``, not only the
        step-top call."""
        if self._admitting.get("paged"):
            return self._advance_admission_paged()
        a = self._admitting
        req, fill = a["req"], a["fill"]
        # clamp the piece width to the room left under max_seq_len: a
        # full-width pad at the context limit would make
        # dynamic_update_slice CLAMP the write start below ``fill`` and
        # overwrite real prompt rows (the same hazard
        # _admit_from_prefix clamps against). Near-limit prompts pay a
        # couple of extra compiled widths; everything else stays on the
        # one full-width program.
        w = min(self.prefill_chunk,
                self.model.cfg.max_seq_len - fill)
        piece = req.prompt[fill:fill + w]
        padded = right_pad(piece, w, self.pad_id)
        with self._device._mesh_ctx():
            if a["cache1"] is None:
                cache1, logits1 = _prefill_padded(
                    self.model, self.params, jnp.asarray(padded),
                    jnp.asarray(piece.size, jnp.int32))
            else:
                cache1, logits1 = _extend_prefix(
                    self.model, self.params, a["cache1"],
                    jnp.asarray(padded), jnp.asarray(fill, jnp.int32),
                    jnp.asarray(piece.size, jnp.int32))
        a["cache1"], a["fill"] = cache1, fill + piece.size
        self._note_prefill_piece(piece.size, req)
        if a["fill"] == req.prompt.size:
            self._device.insert(
                cache1, logits1, a["slot"], req.prompt.size,
                temperature=float(req.temperature),
                top_p=float(req.top_p if req.top_p is not None else 1.0),
                seed=int(req.seed))
            self._draft_admit(a["slot"], req)  # dense chunked:
            #   single-host by construction (guarded in __init__)
            self._slots[a["slot"]] = req
            self._admitting = None

    def _note_prefill_piece(self, n: int,
                            req: Optional[_Request] = None) -> None:
        self._n_prefill_chunks += 1
        self._step_prefill_tokens += int(n)
        self._n_prefill_tokens += int(n)
        self._obs["serve_prefill_chunk_tokens"].observe(n)
        if req is not None and req.span is not None:
            req.span.event("prefill_chunk", rid=req.rid, tokens=int(n))

    def _start_paged_admission(self, slot: int, req: _Request,
                               match=None) -> None:
        """Begin a piecewise paged admission, seeded from the radix
        prefix cache when it matches: matched FULL pages are shared
        read-only (refcount +1, installed verbatim at the head of the
        admission's block-table row), a match ending inside a
        partially-filled tail page clones that page copy-on-write into
        a fresh one, and the pieces start at the match boundary — the
        prefill forward and pool writes cover the UNIQUE SUFFIX only,
        while the piece's attention reads the shared prefix pages
        through the same row."""
        cfg = self.model.cfg
        a = {"slot": slot, "req": req, "fill": 0, "paged": True,
             "row": np.full((cfg.max_pages_per_slot,), cfg.kv_num_pages,
                            np.int32),
             "pages": [], "shared": [], "cow": None}
        if self.radix is not None:
            # count=False: the effective match can still SHRINK below
            # (COW degrade under pool pressure) — the hit/miss note
            # lands after it is final, so the router's hit-rate signal
            # never reads warmer than what admissions actually skipped
            matched, shared, cow = (
                match if match is not None
                else self.radix.match(req.prompt, count=False))
            if cow is not None:
                # pin the source while the clone allocates (allocation
                # may LRU-evict resident pages — never the pinned src)
                self._ref_pages([cow[0]])
                dst = self._take_pages(1)
                if dst is None:
                    # pool can't cover the clone right now: degrade to
                    # the page boundary — full pages still share, only
                    # the tail rows recompute
                    self._unref_pages([cow[0]])
                    matched -= cow[1]
                    cow = None
                else:
                    a["cow"] = (cow[0], dst[0])
                    a["pages"].append(dst[0])
            self.radix.note(matched)
            if matched:
                self._ref_pages(shared)
                a["shared"] = shared
                a["row"][:len(shared)] = shared
                if a["cow"] is not None:
                    a["row"][len(shared)] = a["cow"][1]
                a["fill"] = matched
                self._obs["serve_prefix_cache_hits_total"].inc()
                self._obs["serve_prefix_cache_hit_tokens_total"].inc(
                    matched)
        self._trace_admit(req, slot, "paged_chunked",
                          prefix_hit_tokens=int(a["fill"]),
                          cow=a["cow"] is not None)
        self._admitting = a
        self._advance_admission()

    def _advance_admission_paged(self) -> None:
        """One piece of a PAGED chunked-prefill admission: extend the
        page allocation to cover the piece (page-by-page, as chunks
        land), run the batch-1 multi-token slot-decode forward that
        writes the piece's K/V straight into the pool, and — on the
        final piece — claim the decode extent's pages and activate the
        slot. Announce mode replays the identical piece (fill + row +
        the radix COW clone on the OP_CB_ADMIT wire) on every worker;
        a radix-hit admission's FIRST piece carries the nonzero match
        boundary as its fill, so worker block tables stay
        bit-identical. Pool dry -> the admission stalls (no piece; the
        alloc-failure counter increments once per stalled STEP, so its
        rate reads as stall duration) and retries at the next chunk
        boundary after frees."""
        a = self._admitting
        req, fill = a["req"], a["fill"]
        cfg = self.model.cfg
        ps = cfg.kv_page_size
        # same near-context-limit clamp as the dense path: a full-width
        # pad past max_seq_len would write real rows at clamped
        # positions
        if self.prefill_chunk:
            w = min(self.prefill_chunk, cfg.max_seq_len - fill)
        else:
            # radix-hit admission on an unchunked engine: the whole
            # suffix is ONE piece, width quantized to 32-multiples
            # (same compiled-program discipline as the dense extend)
            rem = req.prompt.size - fill
            w = min(-(-int(rem) // 32) * 32, cfg.max_seq_len - fill)
        piece = req.prompt[fill:fill + w]
        final = fill + piece.size == req.prompt.size
        # pages covering the piece's REAL tokens; the final piece also
        # claims the full decode extent — the engine never allocates
        # mid-decode (PR 2's zero-recompile invariant). Shared prefix
        # pages (+ the COW clone) already cover [0, match).
        covered = len(a["shared"]) + len(a["pages"])
        need_tokens = (req.prompt.size + req.max_new_tokens if final
                       else fill + piece.size)
        need = -(-need_tokens // ps) - covered
        if need > 0:
            taken = self._take_pages(need)
            if taken is None:
                self._n_page_alloc_failures += 1
                self._obs["serve_kv_page_alloc_failures_total"].inc()
                return  # stall; frees at later chunk boundaries
                #         return pages and the admission resumes
            a["row"][covered:covered + need] = taken
            a["pages"].extend(taken)
        padded = right_pad(piece, w, self.pad_id)
        sampling = (float(req.temperature),
                    float(req.top_p if req.top_p is not None else 1.0),
                    int(req.seed))
        cow = a["cow"]
        dp = self._draft_payload(req) if final else None

        def device():
            if cow is not None:
                self._device.copy_page(*cow)
            logits1 = self._device.prefill_chunk(
                padded, fill, piece.size, a["row"])
            if final:
                self._device.activate_slot(
                    a["slot"], req.prompt.size, logits1, a["row"],
                    *sampling)
                if dp is not None:
                    # the draft's context spans the WHOLE prompt (the
                    # radix match boundary included — shared pages
                    # never cross into the draft's dense rows), so the
                    # final piece carries the full prompt as the draft
                    # payload
                    self._device.draft_prefill_row(dp[0], dp[1],
                                                   a["slot"])

        try:
            self._announced(
                lambda wire: wire.announce_cb_admit(
                    self.num_slots, padded, piece.size, a["slot"],
                    self.eos_token_id, self.pad_id,
                    sampling=sampling if final else None,
                    pages=a["row"], chunk_fill=fill, final=final,
                    cow=cow, draft=dp),
                device)
        except BaseException:
            # a failed piece must not leak the admission's pages (the
            # caller may keep driving this engine)
            self._drop_admitting()
            raise
        if cow is not None:
            # the clone ran: drop the source pin (the trie's own ref
            # keeps the page alive for future matches)
            a["cow"] = None
            self._unref_pages([cow[0]])
        a["fill"] = fill + piece.size
        self._note_prefill_piece(piece.size, req)
        if final:
            self._slots[a["slot"]] = req
            self._note_pages(a["slot"], a["shared"] + a["pages"])
            self._admitting = None

    def _drop_admitting(self) -> None:
        """Abandon the in-flight piecewise admission (cancel, deadline,
        failed piece): paged admissions drop every page reference they
        hold — owned pages return to the free list, shared prefix
        pages fall back to their trie/other-slot refs, and a pending
        COW source loses its pin. The slot's table row was never set,
        so whatever the pieces wrote is unreachable and safely
        overwritten by the pages' next owner."""
        a, self._admitting = self._admitting, None
        if a is None or not a.get("paged"):
            return
        if a.get("cow") is not None:
            self._unref_pages([a["cow"][0]])
        drop = list(a.get("shared", ())) + list(a["pages"])
        if drop:
            self._unref_pages(drop)

    def _radix_insert(self, slot: int, req: _Request) -> None:
        """Index a FINISHED request's pages in the radix cache: they
        hold valid KV for prompt + emitted tokens (minus a trailing
        eos, which is emitted but never fed back — its KV row was
        never written), so a future prompt sharing that prefix skips
        its prefill. Near the context limit the insert is skipped:
        rows that are still live on device after the host-side finish
        (budget-terminated slots decode until the free lands, up to
        ``(pipeline_depth + 1) * chunk`` steps of overshoot) can reach
        position ``max_seq_len``, where the paged write's table-index
        clamp would land a garbage row at the LAST page's first
        offset — cheap to exclude, impossible to repair."""
        pages = self._slot_pages.get(slot)
        if not pages:
            return
        s_max = self.model.cfg.max_seq_len
        if (req.prompt.size + req.max_new_tokens
                + (self.pipeline_depth + 1) * self._chunk_token_bound()
                >= s_max):
            return
        toks = [int(t) for t in req.prompt] + list(req.tokens)
        if (self.eos_token_id is not None and toks
                and toks[-1] == self.eos_token_id):
            toks.pop()
        if not toks:
            return
        n_pages = -(-len(toks) // self.model.cfg.kv_page_size)
        self._adopt_into_trie(toks, pages[:n_pages])

    def _admit_batch(self, free: List[int]) -> None:
        """Batched-admission fast path (single-host): take the FIFO
        prefix of the queue that admits immediately (no prefix-cache
        hit, no chunked-prefill route) into ONE shared prompt bucket
        and prefill it all in one device op. The batch dimension is
        padded to a power of two (shape discipline: {2,4,8,...} x
        buckets compiled programs); pad rows replicate row 0 and are
        never inserted. FIFO order is preserved — the batch stops at
        the first request needing a different bucket or a special
        admission route."""
        group: List[_Request] = []
        sb0 = None
        pages_left = len(self._free_pages)
        needs: List[int] = []
        for req in self._queue:
            if len(group) >= len(free):
                break
            if (self.prefix_cache is not None
                    and self.prefix_cache.lookup(req.prompt, peek=True)):
                break  # the hit path is cheaper than a fresh prefill
            if (self.radix is not None
                    and self.radix.match(req.prompt, peek=True)[0]):
                break  # radix hit: the shared-page route skips the
                #        prefix prefill entirely — cheaper than batching
            if self.prefill_chunk and req.prompt.size > self.prefill_chunk:
                break  # piecewise route
            sb = bucket_length(req.prompt.size, self.buckets)
            if sb0 is None:
                sb0 = sb
            elif sb != sb0:
                break
            if self.paged:
                need = self._pages_needed(sb, req.prompt.size,
                                          req.max_new_tokens)
                if need > pages_left:
                    break  # pool covers the prefix only; rest stays
                    #        queued until frees return pages
                pages_left -= need
                needs.append(need)
            group.append(req)
        if len(group) < 2:
            return
        k = len(group)
        k_pad = 1 << (k - 1).bit_length()
        padded = np.full((k_pad, sb0), self.pad_id, np.int32)
        lens = np.ones((k_pad,), np.int32)
        for i, req in enumerate(group):
            padded[i, :req.prompt.size] = req.prompt
            lens[i] = req.prompt.size
        for i in range(k, k_pad):
            padded[i] = padded[0]
            lens[i] = lens[0]
        samplings = [(float(r.temperature),
                      float(r.top_p if r.top_p is not None else 1.0),
                      int(r.seed)) for r in group]
        pages_b = None
        takens: List[List[int]] = []
        if self.paged:
            cfgm = self.model.cfg
            pages_b = np.full((k_pad, cfgm.max_pages_per_slot),
                              cfgm.kv_num_pages, np.int32)
            for i, need in enumerate(needs):
                row, taken = self._alloc_pages(need)  # covered: the
                #   grouping loop already bounded the sum by the pool
                pages_b[i] = row
                takens.append(taken)
        try:
            self._device.admit_padded_batch(padded, lens, free[:k],
                                            samplings, pages=pages_b)
            if self._spec:
                d_max = self._device.draft_model.cfg.max_seq_len
                if sb0 <= d_max:
                    # the group's shared bucket fits the draft: one
                    # batched draft prefill (pad rows drop like the
                    # target-side scatter)
                    self._device.draft_prefill_rows_batch(
                        padded, lens, free[:k])
                else:
                    # bucket too wide for the draft — fall back to the
                    # per-request width discipline (skipping prompts
                    # that cannot fit at all: cold rows, never wrong)
                    for slot, req in zip(free[:k], group):
                        self._draft_admit(slot, req)
        except BaseException:
            for taken in takens:  # failed admit must not leak pages
                self._unref_pages(taken)
            raise
        self._n_prefill_tokens += sum(int(r.prompt.size) for r in group)
        for i, (slot, req) in enumerate(zip(free[:k], group)):
            self._slots[slot] = req
            self._trace_admit(req, slot, "batch")
            if self.paged:
                self._note_pages(slot, takens[i])
            if self.radix is not None:
                # batched admissions are all misses by construction
                # (the grouping loop breaks on any radix peek hit) —
                # they must cool the recent window like any other miss
                self.radix.note(0)
        del self._queue[:k]
        for req in group:
            # per-tenant admitted-token tally (stats parity with the
            # solo path; batch admit only runs single-tenant)
            self._fair.admitted_tokens[req.tenant] = (
                self._fair.admitted_tokens.get(req.tenant, 0)
                + _request_cost(req))
        self._n_batch_admits += k

    def _expire_deadlines(self) -> List[_Request]:
        """Chunk-boundary deadline enforcement: queued requests past
        their deadline never admit (a dead client must not win a KV
        slot over a live one), in-slot ones are cancelled so the slot
        frees NOW instead of after a budget of decode nobody will read,
        and a mid-admission (chunked-prefill) request drops its partial
        tree. Returns the expired requests, marked ``expired``/``done``
        — ``step`` folds them into its finished list so drivers collect
        them like completions and can tell the two apart by the flag."""
        now = time.monotonic()
        expired: List[_Request] = []
        queued_expired = 0
        keep = []
        for req in self._queue:
            if req.deadline is not None and now > req.deadline:
                expired.append(req)
                queued_expired += 1
            else:
                keep.append(req)
        if expired:
            self._queue[:] = keep
        for slot, req in list(self._slots.items()):
            if req.deadline is not None and now > req.deadline:
                req.done = True  # decode-ahead snapshots skip it
                del self._slots[slot]
                self._free_slot(slot)
                expired.append(req)
        if (self._admitting is not None
                and self._admitting["req"].deadline is not None
                and now > self._admitting["req"].deadline):
            # partial cache tree dropped (paged: pages returned); the
            # reserved slot was never inserted/activated, so nothing
            # live to free on device
            expired.append(self._admitting["req"])
            self._drop_admitting()
        for req in expired:
            req.expired = True
            req.done = True
            if req.span is not None:
                # terminal verdict on the request's OWN span — emitted
                # HERE (the state transition) so direct engine callers
                # and the serve front read one consistent timeline
                req.span.event("terminal", rid=req.rid,
                               outcome="deadline",
                               new_tokens=len(req.tokens))
        if expired:
            self._n_deadline_expired += len(expired)
            self._obs["serve_request_deadline_exceeded_total"].inc(
                len(expired))
            if queued_expired:
                # expired before ANY device work — load-shedding taxonomy
                self._obs["serve_requests_rejected_total"].labels(
                    reason="deadline").inc(queued_expired)
        return expired

    @property
    def warm_capacity(self) -> int:
        """How many warmed prefixes a rebuilt engine should replay
        (the serving front retains that many token lists): the dense
        LRU's entry capacity, or a small fixed horizon for the radix
        cache (its residency is page-bounded, not entry-bounded)."""
        if self.prefix_cache is not None:
            return self.prefix_cache.capacity
        return 8 if self.radix is not None else 0

    def queue_depth(self, tenant: Optional[str] = None) -> int:
        """Requests waiting for a slot (admission queue length);
        ``tenant`` filters to one tenant's subqueue (the per-tenant
        queue-share shed check)."""
        if tenant is None:
            return len(self._queue)
        return sum(1 for r in self._queue if r.tenant == tenant)

    def queued_tokens(self, tenant: Optional[str] = None) -> int:
        """Token footprint of the admission queue: prompt + budget per
        queued request (the bound ``max_queued_tokens`` shedding uses —
        an upper bound on the KV the queue will claim). ``tenant``
        filters to one subqueue."""
        return sum(_request_cost(r) for r in self._queue
                   if tenant is None or r.tenant == tenant)

    def fail_outstanding(self, outcome: str = "error") -> List[_Request]:
        """Mark every accepted-but-undelivered request terminally
        failed: emit its ONE terminal span verdict (``outcome``:
        "error" for a rebuild after a failed/hung step, "shed" for a
        hot-swap past its drain bound) and set ``done`` so no later
        path double-delivers. Returns them — the caller (the serving
        front) settles quota refunds and fails the waiters. No device
        work happens here: this runs exactly when the engine is being
        abandoned and its device state may be mid-chunk garbage."""
        out = self.outstanding_requests()
        for req in out:
            self._trace_terminal(req, outcome)
            req.done = True
        return out

    def outstanding_requests(self) -> List[_Request]:
        """Every request the engine has accepted but not yet delivered
        (queued, in-slot, mid-admission; ``done`` ones excluded). The
        serving front settles these — quota refunds — when a failed
        device step forces an engine rebuild: their charges would
        otherwise leak with the dead engine."""
        out = [r for r in self._queue if not r.done]
        out += [r for r in self._slots.values() if not r.done]
        if (self._admitting is not None
                and not self._admitting["req"].done):
            out.append(self._admitting["req"])
        return out

    def queue_delay_ms(self) -> float:
        """Age of the OLDEST queued request in milliseconds (0 when the
        queue is empty) — the replica-side admission-delay term of the
        autoscale signal (/loadz ``queue_delay_ms``)."""
        if not self._queue:
            return 0.0
        oldest = min(r.enqueued_at for r in self._queue)
        return max(0.0, (time.monotonic() - oldest) * 1000.0)

    def tenant_stats(self) -> Dict[str, dict]:
        """Per-tenant snapshot: subqueue depth/footprint + cumulative
        admitted token cost (what the DWRR shares converge over)."""
        out: Dict[str, dict] = {}
        for r in self._queue:
            t = out.setdefault(r.tenant,
                               {"queued": 0, "queued_tokens": 0,
                                "admitted_tokens": 0})
            t["queued"] += 1
            t["queued_tokens"] += _request_cost(r)
        for tenant, adm in self._fair.admitted_tokens.items():
            t = out.setdefault(tenant,
                               {"queued": 0, "queued_tokens": 0,
                                "admitted_tokens": 0})
            t["admitted_tokens"] = int(adm)
        return out

    def _admit_waiting(self) -> None:
        reserved = (self._admitting["slot"]
                    if self._admitting is not None else None)
        free = [s for s in range(self.num_slots)
                if s not in self._slots and s != reserved]
        if (self.batch_admit and len(free) >= 2 and len(self._queue) >= 2
                and not self.announce and self._admitting is None
                and not self._fair_active):
            # the batched prefill is not on the OP_CB_* wire — announce
            # mode keeps the per-request ops (same single-host gate as
            # the prefix cache and chunked prefill). A multi-tenant
            # queue also skips it: the batch takes the QUEUE PREFIX,
            # which would let one tenant's burst jump the DWRR order.
            self._admit_batch(free)
            free = [s for s in range(self.num_slots)
                    if s not in self._slots and s != reserved]
        while free and self._queue:
            # single tenant: index 0 — the exact pre-fairness FIFO/LPT
            # order. Multi-tenant: the DWRR pick arbitrates between the
            # tenants' subqueues by weighted deficit.
            idx = self._fair.pick(self._queue) if self._fair_active else 0
            req = self._queue[idx]
            if not self._try_admit(free[0], req):
                break  # piecewise admission busy / pool dry; the pick
                #        (and its banked deficit) holds for next step
            free.pop(0)
            self._queue.pop(idx)
            if self._fair_active:
                self._fair.charge(req)
            else:
                self._fair.admitted_tokens[req.tenant] = (
                    self._fair.admitted_tokens.get(req.tenant, 0)
                    + _request_cost(req))
            self._n_solo_admits += 1

    def _chunk_token_bound(self) -> int:
        """Upper bound on per-slot fill advance from ONE dispatched
        chunk — the decode-overshoot term the near-context-limit radix
        guard uses. Plain chunks advance by at most ``chunk``; a spec
        chunk by 1 (entry) + rounds x (k+1) accepted+correction
        tokens (+1 exit feed)."""
        if not self._spec:
            return self.chunk
        k = self.spec_tokens
        return 2 + max(1, self.chunk // (k + 1)) * (k + 1)

    # -- the loop --------------------------------------------------------
    def _phase(self, name: str):
        """Phase-timing context on the in-flight step record (no-op
        outside step() — warm_prefix/cancel callers pay one attribute
        check)."""
        rec = self._step_rec
        return rec.phase(name) if rec is not None else (
            contextlib.nullcontext())

    def _effective_chunk(self) -> int:
        """Chunk size for the next dispatch. Fixed mode: ``self.chunk``.
        Adaptive mode: the largest power-of-two bucket (floored at
        ``_MIN_ADAPTIVE_CHUNK``, capped at ``self.chunk``) that does not
        overshoot the smallest remaining per-slot budget, counting steps
        already dispatched but not yet collected. Returns 0 when every
        active slot's budget is fully covered by in-flight chunks —
        dispatching more would be pure dead-row decode."""
        if not self.adaptive_chunk or not self._slots:
            return self.chunk
        pending: Dict[int, int] = {}
        for fs in self._inflight_q:
            for slot, sreq in fs.snapshot.items():
                if self._slots.get(slot) is sreq:  # not a freed slot's
                    #       stale snapshot (those rows are dead anyway)
                    pending[slot] = pending.get(slot, 0) + fs.size
        remaining = min(
            req.max_new_tokens - len(req.tokens) - pending.get(slot, 0)
            for slot, req in self._slots.items())
        if remaining <= 0:
            return 0
        c = min(remaining, self.chunk)
        b = _MIN_ADAPTIVE_CHUNK  # a sub-minimum remainder overshoots by
        while b * 2 <= c:        # < _MIN_ADAPTIVE_CHUNK steps; the
            b *= 2               # collect-side budget clamp discards it
        return min(b, self.chunk)  # an engine configured below the
        #   floor keeps its own (smaller) chunk size

    def _budget_cap(self, prefill_tokens: int) -> Optional[int]:
        """Decode steps the step-token budget leaves after this step's
        prefill piece: (budget - piece) / live_slots, bucketed DOWN to
        a power of two (jit cache: <= log2(chunk) sizes) and floored at
        1 (a piece bigger than the budget must not starve decode — the
        budget bounds the stall, it never stops token flow). None =
        budget off."""
        if not self.step_token_budget:
            return None
        live = max(len(self._slots), 1)
        steps = max((self.step_token_budget - int(prefill_tokens))
                    // live, 1)
        b = 1
        while b * 2 <= steps:
            b *= 2
        return b

    def _dispatch_chunk(self, size: int):
        """Dispatch one ``size``-step decode chunk over the current
        slots; returns the in-flight record (arrays + the slot->request
        snapshot the chunk was computed over). Announce mode,
        unpipelined: dispatch AND the as_host_array gathers run inside
        one hold of the announce lock (workers replay them as one op)
        and the record carries host arrays. Announce mode, pipelined:
        the chunk is announced deferred=1 (dispatch only, one lock
        hold) and the gathers run at the separately announced
        OP_CB_COLLECT in ``_collect`` — announced ops MAY legitimately
        sit between a deferred dispatch and its collect, on every
        process in the same order."""
        # chaos: the hung/failed DEVICE STEP fault point — a fail rule
        # raises here (the step() caller sees it exactly like a real
        # failed dispatch: the front fails in-flight requests loudly
        # and rebuilds the engine); a hang rule sleeps while the
        # driver holds its lock, which is the shape the serve-side
        # step watchdog must reap
        chaos_fire("engine.device_step")
        any_sampling = any(r.temperature > 0
                           for r in self._slots.values())
        if self._step_rec is not None:
            self._step_rec.decode_slots = max(
                self._step_rec.decode_slots, len(self._slots))
        if self._spec:
            return self._dispatch_spec(size, any_sampling)
        self._n_dispatched_steps += size
        if self.announce and not self.pipeline_depth:
            # the unpipelined announce path blocks on the readback
            # INSIDE the dispatch: carve the device sync out of the
            # dispatch phase so host overhead stays honest
            t0 = time.monotonic()
            with self._phase("device_wait"):
                toks, live = self._announced(
                    lambda wire: wire.announce_cb_chunk(
                        self.num_slots, size, self.eos_token_id,
                        self.pad_id, sampling=any_sampling),
                    lambda: self._device.chunk(
                        size, self.eos_token_id, self.pad_id,
                        sampling=any_sampling))
            fs = _InflightStep("host", toks, live, dict(self._slots),
                               size, t0)
            self._note_retired(fs, time.monotonic())
            return fs
        # t_dispatch stamps the dispatch-call ENTRY (see _InflightStep:
        # the async runtime starts executing before the call returns)
        t0 = time.monotonic()
        toks_dev, live_dev = self._announced(
            lambda wire: wire.announce_cb_chunk(
                self.num_slots, size, self.eos_token_id,
                self.pad_id, sampling=any_sampling, deferred=True),
            lambda: self._device.chunk_async(
                size, self.eos_token_id, self.pad_id,
                sampling=any_sampling))
        return _InflightStep("dev", toks_dev, live_dev,
                             dict(self._slots), size, t0)

    def _spec_rounds(self, size: int, cap: Optional[int]) -> int:
        """Draft/verify rounds for one spec dispatch. ``size`` bounds
        the EMITTED tokens per slot (the chunk semantics: fixed chunk
        or the adaptive remaining-budget size); ``cap`` (step-token
        budget) bounds the device WORK per slot — each round costs
        ~2k+2 forward tokens (k+1 draft feeds + the k+1-wide verify),
        so draft AND verify tokens both count against the budget.
        Power-of-two bucketed (jit cache discipline), floored at 1 so
        the engine always makes progress."""
        k = self.spec_tokens
        r = max(1, size // (k + 1))
        if cap is not None:
            r = min(r, max(1, cap // (2 * k + 2)))
        b = 1
        while b * 2 <= r:
            b *= 2
        return b

    def _dispatch_spec(self, rounds: int, any_sampling: bool):
        """Spec-mode dispatch: ``rounds`` draft/verify rounds over the
        current slots, on the same announce/deferred discipline as the
        plain chunk (OP_CB_CHUNK header slot 7 carries spec_tokens,
        slot 3 the round count — workers replay the identical spec
        program; accepted counts ride the collect gathers, which is
        what keeps worker fill counters/block tables bit-identical)."""
        k = self.spec_tokens
        # device-work accounting: (k+1) draft feeds + (k+1) verify
        # positions per round, + the entry/exit feeds — the spec analog
        # of "decode steps dispatched"
        self._n_dispatched_steps += rounds * (2 * k + 2) + 2
        self._n_spec_rounds += rounds
        if self._step_rec is not None:
            self._step_rec.spec_rounds += rounds
        adv = 1 + rounds * (k + 1)  # max tokens emitted per slot
        if self.announce and not self.pipeline_depth:
            t0 = time.monotonic()
            with self._phase("device_wait"):
                out = self._announced(
                    lambda wire: wire.announce_cb_chunk(
                        self.num_slots, rounds, self.eos_token_id,
                        self.pad_id, sampling=any_sampling,
                        spec_tokens=k),
                    lambda: self._device.spec_chunk(
                        rounds, self.eos_token_id, self.pad_id,
                        sampling=any_sampling))
            fs = _InflightStep("spec_host", out, None,
                               dict(self._slots), adv, t0)
            self._note_retired(fs, time.monotonic())
            return fs
        t0 = time.monotonic()  # dispatch-call entry (see _InflightStep)
        out = self._announced(
            lambda wire: wire.announce_cb_chunk(
                self.num_slots, rounds, self.eos_token_id,
                self.pad_id, sampling=any_sampling, deferred=True,
                spec_tokens=k),
            lambda: self._device.spec_chunk_async(
                rounds, self.eos_token_id, self.pad_id,
                sampling=any_sampling))
        return _InflightStep("spec_dev", out, None, dict(self._slots),
                             adv, t0)

    def _spec_slot_stream(self, spec_data, slot: int, req: _Request):
        """Compact one slot's spec-chunk output into its emitted token
        list: the entry token plus each round's window up to its valid
        length (window tails past it are pad, never emitted). Tallies
        proposed/accepted onto the request WHILE it still had budget —
        the same budget-capped stat discipline as the standalone
        drivers (overshoot rounds must not bias acceptance)."""
        entry, windows, wlens, accepted, proposed, _live = spec_data
        stream = [int(entry[slot])]
        budget = req.max_new_tokens
        prop = acc = 0
        for r in range(windows.shape[0]):
            if (int(proposed[r, slot])
                    and len(req.tokens) + len(stream) < budget):
                prop += int(proposed[r, slot])
                acc += int(accepted[r, slot])
            n = int(wlens[r, slot])
            if n:
                stream.extend(int(t) for t in windows[r, :n, slot])
        req.spec_proposed += prop
        req.spec_accepted += acc
        return np.asarray(stream, np.int64), prop, acc

    def _note_spec_stats(self, proposed: int, accepted: int) -> None:
        if not proposed:
            return
        self._n_spec_proposed += proposed
        self._n_spec_accepted += accepted
        self._spec_window.append((proposed, accepted))
        self._obs["serve_spec_proposed_total"].inc(proposed)
        self._obs["serve_spec_accepted_total"].inc(accepted)
        self._obs["serve_spec_accept_rate"].set(
            round(self.spec_accept_rate(), 4))

    def spec_accept_rate(self) -> float:
        """Windowed draft acceptance rate (last 64 collected spec
        chunks; 0.0 when speculation is off or nothing decoded yet) —
        the /loadz `spec_accept_rate` signal."""
        if not self._spec_window:
            return 0.0
        prop = sum(p for p, _ in self._spec_window)
        acc = sum(a for _, a in self._spec_window)
        return acc / prop if prop else 0.0

    def _note_retired(self, fs: _InflightStep, t_retire: float) -> None:
        """Stamp a chunk's retire timestamp (once) and feed its
        [dispatch, retire] device-busy interval to the stats ring —
        the raw input of the interval-union idle derivation."""
        if fs.t_retire is not None:
            return
        fs.t_retire = t_retire
        self.stepstats.note_device_interval(fs.t_dispatch, fs.t_retire)

    def poll_retire(self) -> None:
        """Non-blocking retire sweep: any in-flight chunk whose result
        arrays report ready gets its retire timestamp stamped NOW, so
        device-busy intervals end where the device actually went
        quiet, not where the host eventually fetched. Run at the step
        top (before this step's host work), at the step tail (after
        the settle), and by the serve driver after delivery — each a
        couple of ``is_ready`` calls. A chunk still computing is left
        alone (its settle's fetch return stamps it). Local-only
        ``is_ready`` — no collective, announce-safe."""
        now = time.monotonic()
        for fs in self._inflight_q:
            if fs.t_retire is None and fs.poll_ready():
                self._note_retired(fs, now)
        # admission trackers drain head-first (the device queue is
        # FIFO, so they complete in dispatch order)
        while self._admit_q and self._admit_q[0].poll_ready():
            self._note_retired(self._admit_q.popleft(), now)

    def _collect(self, inflight: _InflightStep) -> List[_Request]:
        """Settle one dispatched chunk: read back its results (a
        device-to-host copy that only blocks if the chunk is still
        computing) and do the host bookkeeping (token append,
        streaming callbacks, eos/budget completion, frees) for the
        slot snapshot it was computed over."""
        kind = inflight.kind
        spec_data = None
        if kind == "host":
            toks, live_host = inflight.a, inflight.b
        elif kind == "dev":
            # the serial loop's ONE blocking device sync: everything
            # outside this context is host overhead by definition
            with self._phase("device_wait"):
                toks, live_host = self._announced(
                    lambda wire: wire.announce_cb_collect(
                        self.num_slots),
                    lambda: self._device.fetch(inflight.a, inflight.b))
        elif kind == "spec_host":
            spec_data = _unpack_spec(inflight.a[0], self.spec_tokens)
            live_host = spec_data[-1]
        else:  # spec_dev: ONE packed gather at the collect
            with self._phase("device_wait"):
                packed = self._announced(
                    lambda wire: wire.announce_cb_collect(
                        self.num_slots),
                    lambda: self._device.fetch_tuple(inflight.a))
            spec_data = _unpack_spec(packed[0], self.spec_tokens)
            live_host = spec_data[-1]
        # a chunk that was still computing when its data was needed:
        # the fetch return IS the observed-ready moment
        self._note_retired(inflight, time.monotonic())
        if self._step_rec is not None:
            self._step_rec.device_busy_ms += (
                inflight.t_retire - inflight.t_dispatch) * 1000.0
        newly_done = []
        useful_tokens = 0
        chunk_prop = chunk_acc = 0
        now = time.monotonic()
        for slot, req in inflight.snapshot.items():
            if req.done:
                # freed/cancelled while this chunk was in flight (only
                # possible with decode-ahead): its rows decoded garbage
                # that nobody reads
                continue
            budget = req.max_new_tokens - len(req.tokens)
            if spec_data is not None:
                row, prop, acc = self._spec_slot_stream(
                    spec_data, slot, req)
                chunk_prop += prop
                chunk_acc += acc
                take = row[:budget]
            else:
                take = toks[slot, :budget]
            if self.eos_token_id is not None:
                hit = np.nonzero(take == self.eos_token_id)[0]
                if hit.size:
                    take = take[:hit[0] + 1]
            new_toks = [int(t) for t in take]
            useful_tokens += len(new_toks)
            if new_toks:
                # time-between-tokens, as a CLIENT sees it: the gap
                # between consecutive token deliveries to one request
                # (a chunk lands as one delivery). Prefill head-of-line
                # stalls show up here — the histogram chunked prefill
                # exists to flatten.
                if req.last_emit is not None:
                    self._obs["serve_tbt_ms"].observe(
                        (now - req.last_emit) * 1000.0)
                if req.span is not None:
                    if req.last_emit is None:
                        req.span.event(
                            "first_token", rid=req.rid,
                            ttft_ms=round(
                                (now - req.enqueued_at) * 1000.0, 3))
                    else:
                        req.span.event("tokens", rid=req.rid,
                                       n=len(new_toks))
                req.last_emit = now
            req.tokens.extend(new_toks)
            if req.on_tokens is not None and new_toks:
                try:
                    req.on_tokens(new_toks)
                except Exception:  # noqa: BLE001 — a slow/broken stream
                    # consumer must not take the whole engine down
                    logger.exception(
                        "on_tokens callback failed for request %d",
                        req.rid)
            eos_done = (self.eos_token_id is not None
                        and not live_host[slot])
            if eos_done or len(req.tokens) >= req.max_new_tokens:
                req.done = True
                newly_done.append(req)
                if req.span is not None and req.spec_proposed:
                    # per-request speculation quality on the trace
                    # (shows on /traces next to TTFT/terminal)
                    req.span.event(
                        "spec", rid=req.rid,
                        proposed=req.spec_proposed,
                        accepted=req.spec_accepted,
                        accept_rate=round(
                            req.spec_accepted / req.spec_proposed, 4))
                if req.span is not None:
                    # the span's LAST engine event: completion with the
                    # actual emitted-token count (replay extraction's
                    # output_tokens source)
                    req.span.event("terminal", rid=req.rid,
                                   outcome="ok",
                                   new_tokens=len(req.tokens))
                if self._slots.get(slot) is req:
                    del self._slots[slot]
                if self.radix is not None:
                    # completed prefixes stay resident: adopt the
                    # slot's pages into the trie BEFORE the slot's
                    # refs drop, so the next same-prefix prompt
                    # admits at the match boundary
                    self._radix_insert(slot, req)
                # slot's live flag must drop so its rows stop advancing
                self._free_slot(slot)
        self._n_finished += len(newly_done)
        if spec_data is not None:
            self._note_spec_stats(chunk_prop, chunk_acc)
        if self._step_rec is not None:
            self._step_rec.tokens_out += useful_tokens
        if useful_tokens:
            self._obs["serve_useful_tokens_total"].inc(useful_tokens)
        self._obs["serve_slots_active"].set(len(self._slots))
        self._obs["serve_queue_depth"].set(len(self._queue))
        return newly_done

    def step(self) -> List[_Request]:
        """Admit into free slots, run one decode chunk, collect tokens.
        Returns requests finished during this chunk.

        With ``pipeline_depth=N`` the collect runs up to N chunks behind
        the dispatch: the chunk launched this call is read back N calls
        later, so the device works ahead while the host waits on older
        tokens.

        Step telemetry (obs/stepstats.py): every step that does work
        closes exactly ONE record into ``self.stepstats`` — outcome
        "ok" on return, "error" when the step raises (a failed device
        dispatch, a chaos fail — the record closes in the except arm
        before the exception reaches the rebuild path), and the
        serving front relabels the record "reaped" when the watchdog
        intervened while the step hung. A step that never returns has
        an open record that never enters the ring — no half rows."""
        rec = self.stepstats.begin(queue_depth=len(self._queue))
        self._step_rec = rec
        try:
            finished = self._step_body(rec)
        except BaseException:
            self.stepstats.close(rec, outcome="error")
            raise
        finally:
            self._step_rec = None
        if rec.activity:
            self.stepstats.close(rec)
        else:
            self.stepstats.discard(rec)  # idle spin: no record
        return finished

    def _step_body(self, rec) -> List[_Request]:
        # retire sweep FIRST: chunks that finished while the host was
        # off delivering get their device-busy intervals closed at
        # this step's entry, before any of this step's host work —
        # idle is measured from here, conservatively
        self.poll_retire()
        with rec.phase("expire"):
            expired = self._expire_deadlines()
        rec.expired = len(expired)
        # per-step prefill-token accounting for the budget: pieces run
        # here AND inside _admit_waiting (a fresh admission's first
        # piece runs from _try_admit) — the counter sees both, so the
        # admission-start step's decode chunk is capped too
        self._step_prefill_tokens = 0
        pieces0 = self._n_prefill_chunks
        # admission-interval bracket: any schedule work that replaced
        # the device slot-pool state dispatched prefill+insert ops —
        # open a busy interval from the bracket entry, retired when
        # the new state's arrays report ready (poll_retire)
        state0 = self._device.state
        t_sched = time.monotonic()
        with rec.phase("schedule"):
            if self._admitting is not None:
                self._advance_admission()
            self._admit_waiting()
        if self._device.state is not state0:
            # track only the tiny `live` leaf: it comes ready with the
            # rest of the insert's outputs, and holding the full state
            # tree here would pin the superseded KV cache in device
            # memory until the tracker retires
            self._admit_q.append(_InflightStep(
                "admit", getattr(self._device.state, "live",
                                 self._device.state), None, {}, 0,
                t_sched))
        rec.prefill_pieces = self._n_prefill_chunks - pieces0
        rec.prefill_tokens = self._step_prefill_tokens
        self._obs["serve_prefill_inflight"].set(
            1 if self._admitting is not None else 0)
        cap = self._budget_cap(self._step_prefill_tokens)
        if not self.pipeline_depth:
            if not self._slots:
                return expired
            size = self._effective_chunk() or self.chunk
            if self._spec:
                # size bounds emitted tokens, cap bounds device work
                # (draft + verify both count) — _spec_rounds folds the
                # two into the round count
                size = self._spec_rounds(size, cap)
            elif cap:
                size = min(size, cap)
            with rec.phase("dispatch"):
                inflight = self._dispatch_chunk(size)
            with rec.phase("collect"):
                collected = self._collect(inflight)
            return expired + collected
        dispatched = False
        if self._slots:
            size = self._effective_chunk()
            if size and self._spec:
                size = self._spec_rounds(size, cap)
            elif size and cap:
                size = min(size, cap)
            if size:  # 0 = every slot's budget is already in flight
                with rec.phase("dispatch"):
                    self._inflight_q.append(self._dispatch_chunk(size))
                dispatched = True
        finished = list(expired)
        # Drain down to the target depth. With live slots, exactly one
        # collect runs per step (the break below) — the per-step
        # announce-op cadence stays dispatch+collect. With all slots
        # idle (everything finished/cancelled), the WHOLE backlog
        # flushes in this one call, since no later step is guaranteed.
        # A dispatch-skipped step (adaptive, budgets fully in flight)
        # must also collect one, or run_until_drained would livelock.
        while (len(self._inflight_q) > self.pipeline_depth
               or (self._inflight_q and not self._slots)
               or (self._inflight_q and not dispatched)):
            with rec.phase("collect"):
                finished += self._collect(self._inflight_q.popleft())
            if self._slots:  # collects freed slots mid-flush: stop at
                break        # target depth next call, after admissions
        # second retire sweep at the step tail: the chunk dispatched
        # THIS step often finishes during the settle above — observing
        # it here instead of at the next step's top keeps the deliver
        # phase and inter-step gap out of its busy interval
        self.poll_retire()
        return finished

    def quiesce(self) -> List[_Request]:
        """Settle EVERY in-flight chunk (device sync + full host
        bookkeeping — spans, frees, trie adoption) without
        dispatching new work; returns requests that finished in the
        flush. The pipeline-drain primitive: hot-swap and drain call
        this so no speculative chunk is abandoned mid-flight when the
        engine is about to be replaced — abandoned chunks would leak
        page refs and eat tokens the swap's successor then re-emits.
        Idempotent; a no-op on an empty pipeline. Announce mode
        announces the matching OP_CB_COLLECTs, so worker replicas
        drain their deferred window in lockstep."""
        finished: List[_Request] = []
        while self._inflight_q:
            finished += self._collect(self._inflight_q.popleft())
        return finished

    def run_until_drained(self):
        """Drive steps until queue + slots are empty; yields finished
        requests in completion order."""
        while (self._queue or self._slots or self._admitting
               or self._inflight_q):
            for req in self.step():
                yield req.rid, req.tokens

    @property
    def busy(self) -> bool:
        """Any work pending? The serving front's driver loop polls
        this every iteration — it must stay O(1) (``stats`` builds the
        full snapshot, including the windowed step-phase summary, and
        is NOT loop-cheap)."""
        return bool(self._queue or self._slots
                    or self._admitting is not None or self._inflight_q)

    @property
    def stats(self) -> dict:
        return {
            "queued": len(self._queue),
            "queued_tokens": self.queued_tokens(),
            "queue_delay_ms": round(self.queue_delay_ms(), 2),
            "tenants": self.tenant_stats(),
            "fair_active": self._fair_active,
            "active": len(self._slots),
            "finished": self._n_finished,
            "deadline_expired": self._n_deadline_expired,
            "num_slots": self.num_slots,
            "chunk": self.chunk,
            "batch_admits": self._n_batch_admits,
            "solo_admits": self._n_solo_admits,
            "dispatched_steps": self._n_dispatched_steps,
            "prefill_chunks": self._n_prefill_chunks,
            "prefill_tokens_computed": self._n_prefill_tokens,
            # windowed step-phase decomposition (obs/stepstats.py):
            # host-overhead fraction + per-phase p50/p99 — the
            # /loadz fraction reads this
            "step_phases": self.stepstats.summary(),
            **({"step_token_budget": self.step_token_budget}
               if self.step_token_budget else {}),
            **({"spec": {
                "spec_tokens": self.spec_tokens,
                "rounds": self._n_spec_rounds,
                "proposed": self._n_spec_proposed,
                "accepted": self._n_spec_accepted,
                "accept_rate": round(
                    self._n_spec_accepted
                    / max(self._n_spec_proposed, 1), 4),
                "recent_accept_rate": round(self.spec_accept_rate(), 4),
                "self_draft": self._self_draft,
            }} if self._spec else {}),
            "admitting": (self._admitting["req"].rid
                          if self._admitting is not None else None),
            "inflight": bool(self._inflight_q),
            **({"prefix_cache": self.prefix_cache.stats}
               if self.prefix_cache is not None else
               {"prefix_cache": self.radix.stats}
               if self.radix is not None else {}),
            **({"paged": {
                "page_size": self.model.cfg.kv_page_size,
                "pages_total": self.model.cfg.kv_num_pages,
                "pages_in_use": (self.model.cfg.kv_num_pages
                                 - len(self._free_pages)),
                "peak_pages_in_use": self._peak_pages_in_use,
                "page_alloc_failures": self._n_page_alloc_failures,
                "page_bytes_per_layer": self._page_bytes_per_layer,
            }} if self.paged else {}),
        }
