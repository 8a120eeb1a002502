"""Continuous-batching serving engine: slot refill mid-decode, ticket
generations for zero-drain hot-swap.

The scheduler keeps a fixed array of decode *slots*.  Each request is
prefilled on its own (padded to a length bucket, masked via
``valid_len`` so padding never leaks into attention) and its caches are
spliced into a free slot's cache lanes; all slots then advance through
ONE jitted decode step per token, each at its own sequence position
(per-slot cache indices).  The moment a slot's request finishes — EOS,
token budget, or deadline expiry — the next queued request is prefilled
and spliced in while the other slots keep decoding.  No request ever
waits for a batch-mate, and no request's output depends on its
batch-mates.

**Ticket generations.**  The engine's params/plan/jitted-fns bundle is
a *generation*.  ``swap(params, masks)`` installs a new generation
without draining traffic: requests already in slots keep decoding on
the generation that prefilled them (identical params, caches and
sampling stream — their outputs are bit-identical to a swap-free run),
while every subsequent admission prefills on the new ticket.  A drained
old generation is retired automatically; ``rollback`` discards a
just-installed generation that has not served traffic yet (the ticket
manager's smoke-verification path).

The engine is drivable two ways: ``run()`` serves the queue to
completion (the original batch surface), while ``step()`` advances one
scheduler tick — refill, deadline sweep, one decode per live
generation — so a front-end (``serve.frontend``) can interleave
admission, streaming, health checks and hot-swaps between ticks.

This is the LM-serving analogue of the paper's "train the pruned model"
story: hand the engine the ticket's masks and the decode projections are
routed through the block-sparse Pallas kernel (``kernels.bsmm``), so
decode compute/bandwidth scales with the live-tile count exactly as the
paper's crossbar count scales with surviving 128×128 blocks.

**Paged KV cache.**  For all-global-attention architectures the engine
replaces the per-slot dense caches with per-generation *block pools*
(``serve.paging.BlockPool`` over ``models.transformer`` paged caches):
each slot holds a block table into a shared pool of ``BLOCK_TOKENS``-
token KV blocks, decode attends through the paged Pallas kernel
(``kernels.paged_attention``), and KV bytes/step scale with *live
context* instead of allocated capacity — the KV-state analogue of the
live-tile story above.  Admission becomes dynamic: a request is
admitted when ``ceil((prompt + budget) / BLOCK)`` blocks are free, so a
prompt longer than the dense ``capacity`` serves fine on an idle
engine (the static ``oversize`` limit moves out to
``(kv_blocks - 1) * BLOCK``); when blocks are short the request waits
at the head of the FIFO queue and is admitted as finished requests
release their blocks.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import (Any, Callable, Deque, List, Optional, Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bsmm import default_interpret
from repro.kernels.paged_attention import BLOCK_TOKENS
from repro.models import transformer as tfm
from repro.serve.paging import BlockPool, blocks_needed
from repro.serve.ticket import PlanStats, build_decode_plan


class SubmitRejected(ValueError):
    """Structured admission rejection.

    ``reason`` is machine-readable:

      * ``"capacity"``     — bounded intake queue is full.  The ONLY
        retryable reason: capacity frees as slots drain, so front-ends
        park these in their wait queue.
      * ``"oversize"``     — prompt + budget exceeds KV-cache capacity.
      * ``"empty_prompt"`` — no prompt tokens.
      * ``"bad_budget"``   — ``max_new_tokens < 1``.
      * ``"unhealthy"``    — the engine's health gate is closed (e.g.
        heartbeat missed); admission stops, in-flight decode continues.

    Subclasses ``ValueError`` so pre-control-plane callers that caught
    the bare failure keep working.
    """

    RETRYABLE = ("capacity",)

    def __init__(self, reason: str, message: str, uid=None):
        self.reason = reason
        self.uid = uid
        super().__init__(message)

    @property
    def retryable(self) -> bool:
        return self.reason in self.RETRYABLE


@dataclass
class EngineHealth:
    healthy: bool = True
    reason: str = "ok"


@dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (S,) int32 — decoder prompt
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    # enc-dec lane: precomputed encoder frames (T_enc, d_model); the
    # prompt above stays the decoder prompt
    frames: Optional[np.ndarray] = None
    # seconds from submission after which the request is cancelled —
    # mid-decode cancellation frees the slot for the next admission
    deadline_s: Optional[float] = None
    # streaming: called with each token the moment it is sampled
    on_token: Optional[Callable[[int], None]] = None
    # pending -> queued/waiting -> active -> done | expired | rejected
    status: str = "pending"
    generation: Optional[int] = None    # ticket generation that served it
    submitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_at is None or self.submitted_at is None:
            return None
        return self.first_token_at - self.submitted_at


@dataclass
class ServeReport:
    """Cumulative scheduler/throughput accounting (see ``report``)."""
    requests: int = 0
    prefills: int = 0
    decode_steps: int = 0
    tokens_generated: int = 0
    slot_occupancy: float = 0.0     # mean busy-slot fraction per decode step
    wall_s: float = 0.0
    tokens_per_s: float = 0.0
    bsmm_enabled: bool = False
    routed_matmuls: int = 0
    live_tiles: int = 0
    total_tiles: int = 0
    skipped_tile_fraction: float = 0.0
    # per-request latency distribution (seconds / tokens-per-second)
    ttft_p50: float = 0.0
    ttft_p95: float = 0.0
    tps_p50: float = 0.0
    tps_p95: float = 0.0
    deadline_misses: int = 0
    swaps: int = 0                  # committed hot-swaps (rollbacks undo)
    # paged-KV accounting (zeros when the engine runs dense caches)
    paged: bool = False
    kv_blocks: int = 0              # pool size per generation (incl. scratch)
    kv_blocks_live: int = 0         # blocks holding live context right now
    kv_blocks_peak: int = 0         # max simultaneous live blocks (all gens)
    kv_block_bytes: int = 0         # KV bytes per block across all layers
    kv_bytes_per_token: float = 0.0  # mean KV bytes read per decoded token


@dataclass
class _Generation:
    """One ticket's serving bundle: params + plan + jitted fns + the
    slot lanes it is decoding.  Swaps append a new one; old ones drain."""
    gid: int
    params: Any
    masks: Any
    plan: Any
    plan_stats: PlanStats
    prefill_exact: Callable
    prefill_masked: Callable
    prefill_frames: Callable
    decode: Callable
    slot_reqs: List[Optional[Request]]
    slot_gens: List[Optional[Any]]
    cur: np.ndarray
    slot_caches: Any = None
    served: int = 0                 # requests prefilled on this ticket
    # paged-KV state (None / unused when the engine runs dense caches)
    pool: Optional[BlockPool] = None
    paged_caches: Any = None        # block pools, one per attention layer
    decode_paged: Optional[Callable] = None
    adopt: Optional[Callable] = None
    tables: Optional[np.ndarray] = None       # (slots, NB) int32
    lens: Optional[np.ndarray] = None         # (slots,) int32 tokens written
    slot_nblocks: Optional[np.ndarray] = None  # blocks allocated per slot
    sized: dict = field(default_factory=dict)  # per-capacity jitted prefills

    def active_count(self) -> int:
        return sum(1 for r in self.slot_reqs if r is not None)

    def free_slot(self, s: int) -> None:
        self.slot_reqs[s] = None
        self.slot_gens[s] = None


def _default_buckets(limit: int) -> List[int]:
    """Power-of-two prefill buckets capped at the largest *admissible*
    prefill length.  ``max_new_tokens >= 1`` means no admitted prompt is
    ever longer than ``limit - 1`` tokens, so a bucket at ``limit``
    would compile a prefill closure no request can reach."""
    top = max(limit - 1, 1)
    out, b = [], 8
    while b < top:
        out.append(b)
        b *= 2
    out.append(top)
    return out


def _pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


class ServeEngine:
    """Continuous-batching scheduler over pure prefill/decode functions.

    ``masks`` (optional): the pruned ticket's mask pytree — turns on
    block-sparse decode (``use_bsmm`` can force it off; it is never
    forced on without masks).  ``decode_fn`` must then accept a
    ``plan=`` kwarg (``models.transformer.decode_step`` does).

    ``queue_limit`` bounds the intake queue: beyond it ``submit``
    rejects with the retryable ``"capacity"`` reason (None = unbounded,
    the legacy batch behaviour).  ``clock`` injects a time source for
    deadline tests.  ``heartbeat``/``heartbeat_worker`` wire a
    ``distributed.fault_tolerance.HeartbeatMonitor``: every scheduler
    tick beats, so a wedged decode step surfaces as a stale heartbeat
    the front-end turns into an unhealthy admission gate.

    Oversized requests — ``len(prompt) + max_new_tokens > capacity`` —
    are rejected at ``submit`` (``SubmitRejected("oversize")``) rather
    than silently decoding past the KV-cache capacity.  With paged KV
    the static limit moves out to ``max_context`` and admission becomes
    dynamic (see below).

    ``paged`` (default None = auto) switches decode onto the paged KV
    cache: auto-enables when the architecture supports it
    (``transformer.supports_paged_decode``) and ``decode_fn`` is the
    stock ``transformer.decode_step`` (custom decode fns keep dense
    slot caches — they never learned the paged protocol).  ``kv_blocks``
    sizes each generation's block pool (default: one scratch block +
    enough blocks for every slot at dense ``capacity``, so the default
    paged engine admits at least the dense engine's load); block id 0
    is the scratch block idle table rows point at.
    """

    def __init__(self, *, params, cfg, prefill_fn, decode_fn,
                 batch_slots: int = 8, capacity: int = 512,
                 greedy: Optional[bool] = None, temperature: float = 0.0,
                 sample_seed: int = 0, masks=None,
                 use_bsmm: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 queue_limit: Optional[int] = None,
                 clock: Optional[Callable[[], float]] = None,
                 heartbeat=None, heartbeat_worker: str = "engine",
                 paged: Optional[bool] = None,
                 kv_blocks: Optional[int] = None,
                 mesh=None, rules=None):
        if batch_slots < 1:
            raise ValueError(f"batch_slots must be >= 1, got {batch_slots}")
        if capacity < 2:
            raise ValueError(f"capacity must be >= 2, got {capacity}")
        # -- SPMD: a (data, model) Mesh + ShardingRules shard every
        # generation's params, tile plans and slot/paged KV caches with
        # NamedShardings, and the jitted closures trace with the rules'
        # activation constrainer installed (scoped — it never leaks
        # into other engines' traces).  GSPMD then partitions the same
        # scheduler code; on a 1-device mesh all specs are replicated
        # and the engine is bit-identical to the meshless path.
        self.mesh = mesh
        if rules is None and mesh is not None:
            from repro.distributed.sharding import ShardingRules
            rules = ShardingRules(mesh,
                                  head_dim=getattr(cfg, "head_dim", None))
        self.rules = rules
        self.cfg = cfg
        self.capacity = capacity
        self.slots = batch_slots
        # greedy=None (default) derives from temperature, so passing
        # temperature=0.8 alone turns sampling on; an explicit greedy
        # wins over temperature
        self.greedy = (temperature <= 0.0) if greedy is None else greedy
        self.temperature = temperature
        self.sample_seed = sample_seed
        self._prefill_fn = prefill_fn
        self._decode_fn = decode_fn

        self._interpret = default_interpret(interpret)
        self._use_bsmm = use_bsmm

        # -- masked (bucketed) vs exact-length prefill ------------------
        self._masked_prefill = tfm.supports_masked_prefill(cfg)

        # -- paged KV cache ---------------------------------------------
        paged_ok = (tfm.supports_paged_decode(cfg)
                    and decode_fn is tfm.decode_step)
        if paged is None:
            paged = paged_ok
        elif paged and not paged_ok:
            raise ValueError(
                "paged=True needs a paged-capable architecture (all-global-"
                "attention) and the stock transformer.decode_step decode_fn")
        self.paged = bool(paged)
        if self.paged:
            if kv_blocks is None:
                kv_blocks = self.slots * blocks_needed(capacity,
                                                       BLOCK_TOKENS) + 1
            if kv_blocks < 2:
                raise ValueError(f"kv_blocks must be >= 2, got {kv_blocks}")
            self.kv_blocks = int(kv_blocks)
            self.max_context = (self.kv_blocks - 1) * BLOCK_TOKENS
        else:
            self.kv_blocks = 0
            self.max_context = capacity

        self._buckets = sorted(prefill_buckets) if prefill_buckets \
            else _default_buckets(self.max_context)

        self.queue_limit = queue_limit
        self.clock = clock or time.perf_counter
        self.heartbeat = heartbeat
        self.heartbeat_worker = heartbeat_worker
        self.health = EngineHealth()

        self.queue: Deque[Request] = deque()
        self._axes = None
        self._splice = None              # built lazily from the first prefill
        self._gens: List[_Generation] = []
        self._next_gid = 0
        self._finished: List[Request] = []
        self._prefills = 0
        self._decode_steps = 0
        self._tokens = 0
        self._busy_acc = 0
        self._deadline_misses = 0
        self._swaps = 0
        self._kv_bytes = 0           # analytic KV bytes read by paged decode
        self._kv_tokens = 0          # tokens decoded on the paged path
        self._kv_peak = 0            # peak live blocks across generations
        self._block_bytes = 0        # KV bytes per block across all layers
        self._t0: Optional[float] = None
        self._t_last: Optional[float] = None
        self._install_generation(params, masks, use_bsmm)

    # -- SPMD plumbing -----------------------------------------------------
    def _constrained(self, fn):
        """Wrap a closure body so ITS traces see this engine's
        activation constraints.  The previously installed rules are
        restored afterwards, so engines with different meshes (or none)
        coexist in one process — including the single-device oracle an
        engine is verified against."""
        if self.rules is None:
            return fn
        rules = self.rules

        def wrapped(*args):
            from repro.distributed import sharding as _sharding
            prev = _sharding.installed()
            _sharding.install(rules)
            try:
                return fn(*args)
            finally:
                _sharding.install(prev)

        return wrapped

    def _shard_caches(self, caches):
        """NamedShardings for freshly created slot/paged cache arrays
        (decode outputs inherit the placement GSPMD propagates)."""
        if self.rules is None:
            return caches
        return jax.device_put(caches, self.rules.cache_shardings(caches))

    # -- generations (the hot-swap machinery) ------------------------------
    def _install_generation(self, params, masks, use_bsmm) -> int:
        # the ticket's TilePlans drive BOTH serving paths: prefill
        # projections skip the same dead tiles decode skips.  The
        # plan= kwarg is passed only when a plan exists, so unpruned
        # engines keep working with prefill/decode fns that never
        # learned to accept it (``models.transformer``'s do).
        plan, stats = (build_decode_plan(masks, interpret=self._interpret)
                       if masks is not None else (None, PlanStats()))
        if use_bsmm is False:
            plan, stats = None, PlanStats()
        elif use_bsmm and plan is None:
            raise ValueError("use_bsmm=True needs masks with routable "
                             "dense projections")
        if self.rules is not None:
            params = jax.device_put(params,
                                    self.rules.params_shardings(params))
            if plan is not None:
                plan = self.rules.shard_plan(plan)
        cfg, capacity = self.cfg, self.capacity
        prefill_fn, decode_fn = self._prefill_fn, self._decode_fn
        plankw = {} if plan is None else {"plan": plan}
        gen = _Generation(
            gid=self._next_gid, params=params, masks=masks, plan=plan,
            plan_stats=stats,
            prefill_exact=jax.jit(self._constrained(
                lambda p, toks: prefill_fn(p, cfg, {"tokens": toks},
                                           capacity, **plankw))),
            prefill_masked=jax.jit(self._constrained(
                lambda p, toks, vl: prefill_fn(p, cfg, {"tokens": toks},
                                               capacity, valid_len=vl,
                                               **plankw))),
            prefill_frames=jax.jit(self._constrained(
                lambda p, toks, fr: prefill_fn(p, cfg,
                                               {"tokens": toks,
                                                "frames": fr},
                                               capacity, **plankw))),
            decode=jax.jit(self._constrained(
                lambda p, caches, tok: decode_fn(p, cfg, caches, tok,
                                                 **plankw))),
            slot_reqs=[None] * self.slots,
            slot_gens=[None] * self.slots,
            cur=np.zeros((self.slots,), np.int32))
        if self.paged:
            gen.pool = BlockPool(self.kv_blocks)
            gen.paged_caches = self._shard_caches(
                tfm.make_paged_caches(cfg, self.kv_blocks))
            if not self._block_bytes:
                spec = tfm.paged_cache_spec(cfg, self.kv_blocks)
                total = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                            for s in jax.tree.leaves(spec))
                self._block_bytes = total // self.kv_blocks
            gen.decode_paged = jax.jit(self._constrained(
                lambda p, caches, tok, tables, lens: tfm.decode_step_paged(
                    p, cfg, caches, tok, tables, lens, **plankw)))
            gen.adopt = jax.jit(self._constrained(
                lambda paged, dense, blocks: tfm.adopt_prefill(
                    cfg, paged, dense, blocks)))
            nb = self.kv_blocks - 1     # one request may hold every block
            gen.tables = np.zeros((self.slots, nb), np.int32)
            gen.lens = np.zeros((self.slots,), np.int32)
            gen.slot_nblocks = np.zeros((self.slots,), np.int64)
        self._next_gid += 1
        self._gens.append(gen)
        return gen.gid

    @property
    def current_generation(self) -> int:
        """Generation id new admissions will prefill on."""
        return self._gens[-1].gid

    @property
    def generations(self) -> Tuple[_Generation, ...]:
        """Live ticket generations, oldest → newest.  A read-only view
        for verification tooling (``repro.analysis`` checks each
        generation's plan against its masks and traces its closures);
        the scheduler itself only ever touches ``self._gens``."""
        return tuple(self._gens)

    def swap(self, params, masks=None, use_bsmm: Optional[bool] = None
             ) -> int:
        """Install a new ticket generation WITHOUT draining traffic.

        In-flight requests finish on the generation (params + tile
        plans + caches) that prefilled them; every admission from this
        call on prefills on the new ticket.  Returns the new generation
        id (``rollback`` it if a post-swap verification fails)."""
        if use_bsmm is None:
            use_bsmm = self._use_bsmm
        gid = self._install_generation(params, masks, use_bsmm)
        self._swaps += 1
        return gid

    def rollback(self, gid: int) -> None:
        """Discard a just-swapped generation that has served nothing.

        The ticket manager swaps, smoke-verifies against the ticket's
        recorded fingerprint, and rolls back on mismatch — admissions
        in between are impossible because the scheduler is not stepped
        during verification."""
        gen = self._gens[-1]
        if gen.gid != gid:
            raise ValueError(f"generation {gid} is not the newest "
                             f"swapped-in generation")
        if gen.served or gen.active_count():
            raise RuntimeError(f"generation {gid} already served "
                               f"{gen.served} request(s); cannot roll back")
        if len(self._gens) == 1:
            raise ValueError("cannot roll back the only live generation")
        self._gens.pop()
        self._swaps -= 1

    def _gen_by_gid(self, gid: int) -> _Generation:
        for g in self._gens:
            if g.gid == gid:
                return g
        raise KeyError(f"no live generation {gid}")

    # -- health ------------------------------------------------------------
    def set_health(self, healthy: bool, reason: str = "ok") -> None:
        self.health = EngineHealth(healthy, reason)

    def evict_all(self) -> List[Request]:
        """Failover drain: remove every queued and in-slot request
        WITHOUT finishing it.  Slots free, paged blocks (and unspent
        reservations) return to their pools, and the requests come back
        unfinished (status ``"evicted"``, emitted tokens kept) so a
        fleet router can re-dispatch them onto surviving engines —
        re-prefilling from prompt + emitted tokens continues a greedy
        stream exactly where this engine left it."""
        out: List[Request] = []
        for gen in self._gens:
            for s in range(self.slots):
                req = gen.slot_reqs[s]
                if req is not None:
                    self._free_slot(gen, s)
                    req.status = "evicted"
                    out.append(req)
        while self.queue:
            req = self.queue.popleft()
            req.status = "evicted"
            out.append(req)
        return out

    # -- request intake ----------------------------------------------------
    def submit(self, req: Request) -> None:
        if not self.health.healthy:
            raise SubmitRejected(
                "unhealthy", f"request {req.uid}: engine is unhealthy "
                f"({self.health.reason}); admission stopped", req.uid)
        n = len(req.prompt)
        if n < 1:
            raise SubmitRejected(
                "empty_prompt", f"request {req.uid}: empty prompt", req.uid)
        if req.max_new_tokens < 1:
            raise SubmitRejected(
                "bad_budget", f"request {req.uid}: max_new_tokens must be "
                f">= 1, got {req.max_new_tokens}", req.uid)
        if n + req.max_new_tokens > self.max_context:
            what = (f"paged KV limit ((kv_blocks-1)*BLOCK = "
                    f"{self.max_context})" if self.paged
                    else f"KV-cache capacity ({self.capacity})")
            raise SubmitRejected(
                "oversize",
                f"request {req.uid}: prompt ({n}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds {what}; shorten the "
                "request or raise capacity",
                req.uid)
        if self.queue_limit is not None \
                and len(self.queue) >= self.queue_limit:
            raise SubmitRejected(
                "capacity", f"request {req.uid}: intake queue full "
                f"({self.queue_limit}); retry when slots free", req.uid)
        if req.submitted_at is None:
            req.submitted_at = self.clock()
        req.status = "queued"
        self.queue.append(req)

    # -- sampling ----------------------------------------------------------
    def _gen_for(self, req: Request):
        # per-request stream: sampling stays batch-invariant too
        return np.random.default_rng((self.sample_seed, req.uid))

    def _sample_row(self, logits_row: np.ndarray, gen) -> int:
        """Greedy argmax, or temperature sampling via the Gumbel trick.

        ``temperature <= 0`` degrades to argmax so callers can sweep a
        temperature schedule down to deterministic decoding.
        """
        if self.greedy or self.temperature <= 0.0:
            return int(np.argmax(logits_row))
        z = logits_row.astype(np.float64) / self.temperature
        g = gen.gumbel(size=z.shape)
        return int(np.argmax(z + g))

    # -- cache plumbing ----------------------------------------------------
    # Cache leaves are NOT uniformly batch-leading: scan-stacked segments
    # are (reps, B, ...) with the batch axis second.  The model reports
    # each leaf's batch axis (``transformer.cache_batch_axes``); leaves
    # whose ndim equals their axis (scalar cache indices) get a slot
    # axis appended.
    def _cache_axes(self, proto):
        if self._axes is None:
            try:
                self._axes = tfm.cache_batch_axes(self.cfg, proto)
            except ValueError:
                # not a segment-structured decoder cache (the enc-dec
                # lane): every leaf is batch-leading
                self._axes = jax.tree.map(lambda _: 0, proto)
        return self._axes

    def _empty_slot_caches(self, proto):
        """Zeros shaped like ``proto`` with the batch axis = slot count."""
        def mk(leaf, a):
            leaf = jnp.asarray(leaf)
            if leaf.ndim <= a:           # scalar index: append slot axis
                return jnp.zeros((*leaf.shape, self.slots), leaf.dtype)
            shape = list(leaf.shape)
            shape[a] = self.slots
            return jnp.zeros(tuple(shape), leaf.dtype)
        return self._shard_caches(
            jax.tree.map(mk, proto, self._cache_axes(proto)))

    def _make_splice(self, proto):
        """Jitted: copy a single-request prefill cache into slot lanes."""
        axes = self._cache_axes(proto)

        def impl(slot_caches, new_caches, slot):
            def sp(dst, src, a):
                src = jnp.asarray(src)
                lane = (slice(None),) * a + (slot,)
                if src.ndim <= a:        # scalar index leaf
                    return dst.at[lane].set(src)
                return dst.at[lane].set(jnp.take(src, 0, axis=a))
            return jax.tree.map(sp, slot_caches, new_caches, axes)

        return jax.jit(impl)

    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    def _sized_prefill(self, gen: _Generation, masked: bool):
        """Paged-mode prefill closures: the dense cache capacity is the
        *padded prompt length* (``toks.shape[1]``, static at trace), not
        the engine capacity — the cache only exists long enough to be
        scattered into pool blocks, so sizing it to the prompt keeps
        adopt cost linear in the prompt.  One jitted fn per generation;
        jax retraces per bucket exactly like the dense closures."""
        key = "masked" if masked else "exact"
        fn = gen.sized.get(key)
        if fn is None:
            cfg, prefill_fn = self.cfg, self._prefill_fn
            plankw = {} if gen.plan is None else {"plan": gen.plan}
            if masked:
                fn = jax.jit(self._constrained(lambda p, toks, vl: prefill_fn(
                    p, cfg, {"tokens": toks}, toks.shape[1], valid_len=vl,
                    **plankw)))
            else:
                fn = jax.jit(self._constrained(lambda p, toks: prefill_fn(
                    p, cfg, {"tokens": toks}, toks.shape[1], **plankw)))
            gen.sized[key] = fn
        return fn

    def _prefill_request(self, gen: _Generation, req: Request, rng):
        """Single-request prefill → (first sampled token, caches, S).

        ``rng`` is the request's sampling stream — shared with the
        decode loop so prefill and decode draws never reuse noise.
        ``S`` is the dense cache length actually prefilled (the padded
        prompt length in paged mode; the engine capacity otherwise).
        """
        prompt = np.asarray(req.prompt, np.int32)
        n = len(prompt)
        if req.frames is not None:
            # enc-dec lane: encoder frames ride along; exact-length
            # decoder prefill (frames shape is config-static, so the
            # trace caches like the bucketed path)
            frames = np.asarray(req.frames, np.float32)
            logits, caches = gen.prefill_frames(
                gen.params, jnp.asarray(prompt[None]),
                jnp.asarray(frames[None]))
            S = self.capacity
        elif self._masked_prefill:
            S = self._bucket(n)
            toks = np.zeros((1, S), np.int32)
            toks[0, :n] = prompt                       # right-pad
            fn = self._sized_prefill(gen, True) if self.paged \
                else gen.prefill_masked
            logits, caches = fn(gen.params, jnp.asarray(toks),
                                jnp.asarray([n], jnp.int32))
            S = S if self.paged else self.capacity
        else:
            fn = self._sized_prefill(gen, False) if self.paged \
                else gen.prefill_exact
            logits, caches = fn(gen.params, jnp.asarray(prompt[None]))
            S = n if self.paged else self.capacity
        tok = self._sample_row(np.asarray(logits[0, -1]), rng)
        return tok, caches, S

    # -- lifecycle helpers -------------------------------------------------
    def _finish(self, req: Request, status: str,
                out: Optional[List[Request]] = None) -> None:
        req.done = True
        req.status = status
        req.finished_at = self.clock()
        self._finished.append(req)
        if out is not None:
            out.append(req)

    def _emit_token(self, req: Request, tok: int) -> None:
        req.tokens.append(tok)
        self._tokens += 1
        if req.first_token_at is None:
            req.first_token_at = self.clock()
        if req.on_token is not None:
            req.on_token(tok)

    def _expired(self, req: Request) -> bool:
        return (req.deadline_s is not None and req.submitted_at is not None
                and self.clock() - req.submitted_at > req.deadline_s)

    def expire(self, req: Request) -> None:
        """Mark a not-yet-admitted request deadline-expired (the
        front-end's wait-queue sweep books misses here so the report
        counts every miss once)."""
        self._deadline_misses += 1
        self._finish(req, "expired")

    def _expire_queue(self, out: List[Request]) -> None:
        keep: Deque[Request] = deque()
        while self.queue:
            req = self.queue.popleft()
            if self._expired(req):
                self._deadline_misses += 1
                self._finish(req, "expired", out)
            else:
                keep.append(req)
        self.queue = keep

    def _free_slot(self, gen: _Generation, s: int) -> None:
        """Release a slot AND its paged-KV state: blocks (plus any
        unspent reservation) go back to the generation's pool, the
        table row resets to the scratch block, the length to zero."""
        req = gen.slot_reqs[s]
        if gen.pool is not None and req is not None:
            gen.pool.release(req.uid)
            gen.tables[s, :] = 0
            gen.lens[s] = 0
            gen.slot_nblocks[s] = 0
        gen.free_slot(s)

    def _expire_slots(self, out: List[Request]) -> None:
        # mid-decode cancellation: the slot is freed NOW and refilled
        # this same tick — an expired request never blocks admission
        for gen in self._gens:
            for s in range(self.slots):
                req = gen.slot_reqs[s]
                if req is not None and self._expired(req):
                    self._deadline_misses += 1
                    self._finish(req, "expired", out)
                    self._free_slot(gen, s)

    # -- the scheduler -----------------------------------------------------
    def _adopt_request(self, gen: _Generation, req: Request, s: int,
                       caches, n: int, S: int) -> None:
        """Scatter a request's dense prefill caches into pool blocks and
        point slot ``s``'s table row at them.  Blocks are drawn from the
        request's reservation; table entries past the prompt (the padded
        bucket tail) stay on the scratch block — pad keys land there or
        in the last real block's tail, both masked by ``lens``."""
        nb_real = blocks_needed(n, BLOCK_TOKENS)
        nb_total = blocks_needed(S, BLOCK_TOKENS)
        blocks = [gen.pool.alloc(req.uid) for _ in range(nb_real)]
        blocks += [0] * (nb_total - nb_real)
        gen.paged_caches = gen.adopt(gen.paged_caches, caches,
                                     jnp.asarray(blocks, jnp.int32))
        gen.tables[s, :] = 0
        gen.tables[s, :nb_real] = blocks[:nb_real]
        gen.lens[s] = n
        gen.slot_nblocks[s] = nb_real

    def _refill(self, out: List[Request]) -> None:
        gen = self._gens[-1]            # admissions target: newest ticket
        for s in range(self.slots):
            while gen.slot_reqs[s] is None and self.queue:
                req = self.queue.popleft()
                if self._expired(req):
                    self._deadline_misses += 1
                    self._finish(req, "expired", out)
                    continue
                n = len(req.prompt)
                if gen.pool is not None:
                    # dynamic admission: the request enters a slot only
                    # when its whole block budget can be reserved —
                    # every later alloc is then guaranteed, so decode
                    # never deadlocks mid-stream.  Short on blocks →
                    # the request waits at the FIFO head (no reorder)
                    # until finished requests release theirs.
                    need = blocks_needed(n + req.max_new_tokens,
                                         BLOCK_TOKENS)
                    if not gen.pool.can_reserve(need):
                        self.queue.appendleft(req)
                        return
                    gen.pool.reserve(req.uid, need)
                rng = self._gen_for(req)
                tok, caches, S = self._prefill_request(gen, req, rng)
                self._prefills += 1
                gen.served += 1
                req.generation = gen.gid
                req.status = "active"
                self._emit_token(req, tok)
                if ((req.eos_id is not None and tok == req.eos_id)
                        or req.max_new_tokens <= 1):
                    if gen.pool is not None:
                        gen.pool.release(req.uid)
                    self._finish(req, "done", out)   # done at prefill
                    continue
                if gen.pool is not None:
                    self._adopt_request(gen, req, s, caches, n, S)
                else:
                    if gen.slot_caches is None:
                        gen.slot_caches = self._empty_slot_caches(caches)
                        if self._splice is None:
                            self._splice = self._make_splice(caches)
                    gen.slot_caches = self._splice(gen.slot_caches, caches,
                                                   jnp.asarray(s, jnp.int32))
                gen.slot_reqs[s] = req
                gen.slot_gens[s] = rng
                gen.cur[s] = tok
        self._kv_peak = max(self._kv_peak, self.kv_blocks_live)

    def _decode_gen(self, gen: _Generation, out: List[Request]) -> None:
        active = [s for s in range(self.slots)
                  if gen.slot_reqs[s] is not None]
        if not active:
            return
        if gen.pool is not None:
            # alloc-on-append: the block the new token lands in
            # (lens // BLOCK) must exist before the decode step writes
            # it.  Draws come from the request's reservation, so they
            # cannot fail.
            for s in active:
                req = gen.slot_reqs[s]
                while gen.slot_nblocks[s] <= gen.lens[s] // BLOCK_TOKENS:
                    pid = gen.pool.alloc(req.uid)
                    gen.tables[s, gen.slot_nblocks[s]] = pid
                    gen.slot_nblocks[s] += 1
            self._kv_peak = max(self._kv_peak, self.kv_blocks_live)
            # copy the host-side table/len arrays at the device boundary:
            # jnp.asarray of a numpy array may alias its buffer on CPU,
            # and the scheduler mutates these in place while the decode
            # step is still dispatching (async) — aliasing would race
            logits, gen.paged_caches = gen.decode_paged(
                gen.params, gen.paged_caches,
                jnp.asarray(gen.cur[:, None].copy()),
                jnp.asarray(gen.tables.copy()), jnp.asarray(gen.lens.copy()))
            # analytic bytes: the kernel gathers ceil((len+1)/BLOCK)
            # live blocks per active row — bandwidth scales with live
            # context, independent of capacity/kv_blocks
            self._kv_bytes += self._block_bytes * sum(
                blocks_needed(int(gen.lens[s]) + 1, BLOCK_TOKENS)
                for s in active)
            self._kv_tokens += len(active)
            gen.lens[active] += 1
        else:
            logits, gen.slot_caches = gen.decode(
                gen.params, gen.slot_caches, jnp.asarray(gen.cur[:, None]))
        self._decode_steps += 1
        self._busy_acc += len(active)
        logits_h = np.asarray(logits[:, 0])
        for s in active:
            req = gen.slot_reqs[s]
            tok = self._sample_row(logits_h[s], gen.slot_gens[s])
            self._emit_token(req, tok)
            gen.cur[s] = tok
            if ((req.eos_id is not None and tok == req.eos_id)
                    or len(req.tokens) >= req.max_new_tokens):
                self._finish(req, "done", out)
                self._free_slot(gen, s)  # freed: refilled next tick

    def step(self) -> List[Request]:
        """One scheduler tick: deadline sweep, slot refill (newest
        generation), one decode step per generation with live slots,
        retire drained generations, heartbeat.  Returns the requests
        that finished this tick."""
        if self._t0 is None:
            self._t0 = self.clock()
        out: List[Request] = []
        self._expire_queue(out)
        self._expire_slots(out)
        if self.queue:
            self._refill(out)
        for gen in list(self._gens):
            self._decode_gen(gen, out)
        newest = self._gens[-1]
        self._gens = [g for g in self._gens
                      if g is newest or g.active_count()]
        self._t_last = self.clock()
        if self.heartbeat is not None:
            self.heartbeat.beat(self.heartbeat_worker)
        return out

    @property
    def idle(self) -> bool:
        return not self.queue and all(g.active_count() == 0
                                      for g in self._gens)

    @property
    def kv_blocks_live(self) -> int:
        """Blocks holding live context, summed over live generations."""
        return sum(g.pool.live for g in self._gens if g.pool is not None)

    def run(self) -> List[Request]:
        """Serve everything in the queue to completion (continuous).

        Returns the requests that finished during this call;
        ``self.report`` holds the cumulative accounting.
        """
        start = len(self._finished)
        while not self.idle:
            self.step()
        return self._finished[start:]

    # -- verification ------------------------------------------------------
    def smoke_decode(self, prompt, max_new: int, *,
                     gid: Optional[int] = None, frames=None) -> List[int]:
        """Greedy-decode one probe prompt through a generation's jitted
        prefill/decode WITHOUT touching slot state or the queue — the
        ticket manager verifies a swapped-in generation against the
        ticket's recorded fingerprint before committing to it."""
        gen = self._gens[-1] if gid is None else self._gen_by_gid(gid)
        prompt = np.asarray(prompt, np.int32)
        if frames is not None:
            logits, caches = gen.prefill_frames(
                gen.params, jnp.asarray(prompt[None]),
                jnp.asarray(np.asarray(frames, np.float32)[None]))
        elif len(prompt) + max_new > self.capacity:
            # probe longer than the dense capacity (possible in paged
            # mode, where admission allows it): verify through a
            # right-sized dense prefill/decode pair instead
            cap = len(prompt) + max_new
            key = ("smoke", cap)
            fns = gen.sized.get(key)
            if fns is None:
                cfg, prefill_fn = self.cfg, self._prefill_fn
                decode_fn = self._decode_fn
                plankw = {} if gen.plan is None else {"plan": gen.plan}
                fns = (jax.jit(self._constrained(lambda p, toks: prefill_fn(
                           p, cfg, {"tokens": toks}, cap, **plankw))),
                       jax.jit(self._constrained(
                           lambda p, caches, tok: decode_fn(
                               p, cfg, caches, tok, **plankw))))
                gen.sized[key] = fns
            pf, dec = fns
            logits, caches = pf(gen.params, jnp.asarray(prompt[None]))
            tok = int(np.argmax(np.asarray(logits[0, -1])))
            out = [tok]
            for _ in range(max_new - 1):
                logits, caches = dec(gen.params, caches,
                                     jnp.asarray([[tok]], jnp.int32))
                tok = int(np.argmax(np.asarray(logits[0, 0])))
                out.append(tok)
            return out
        else:
            logits, caches = gen.prefill_exact(gen.params,
                                               jnp.asarray(prompt[None]))
        tok = int(np.argmax(np.asarray(logits[0, -1])))
        out = [tok]
        for _ in range(max_new - 1):
            logits, caches = gen.decode(gen.params, caches,
                                        jnp.asarray([[tok]], jnp.int32))
            tok = int(np.argmax(np.asarray(logits[0, 0])))
            out.append(tok)
        return out

    # -- accounting --------------------------------------------------------
    @property
    def report(self) -> ServeReport:
        """Live cumulative report; latency percentiles come from every
        finished request's timestamps (TTFT = first token − submission;
        tokens/s = tokens over total request latency)."""
        fin = self._finished
        wall = ((self._t_last - self._t0)
                if self._t0 is not None and self._t_last is not None
                else 0.0)
        ttft = [r.ttft for r in fin if r.ttft is not None]
        tps = [len(r.tokens) / max(r.finished_at - r.submitted_at, 1e-9)
               for r in fin
               if r.tokens and r.finished_at is not None
               and r.submitted_at is not None]
        cur = self._gens[-1]
        st = cur.plan_stats
        return ServeReport(
            requests=len(fin),
            prefills=self._prefills,
            decode_steps=self._decode_steps,
            tokens_generated=self._tokens,
            slot_occupancy=(self._busy_acc / (self._decode_steps
                                              * self.slots)
                            if self._decode_steps else 0.0),
            wall_s=wall,
            tokens_per_s=self._tokens / wall if wall > 0 else 0.0,
            bsmm_enabled=cur.plan is not None,
            routed_matmuls=st.routed,
            live_tiles=st.live_tiles,
            total_tiles=st.total_tiles,
            skipped_tile_fraction=st.skipped_tile_fraction,
            ttft_p50=_pct(ttft, 50), ttft_p95=_pct(ttft, 95),
            tps_p50=_pct(tps, 50), tps_p95=_pct(tps, 95),
            deadline_misses=self._deadline_misses,
            swaps=self._swaps,
            paged=self.paged,
            kv_blocks=self.kv_blocks,
            kv_blocks_live=self.kv_blocks_live,
            kv_blocks_peak=self._kv_peak,
            kv_block_bytes=self._block_bytes,
            kv_bytes_per_token=(self._kv_bytes / self._kv_tokens
                                if self._kv_tokens else 0.0),
        )
