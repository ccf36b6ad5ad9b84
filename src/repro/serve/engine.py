"""Serving engine: prefill/decode with an inline Planter gate.

The paper's deployment story is ML *coexisting* with the switch's
mandatory function at line rate (switch.p4 + ML, §7.3/Fig. 16).  Here the
mandatory function is LM decoding; the Planter-mapped classifier runs on
the request stream *inside the same jitted step* (``fused_step``), so
admission control costs no extra dispatch and its FLOPs/bytes are visible
in the step's cost analysis (benchmarks/coexist.py measures exactly the
paper's relative-latency experiment).

Two batchers share the scheduling semantics (ascending-slot fill, FIFO
queue, EOS/max-token eviction):

* ``ContinuousBatcher`` — the host-driven reference: one jit dispatch and
  one logits sync per token, slot bookkeeping in Python.
* ``DeviceContinuousBatcher`` — the hot path: all slot state lives in a
  donated device pytree and gate-predict -> decode -> greedy sample ->
  evict -> refill is ONE jitted step, run ``sync_every`` steps per host
  round trip (the driver only drains finished sequences).  Admission is
  one batched gate launch over the whole waiting queue, and the gate's
  verdicts drive slot eviction *inside* the step.

Both batchers run either decode-cache layout:

* **dense** (default): one global position, ``[B, cache_len]`` ring
  cache, single-token prompts — the seed semantics, kept bit-stable.
* **paged** (``ServeConfig(page_size=...)``): block-table page pool with
  per-slot position offsets; prompts are token sequences.  The host
  batcher seeds them one token per launch (the measured baseline), the
  device batcher consumes ``prefill_chunk`` tokens per fused step —
  bit-identical streams, ``ceil(P/chunk)`` launches instead of P.
  Admission reserves a request's whole worst-case page footprint
  (``page_demand``), so live slots never stall on an empty pool and a
  pool smaller than ``B x cache_len`` oversubscribes slots (more live
  slots at fixed cache memory).

The paged pool is refcounted (``serve.pages.PagePool``) and grows two
multipliers on top of paging:

* ``share_prefix=True`` — requests with a common token prefix share
  read-only prefix pages (prefix trie + cache holds, copy-on-write on
  a partially matching tail page); N sharers pin ~1x instead of Nx
  prefix pages, with streams bit-identical to the unshared pool.
* ``kv_int8=True`` — int8 page pool with per-page f32 scale planes
  (quantize on write, dequant in the gathered attention), ~2x pool
  tokens per byte at the dense int8 cache's round-trip bound.

Dropped requests record a reason in ``drop_reasons`` and a wall-clock
stamp in ``dropped_at``: ``gate-reject`` (Planter verdict),
``queue-full`` (bounded ``max_queue``, after ``max_retries`` backoff
re-attempts when enabled), ``empty-prompt`` (zero-token submit, which
also raises), ``deadline`` (per-request ``deadline_s`` exceeded — checked
at admission and every drain boundary; mid-flight expiry evicts the slot
and reclaims its pages) and ``quarantined`` (the per-drain finite check
caught a poisoned sample in that slot — only the offending slot is
evicted).  Failure injection (``serve.faults.FaultInjector``) applies at
host drain boundaries ONLY: the jitted kernel is byte-identical with or
without a fault plan attached, and the fault path costs nothing when no
fault is active.
"""
from __future__ import annotations

import collections
import dataclasses
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..arch import model as M
from ..arch import sampling as S
from ..arch.config import ArchConfig
from ..core.pipeline import MappedModel
from ..dist import sharding as SH
from ..nn import attn_backend as AB
from ..obs.trace import step_time_interp
from .pages import PagePool
from .pages import page_demand as _page_demand


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    cache_len: int = 256
    gate_action_drop: int = 1  # gate label that means "drop request"
    # paged KV cache geometry: page_size > 0 switches the serve path to
    # the block-table cache (per-slot position offsets, chunked
    # prefill).  ``pages`` sizes the physical pool; 0 = one full
    # cache_len worth of pages per slot (no oversubscription — the
    # dense-equivalent footprint).  Smaller pools oversubscribe: a slot
    # only pins ceil((prompt+max_tokens)/page_size) pages while live,
    # so at fixed cache memory strictly more slots fit than the dense
    # [B, cache_len] cache allows.
    page_size: int = 0
    pages: int = 0
    # prefix sharing: requests with a common token prefix map their
    # full prefix pages to shared read-only pool entries (refcounted,
    # copy-on-write on the partial tail page) — N sharers pin ~1x
    # instead of Nx prefix pages.  Streams are bit-identical to the
    # unshared pool: shared pages hold exactly what each sharer would
    # have written itself.
    share_prefix: bool = False
    # int8 page pool: quantize_kv_int8 on write + dequant on gather,
    # ~2x more pool tokens per byte at the <= scale/2 round-trip bound
    # (the paged analogue of the dense int8 cache).
    kv_int8: bool = False
    # cap on pages the prefix cache may hold (None = pool minus one
    # full slot, so cached prefixes can never starve admission)
    prefix_hold_budget: Optional[int] = None
    # paged-attention backend (repro.nn.attn_backend registry):
    # 'auto' = Pallas kernel on TPU / jnp gather oracle elsewhere;
    # explicit 'jnp' | 'pallas' force one (the kernel runs in interpret
    # mode off-TPU — slow, correctness-leg only).  Backends agree to the
    # tolerance stated in nn.attn_backend.
    attn_impl: str = "auto"
    # on-device sampling (arch.sampling): STATIC python scalars, so
    # temperature=0.0 compiles to exactly the seed argmax (greedy stays
    # bit-identical, no noise evaluated).  temperature > 0 draws
    # counter-based noise keyed by (request seed, generated-token
    # index) — streams are invariant to batching, chunking, sync_every
    # and wave boundaries, and identical on the host and device paths.
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0 (0 = greedy), got "
                f"{self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1], got {self.top_p}")
        if self.temperature == 0.0 and (self.top_k or self.top_p < 1.0):
            raise ValueError(
                "top_k/top_p filter a sampling distribution; with "
                "temperature=0 decoding is exact greedy argmax — set "
                "temperature > 0 to enable the filters")
        if self.page_size:
            if self.cache_len % self.page_size:
                raise ValueError(
                    f"cache_len {self.cache_len} must be a multiple of "
                    f"page_size {self.page_size}")
        elif self.share_prefix or self.kv_int8:
            raise ValueError(
                "share_prefix/kv_int8 are page-pool features: set "
                "ServeConfig(page_size=...) to enable the paged cache")
        if self.attn_impl not in AB.valid_impls():
            raise ValueError(
                f"attn_impl must be one of {AB.valid_impls()}; "
                f"got {self.attn_impl!r}")

    @property
    def paged(self) -> bool:
        return self.page_size > 0

    @property
    def pages_per_slot(self) -> int:
        return self.cache_len // self.page_size

    @property
    def n_pages(self) -> int:
        return self.pages or self.max_batch * self.pages_per_slot

    @property
    def kv_dtype(self) -> str:
        return "int8" if self.kv_int8 else "bf16"

    @property
    def hold_budget(self) -> int:
        if self.prefix_hold_budget is not None:
            return self.prefix_hold_budget
        return max(0, self.n_pages - min(self.n_pages, self.pages_per_slot))

    def make_pool(self) -> PagePool:
        """The host-side page allocator both batchers build on."""
        return PagePool(self.n_pages, self.page_size,
                        share_prefix=self.share_prefix,
                        hold_budget=self.hold_budget)


def page_demand(scfg: ServeConfig, prompt_len: int, max_tokens: int) -> int:
    """Pages a request pins while live: reservation-based admission
    (prompt + worst-case decode), so in-flight slots can never stall on
    an empty pool and the step needs no mid-flight allocator.  Delegates
    to ``serve.pages.page_demand`` — the ONE reservation formula the
    allocator, submit-side validation and the fused step all share."""
    return _page_demand(scfg.page_size, prompt_len, max_tokens)


def validate_prompt(scfg: ServeConfig, prompt_tokens, max_tokens: int,
                    dense_ok: bool = False) -> list:
    """Normalize a submit()-side prompt (bare int = length-1) and check
    it can ever be served — the ONE validation all batchers and the
    router share, so submit-time rejection can never drift from the
    in-step reservation rule.  ``dense_ok`` marks callers that can loop
    a multi-token prompt on the dense cache (the host batcher); the
    fused device step and the router's shard batchers cannot.
    """
    prompt = ([int(prompt_tokens)] if np.isscalar(prompt_tokens)
              else [int(t) for t in prompt_tokens])
    if not prompt:
        raise ValueError(
            "empty prompt: a request must carry at least one token — it "
            "can never produce output and would reserve zero-demand pages")
    if scfg.paged:
        demand = page_demand(scfg, len(prompt), max_tokens)
        if demand > min(scfg.n_pages, scfg.pages_per_slot):
            raise ValueError(
                f"prompt of {len(prompt)} tokens + {max_tokens} decode "
                f"tokens needs {demand} pages, but only "
                f"{min(scfg.n_pages, scfg.pages_per_slot)} fit")
    elif len(prompt) > 1 and not dense_ok:
        raise ValueError(
            "multi-token prompts need the paged cache "
            "(ServeConfig(page_size=...)); the dense cache has one "
            "global position per step")
    return prompt


def validate_prompt_or_drop(scfg: ServeConfig, request_id, prompt_tokens,
                            max_tokens: int, dropped: list,
                            drop_reasons: dict,
                            dense_ok: bool = False,
                            dropped_at: Optional[dict] = None) -> list:
    """``validate_prompt`` with drop bookkeeping: an empty prompt is
    recorded in ``drop_reasons`` (reason ``empty-prompt``) before the
    ValueError surfaces, so the rejected request never silently vanishes
    from accounting — and never reserves zero-demand pages."""
    try:
        return validate_prompt(scfg, prompt_tokens, max_tokens, dense_ok)
    except ValueError as e:
        if "empty prompt" in str(e):
            dropped.append(request_id)
            drop_reasons[request_id] = "empty-prompt"
            if dropped_at is not None:
                dropped_at[request_id] = time.perf_counter()
        raise


def _default_seed(request_id) -> int:
    """Deterministic per-request sampling seed when ``submit()`` passes
    none: a CRC32 of the request id's repr, resolved AT SUBMIT TIME so
    the host batcher, the device batcher and the router's failover
    replay all derive the same stream for the same request.  Hashing
    the id (instead of a shared constant) decorrelates the default
    streams of distinct requests."""
    return zlib.crc32(repr(request_id).encode()) & 0x7FFFFFFF


def _drop_request(b, rid, reason: str, now: Optional[float] = None,
                  trace: bool = True, step: Optional[int] = None) -> None:
    """Shared terminal-drop bookkeeping for both batchers: reason +
    wall-clock stamp (``dropped_at`` rides next to ``done_at``), deadline
    cleanup, tracer/metrics emission (``step``: the absolute device step,
    where known).  ``trace=False`` leaves emission to the caller — the
    device batcher emits an in-step gate drop at its admission stamp."""
    now = b._clock() if now is None else now
    b.dropped.append(rid)
    b.drop_reasons[rid] = reason
    b.dropped_at[rid] = now
    b.deadline.pop(rid, None)
    if trace and b.tracer is not None:
        if reason == "deadline":
            b.tracer.deadline_dropped(rid, t=now, step=step,
                                      shard=b.trace_shard)
        elif reason == "quarantined":
            b.tracer.quarantined(rid, t=now, step=step, shard=b.trace_shard)
        else:
            b.tracer.dropped(rid, reason, t=now, step=step)


def _defer_full(b, rid, prompt, feat, dabs) -> None:
    """Queue-full with retries enabled: park the request in the backoff
    queue instead of dropping.  Attempts are scheduled in *drain
    boundaries* (not wall-clock), so backoff is deterministic under test
    and scales with actual serving progress."""
    b._retry_q.append([b._drains + b.retry_backoff, 1, rid, prompt,
                       feat, dabs])
    if b.metrics is not None:
        b.metrics.counter("serve.queue_full_deferred").inc()


def _service_retries(b) -> None:
    """Re-attempt deferred submissions whose backoff expired.  Entry
    layout: ``[due_drain, attempt, rid, prompt, feat, deadline_abs]``.
    On a still-full queue the entry reschedules with exponential backoff
    (``retry_backoff * 2**attempt`` drains) until ``max_retries`` is
    exhausted -> ``queue-full`` drop; an expired deadline drops as
    ``deadline`` without consuming an attempt."""
    if not b._retry_q:
        return
    now = b._clock()
    rest: collections.deque = collections.deque()
    while b._retry_q:
        ent = b._retry_q.popleft()
        due, attempt, rid, prompt, feat, dabs = ent
        if dabs is not None and now > dabs:
            _drop_request(b, rid, "deadline", now)
            continue
        if due > b._drains:
            rest.append(ent)
            continue
        if b.max_queue is None or len(b.queue) < b.max_queue:
            if dabs is not None:
                b.deadline[rid] = dabs
            b.queue.append((rid, prompt, feat))
            if b.tracer is not None:
                b.tracer.retried(rid, attempt=attempt, t=now,
                                 shard=b.trace_shard)
            elif b.metrics is not None:
                b.metrics.counter("serve.requests_retried").inc()
            continue
        if attempt >= b.max_retries:
            _drop_request(b, rid, "queue-full", now)
            continue
        ent[0] = b._drains + b.retry_backoff * (1 << attempt)
        ent[1] = attempt + 1
        rest.append(ent)
    b._retry_q = rest


class ServeEngine:
    """Batched decode with optional inline Planter admission gate."""

    def __init__(self, cfg: ArchConfig, params, scfg: ServeConfig,
                 gate: Optional[MappedModel] = None,
                 gate_backend: str = "jnp", mesh=None,
                 tp_params: bool = False):
        self.cfg = cfg
        self.mesh = mesh
        self.tp_params = bool(tp_params)
        if mesh is not None:
            # place once: params REPLICATED across the shard's devices
            # by default, the decode cache per
            # `dist.sharding.cache_pspec` (batch over data, KV sequence
            # over model).  Tensor-parallel param sharding
            # (``tp_params=True``) is opt-in on the serve path: the
            # row-parallel psum reassociates the hidden-dim reduction
            # and can flip bf16 greedy argmaxes at deeper cache
            # positions, so TP runs are gated on a token-flip *rate*
            # (``serve_bench --parity-tol``) instead of the bit-exact
            # parity the replicated placement guarantees.
            from jax.sharding import NamedSharding, PartitionSpec

            if tp_params:
                params = jax.device_put(
                    params, SH.param_shardings(params, mesh))
            else:
                params = jax.device_put(
                    params, NamedSharding(mesh, PartitionSpec()))
        self.params = params
        self.scfg = scfg
        self.gate = gate
        # 'auto' resolves via MappedModel.select_backend (fused Pallas EB
        # kernel on TPU for gate-sized tables, jnp oracle elsewhere)
        self.gate_fn = gate.jax_predict(gate_backend) if gate else None
        self._admit = None
        if self.gate_fn is not None:
            gate_only = self.gate_fn

            def admit_labels(feats):
                with jax.named_scope("gate"):
                    return gate_only(feats)

            self._admit = jax.jit(admit_labels)
        # the decode cache is lazy: only the host-driven paths (step /
        # generate / ContinuousBatcher) touch engine.state, and
        # DeviceContinuousBatcher keeps its own donated cache — eager
        # allocation would double serve-path cache memory per shard
        self._state = None
        self._step = jax.jit(
            lambda p, s, t: M.decode_step(p, s, t, cfg))
        self._sample = jax.jit(
            lambda p, s, t: M.decode_step(p, s, t, cfg, sample_greedy=True))
        if self.gate_fn is not None:
            gate_fn = self.gate_fn

            def fused(p, s, t, feats):
                labels = gate_fn(feats)
                logits, s = M.decode_step(p, s, t, cfg)
                return logits, s, labels

            def fused_sample(p, s, t, feats):
                labels = gate_fn(feats)
                nxt, s = M.decode_step(p, s, t, cfg, sample_greedy=True)
                return nxt, s, labels

            self._fused = jax.jit(fused)
            self._fused_sample = jax.jit(fused_sample)
        else:
            self._fused = None
            self._fused_sample = None
        # paged serve path: chunked multi-token steps with per-slot
        # position offsets through the block-table cache
        self._paged_kv = None
        if scfg.paged:
            self._paged_sample = jax.jit(
                lambda p, kv, tbl, pos, t, n: M.paged_decode_step(
                    p, kv, tbl, pos, t, n, cfg, sample_greedy=True,
                    attn_impl=scfg.attn_impl))
            # logits variant for temperature > 0: the host batcher
            # samples from these with its own per-slot seeds/indices
            self._paged_logits = jax.jit(
                lambda p, kv, tbl, pos, t, n: M.paged_decode_step(
                    p, kv, tbl, pos, t, n, cfg,
                    attn_impl=scfg.attn_impl))
            # COW: seed a request's fresh tail page with a copy of a
            # shared page (all layers, every pool leaf incl. scales)
            self._copy_page = jax.jit(
                lambda kv, s, d: jax.tree.map(
                    lambda pool: pool.at[:, d].set(pool[:, s]), kv),
                donate_argnums=(0,))
        else:
            self._paged_sample = None

    @property
    def state(self):
        if self._state is None:
            st = M.init_decode_state(self.cfg, self.scfg.max_batch,
                                     self.scfg.cache_len)
            if self.mesh is not None:
                st = jax.device_put(
                    st, SH.cache_shardings(st, self.mesh,
                                           self.scfg.max_batch))
            self._state = st
        return self._state

    @state.setter
    def state(self, value):
        self._state = value

    @property
    def paged_kv(self):
        """Lazy physical page pool for the host-driven paged loop
        (``ContinuousBatcher`` over a paged engine); the device batcher
        keeps its own donated pool, same as the dense cache."""
        if self._paged_kv is None:
            kv = M.init_paged_kv(self.cfg, self.scfg.n_pages,
                                 self.scfg.page_size,
                                 kv_dtype=self.scfg.kv_dtype)
            if self.mesh is not None:
                kv = jax.device_put(
                    kv, SH.paged_kv_shardings(kv, self.mesh))
            self._paged_kv = kv
        return self._paged_kv

    @paged_kv.setter
    def paged_kv(self, value):
        self._paged_kv = value

    def copy_page(self, src: int, dst: int):
        """Copy physical page ``src`` over ``dst`` in the host pool (the
        COW half of prefix sharing: ``dst`` is a freshly reserved page
        with refcount 1, never a page another request can see)."""
        self._paged_kv = self._copy_page(self.paged_kv, jnp.int32(src),
                                         jnp.int32(dst))

    def step_paged(self, tokens: np.ndarray, block_tbl: np.ndarray,
                   pos: np.ndarray, n_new: np.ndarray) -> np.ndarray:
        """One chunked paged step (host-driven): greedy next token per
        slot at its own position offset; the page pool stays on device."""
        nxt, self._paged_kv = self._paged_sample(
            self.params, self.paged_kv, jnp.asarray(block_tbl, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(tokens, jnp.int32),
            jnp.asarray(n_new, jnp.int32))
        return nxt

    def step_paged_logits(self, tokens: np.ndarray, block_tbl: np.ndarray,
                          pos: np.ndarray, n_new: np.ndarray):
        """Chunked paged step returning last-position logits per slot
        (the host batcher's sampling path, ``temperature > 0``)."""
        logits, self._paged_kv = self._paged_logits(
            self.params, self.paged_kv, jnp.asarray(block_tbl, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(tokens, jnp.int32),
            jnp.asarray(n_new, jnp.int32))
        return logits

    # ------------------------------------------------------------ admission
    def admit(self, features: np.ndarray) -> np.ndarray:
        """Planter gate on request features -> keep mask (True = admit).

        One gate launch for the whole feature matrix — callers batch the
        waiting queue rather than gating request-by-request.
        """
        if self.gate_fn is None:
            return np.ones(len(features), bool)
        labels = np.asarray(self._admit(jnp.asarray(features)))
        return labels != self.scfg.gate_action_drop

    # --------------------------------------------------------------- decode
    def step(self, tokens: np.ndarray,
             features: Optional[np.ndarray] = None, block: bool = True):
        """One decode step for the whole batch; gate fused when present.

        ``block=False`` returns device arrays (no host sync) so callers
        can keep sampling on device; the default converts to numpy for
        backward compatibility.
        """
        t = jnp.asarray(tokens)
        if self._fused is not None and features is not None:
            logits, self.state, labels = self._fused(
                self.params, self.state, t, jnp.asarray(features))
            if not block:
                return logits, labels
            return np.asarray(logits), np.asarray(labels)
        logits, self.state = self._step(self.params, self.state, t)
        if not block:
            return logits, None
        return np.asarray(logits), None

    def generate(self, prompts: np.ndarray, n_tokens: int,
                 features: Optional[np.ndarray] = None,
                 block: bool = True) -> np.ndarray:
        """Greedy generation; prompts [B, P] seed the cache token by token.

        The argmax stays on device (``decode_step(sample_greedy=True)``)
        and prompts are transferred once up front, so the loop issues
        dispatches without ever syncing logits to host; the only sync is
        the final result (skipped with ``block=False``).
        """
        B, P = prompts.shape
        assert B == self.scfg.max_batch
        dprompts = jnp.asarray(prompts, jnp.int32)
        feats = (jnp.asarray(features)
                 if (features is not None and self._fused_sample is not None)
                 else None)
        out = []
        tok = dprompts[:, :1]
        for i in range(P + n_tokens - 1):
            if feats is not None:
                nxt, self.state, _ = self._fused_sample(
                    self.params, self.state, tok, feats)
            else:
                nxt, self.state = self._sample(self.params, self.state, tok)
            nxt = nxt[:, None]
            tok = dprompts[:, i + 1: i + 2] if i + 1 < P else nxt
            if i + 1 >= P:
                out.append(nxt)
        res = (jnp.concatenate(out, axis=1) if out
               else jnp.zeros((B, 0), jnp.int32))
        return np.asarray(res) if block else res


class ContinuousBatcher:
    """Slot-based continuous batching over a ServeEngine (host-driven).

    The fleet-scale serving pattern: a fixed decode batch of ``max_batch``
    slots; finished sequences release their slot, the admission gate
    filters the waiting queue, and freed slots refill immediately — no
    global drain between requests.  Per-slot position bookkeeping keeps
    one shared cache (slot i writes its own rows; sequences are
    left-aligned since every slot starts at its admission step, which is
    sufficient for throughput accounting and tested for isolation).

    Per-slot gate features are threaded through ``engine.step`` so the
    fused gate+decode path runs in continuous mode too (the labels are
    advisory here; ``DeviceContinuousBatcher`` wires them into eviction).
    This class is the measured baseline for ``benchmarks/serve_bench`` —
    it syncs logits to host every token by design.
    """

    def __init__(self, engine: ServeEngine, eos_token: int = 0,
                 max_tokens: int = 32, max_queue: Optional[int] = None,
                 tracer=None, metrics=None, max_retries: int = 0,
                 retry_backoff: int = 1,
                 deadline_s: Optional[float] = None,
                 fault_injector=None,
                 clock: Callable[[], float] = time.perf_counter):
        self.engine = engine
        self.eos = eos_token
        self.max_tokens = max_tokens
        self.max_queue = max_queue
        self.tracer = None
        self.metrics = None
        self.trace_shard = 0
        # failure handling: queue-full retry budget (drain-boundary
        # backoff), default deadline, drain-boundary fault injector and
        # an injectable clock (tests pin deadlines deterministically)
        self.max_retries = int(max_retries)
        self.retry_backoff = max(1, int(retry_backoff))
        self.default_deadline_s = deadline_s
        self.injector = fault_injector
        self._clock = clock
        self._drains = 0
        self._retry_q: collections.deque = collections.deque()
        self._exh_holds: List[list] = []
        self._vocab = engine.cfg.vocab_size
        scfg = engine.scfg
        B = scfg.max_batch
        self.slot_free = np.ones(B, bool)
        self.slot_prompt: list = [[] for _ in range(B)]
        self.slot_ptr = np.zeros(B, np.int64)  # prompt tokens consumed
        self.slot_gen: list = [[] for _ in range(B)]
        self.slot_req: list = [None] * B
        self.slot_feat: Optional[np.ndarray] = None  # [B, F] once known
        # per-request sampling seeds (resolved at submit; _default_seed
        # when the caller passes none) + the per-slot mirror the
        # sampler reads.  temperature == 0 never touches either.
        self.seeds: dict = {}
        self.slot_seed = np.zeros(B, np.int32)
        self._sampler = None
        if scfg.temperature > 0.0:
            t, k, p = scfg.temperature, scfg.top_k, scfg.top_p
            self._sampler = jax.jit(
                lambda lg, sd, gi: S.sample_tokens(lg, sd, gi, t, k, p))
        self.queue: collections.deque = collections.deque()
        self.done: dict = {}
        self.done_at: dict = {}  # request_id -> perf_counter at completion
        self.dropped: list = []
        self.drop_reasons: dict = {}  # request_id -> why it was dropped
        self.dropped_at: dict = {}  # request_id -> perf_counter at drop
        self.deadline: dict = {}  # request_id -> absolute deadline
        self.max_live = 0  # peak concurrent slots (pool-sizing evidence)
        if scfg.paged:
            # per-slot position offsets + block table; allocation,
            # refcounts and the prefix trie live in the shared PagePool
            self.slot_pos = np.zeros(B, np.int64)
            self.slot_tbl = np.full((B, scfg.pages_per_slot),
                                    scfg.n_pages, np.int32)
            self.pool = scfg.make_pool()
            self.slot_res: list = [None] * B
        self.attach_obs(tracer, metrics)

    def attach_obs(self, tracer=None, metrics=None) -> None:
        """Attach a ``repro.obs`` Tracer/Metrics pair (None detaches).
        Instrumentation is host-side bookkeeping only — the decode math
        and token streams are identical with obs on or off."""
        self.tracer = tracer
        self.metrics = metrics
        if tracer is not None and metrics is not None \
                and tracer.metrics is None:
            tracer.metrics = metrics
        if metrics is not None and self.engine.scfg.paged:
            self.pool.bind_metrics(metrics)

    @property
    def page_free(self) -> np.ndarray:
        """Free-page mask view over the refcounted pool (a page is free
        iff nothing — live slot or prefix cache — references it)."""
        return self.pool.ref == 0

    def submit(self, request_id, prompt_tokens,
               features: Optional[np.ndarray] = None,
               deadline_s: Optional[float] = None,
               seed: Optional[int] = None):
        """Enqueue a request.  ``prompt_tokens`` is a token sequence (a
        bare int is accepted as a length-1 prompt); the host loop feeds
        it one token per step — the measured token-by-token baseline the
        chunked device path is benchmarked against.  ``deadline_s``
        (falls back to the batcher default) bounds queue + serve time:
        an already-expired budget drops at admission, a mid-flight
        expiry evicts the slot at the next drain boundary.  ``seed``
        keys the request's sampling noise when ``temperature > 0``
        (default: a deterministic hash of the request id)."""
        self.seeds[request_id] = (int(seed) if seed is not None
                                  else _default_seed(request_id))
        try:
            prompt = validate_prompt_or_drop(
                self.engine.scfg, request_id, prompt_tokens,
                self.max_tokens, self.dropped, self.drop_reasons,
                dense_ok=True, dropped_at=self.dropped_at)
        except ValueError:
            if (self.tracer is not None
                    and self.drop_reasons.get(request_id) == "empty-prompt"):
                self.tracer.dropped(request_id, "empty-prompt")
            raise
        if self.tracer is not None:
            self.tracer.submitted(request_id)
        ddl = deadline_s if deadline_s is not None else self.default_deadline_s
        dabs = None
        if ddl is not None:
            if ddl <= 0:
                _drop_request(self, request_id, "deadline")
                return False
            dabs = self._clock() + float(ddl)
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            if self.max_retries > 0:
                _defer_full(self, request_id, prompt, features, dabs)
                return True
            _drop_request(self, request_id, "queue-full")
            return False
        if features is not None:
            keep = self.engine.admit(features[None])[0]
            if not keep:
                _drop_request(self, request_id, "gate-reject")
                return False
        if dabs is not None:
            self.deadline[request_id] = dabs
        self.queue.append((request_id, prompt, features))
        return True

    def _fill_slots(self):
        scfg = self.engine.scfg
        if scfg.paged:
            self.pool.begin_wave()
        track = self.tracer is not None or bool(self.deadline)
        now = self._clock() if track else 0.0
        free_idx = list(np.where(self.slot_free)[0])
        fi = 0
        while fi < len(free_idx) and self.queue:
            b = free_idx[fi]
            rid, prompt, feat = self.queue[0]
            dabs = self.deadline.get(rid)
            if dabs is not None and now > dabs:
                # admission-side deadline check: an expired queue head
                # never takes a slot (or pages) — drop and retry the
                # same free slot against the next entry
                self.queue.popleft()
                _drop_request(self, rid, "deadline", now)
                continue
            res = None
            if scfg.paged:
                # reservation-based admission: the request's whole
                # worst-case footprint (minus shared prefix pages) must
                # be free, so live slots never stall mid-stream; FIFO
                # blocks (no leapfrogging) when the head doesn't fit —
                # identical to the device step's in-fill capacity rule
                res = self.pool.reserve(prompt, self.max_tokens)
                if res is None:
                    break
                self.slot_tbl[b] = scfg.n_pages
                self.slot_tbl[b, : len(res.tbl)] = res.tbl
                if res.cow is not None:
                    # COW: the fresh tail page starts as a copy of the
                    # partially-matching cached page; rows past the
                    # match are stale until overwritten (mask-safe)
                    self.engine.copy_page(*res.cow)
                self.slot_pos[b] = res.start
                self.slot_res[b] = res
            self.queue.popleft()
            self.slot_free[b] = False
            self.slot_req[b] = rid
            self.slot_seed[b] = self.seeds.get(rid, _default_seed(rid))
            if self.tracer is not None:
                self.tracer.admitted(rid, t=now, shard=self.trace_shard)
            self.slot_prompt[b] = prompt
            # shared prefix tokens are already in the pool: skip them
            self.slot_ptr[b] = res.start if res is not None else 0
            self.slot_gen[b] = []
            if feat is not None:
                if self.slot_feat is None:
                    self.slot_feat = np.zeros(
                        (len(self.slot_free), len(feat)), np.int32)
                self.slot_feat[b] = feat
            fi += 1

    def _evict(self, b, now):
        self.done[self.slot_req[b]] = self.slot_gen[b]
        self.done_at[self.slot_req[b]] = now
        self.deadline.pop(self.slot_req[b], None)
        if self.tracer is not None:
            # same `now` as done_at: tracer spans and drain timestamps
            # agree exactly, not just in order
            self.tracer.finished(self.slot_req[b],
                                 n_tokens=len(self.slot_gen[b]), t=now)
            self.tracer.drained(self.slot_req[b], t=now)
        self.slot_free[b] = True
        self.slot_req[b] = None
        if self.engine.scfg.paged:
            # drop the slot's references; completed full prompt pages
            # register in the prefix trie (a cache hold survives) so
            # later same-prefix requests share instead of re-filling
            self.pool.release(self.slot_res[b], self.slot_prompt[b])
            self.slot_res[b] = None
            self.slot_tbl[b] = self.engine.scfg.n_pages

    def _evict_drop(self, b, reason: str, now: float):
        """Mid-flight eviction on the drop path (deadline / quarantine):
        frees exactly this slot and reclaims its pages via the release
        path WITHOUT trie registration — a dropped request's stream is
        void, so its prefix must never seed the cache."""
        rid = self.slot_req[b]
        self.slot_free[b] = True
        self.slot_req[b] = None
        if self.engine.scfg.paged:
            self.pool.release(self.slot_res[b], self.slot_prompt[b],
                              register=False)
            self.slot_res[b] = None
            self.slot_tbl[b] = self.engine.scfg.n_pages
        _drop_request(self, rid, reason, now)

    def run(self, max_steps: int = 1000) -> dict:
        """Decode until queue + slots drain; returns {request_id: tokens}."""
        B = self.engine.scfg.max_batch
        paged = self.engine.scfg.paged
        use_gate = (self.engine._fused is not None
                    and self.slot_feat is not None)
        inj = self.injector
        for _ in range(max_steps):
            _service_retries(self)
            self._fill_slots()
            self.max_live = max(self.max_live,
                                int((~self.slot_free).sum()))
            if self.slot_free.all() and not self.queue:
                if self._retry_q:
                    # only backed-off retries left: advance the drain
                    # clock so deferred submissions come due (there is
                    # no decode work to run meanwhile)
                    self._drains += 1
                    continue
                break
            use_gate = use_gate or (self.engine._fused is not None
                                    and self.slot_feat is not None)
            # feed the next un-consumed prompt token, else the last
            # generated token (one token per launch: the baseline cost
            # of not having chunked prefill)
            tok = np.zeros(B, np.int32)
            for b in range(B):
                if self.slot_free[b]:
                    continue
                ptr, prompt = self.slot_ptr[b], self.slot_prompt[b]
                tok[b] = (prompt[ptr] if ptr < len(prompt)
                          else self.slot_gen[b][-1])
            sampler = self._sampler
            gi = (np.array([len(self.slot_gen[b]) for b in range(B)],
                           np.int32) if sampler is not None else None)
            if paged:
                if sampler is None:
                    nxt = np.asarray(self.engine.step_paged(
                        tok[:, None], self.slot_tbl, self.slot_pos,
                        (~self.slot_free).astype(np.int32)))
                else:
                    # sample on the last-position logits, keyed by
                    # (request seed, generated-token index) — mid-prompt
                    # draws are discarded below exactly like argmaxes
                    logits = self.engine.step_paged_logits(
                        tok[:, None], self.slot_tbl, self.slot_pos,
                        (~self.slot_free).astype(np.int32))
                    nxt = np.asarray(sampler(logits, self.slot_seed, gi))
            else:
                logits, _ = self.engine.step(
                    tok[:, None], self.slot_feat if use_gate else None)
                if sampler is None:
                    nxt = np.asarray(logits.argmax(axis=-1))
                else:
                    nxt = np.asarray(sampler(logits, self.slot_seed, gi))
            now = self._clock()
            if inj is not None:
                # fault injection lives HERE, at the host drain boundary
                # (the host batcher drains every step) — the jitted
                # decode above never sees a fault plan
                evs = inj.corruptions(self.trace_shard, self._drains)
                if evs:
                    # np.asarray over a jax buffer is a read-only view
                    nxt = nxt.copy()
                for ev in evs:
                    if ev.slot < B and not self.slot_free[ev.slot]:
                        nxt[ev.slot] = ev.value
                if paged:
                    for ev in inj.exhaustions(self.trace_shard,
                                              self._drains):
                        held = self.pool.hold_free_pages()
                        self._exh_holds.append(
                            [self._drains + ev.hold_drains, held])
            for b in range(B):
                if self.slot_free[b]:
                    continue
                if paged:
                    self.slot_pos[b] += 1
                self.slot_ptr[b] = min(self.slot_ptr[b] + 1,
                                       len(self.slot_prompt[b]))
                if self.slot_ptr[b] < len(self.slot_prompt[b]):
                    continue  # mid-prompt prediction: discard
                tokv = int(nxt[b])
                self.slot_gen[b].append(tokv)
                if not (0 <= tokv < self._vocab):
                    # per-drain finite check: greedy argmax can never
                    # emit outside [0, vocab), so an out-of-range token
                    # is a poisoned sample — quarantine exactly this
                    # slot, every other stream unaffected
                    self._evict_drop(b, "quarantined", now)
                    continue
                if self.tracer is not None and len(self.slot_gen[b]) == 1:
                    self.tracer.first_token(self.slot_req[b], t=now)
                if (len(self.slot_gen[b]) >= self.max_tokens
                        or int(nxt[b]) == self.eos):
                    self._evict(b, now)
            if self.deadline:
                for b in range(B):
                    if self.slot_free[b]:
                        continue
                    dabs = self.deadline.get(self.slot_req[b])
                    if dabs is not None and now > dabs:
                        self._evict_drop(b, "deadline", now)
            self._drains += 1
            if self._exh_holds:
                due = [h for h in self._exh_holds if h[0] <= self._drains]
                if due:
                    self._exh_holds = [h for h in self._exh_holds
                                       if h[0] > self._drains]
                    for _, pages in due:
                        self.pool.release_held(pages)
        return self.done


class DeviceContinuousBatcher:
    """Device-resident continuous batching: one fused jitted serve step.

    Reproduces ``ContinuousBatcher``'s schedule exactly — ascending-slot
    fill from a FIFO queue, decode, greedy argmax, EOS/max-token eviction
    — but the whole loop body is a single jitted step over a donated
    ``ServeState`` pytree:

    * slot state (free mask, per-slot generated counts, last tokens, gate
      features) and per-request output rings live on device;
    * the waiting queue is a device array; freed slots refill *inside*
      the step (no host round trip between eviction and admission);
    * the Planter gate runs fused with decode on the per-slot features
      and its verdict is wired into eviction (slot-level admission): a
      slot whose features classify as ``gate_action_drop`` is evicted
      before its first token is recorded;
    * ``sync_every`` steps run back-to-back in a ``lax.while_loop``; the
      Python driver only reads a tiny alive flag + done mask per round
      trip to drain finished sequences.

    Admission is batched: ``run()`` makes ONE gate launch over the whole
    waiting queue (``pregate=True``, matching the reference batcher's
    dropped set), or defers entirely to the in-step verdict
    (``pregate=False``), where dropped requests cost one decode step and
    produce no tokens.

    ``run(max_steps=...)`` is resumable like the host batcher: when the
    step budget expires mid-stream, in-flight slots (including their
    partial token rings) are carried over and un-admitted queue entries
    are re-enqueued, so a later ``run()`` continues the exact same
    schedule.
    """

    def __init__(self, engine: ServeEngine, eos_token: int = 0,
                 max_tokens: int = 32, sync_every: int = 8,
                 pregate: bool = True, mesh=None,
                 prefill_chunk: int = 1, max_queue: Optional[int] = None,
                 tracer=None, metrics=None, max_retries: int = 0,
                 retry_backoff: int = 1,
                 deadline_s: Optional[float] = None,
                 fault_injector=None,
                 clock: Callable[[], float] = time.perf_counter,
                 spec_k: int = 0, draft=None):
        self.engine = engine
        self.eos = int(eos_token)
        self.max_tokens = int(max_tokens)
        self.sync_every = max(1, int(sync_every))
        self.pregate = pregate
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.max_queue = max_queue
        # speculative decoding: a table-mapped draft (serve.spec) drafts
        # ``spec_k`` tokens per decoding slot inside the fused step; the
        # LM verifies the whole chain in one chunked launch.  Greedy
        # (temperature=0) verification is exact — accepted tokens are
        # bit-identical to non-speculative decode; temperature>0 uses
        # the standard rejection-sampling rule (marginal per token is
        # exactly the target distribution).
        self.spec_k = int(spec_k)
        self.draft = draft
        self._draft_tbl = None
        if self.spec_k:
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            if not engine.scfg.paged:
                raise ValueError(
                    "speculative decoding verifies drafts through the "
                    "chunked paged step: set ServeConfig(page_size=...)")
            if draft is None:
                raise ValueError(
                    "spec_k > 0 needs a compiled draft model "
                    "(serve.spec.train_draft / compile_draft)")
            if draft.vocab_size < engine.cfg.vocab_size:
                raise ValueError(
                    f"draft table covers {draft.vocab_size} tokens but "
                    f"the LM vocab is {engine.cfg.vocab_size}")
            self._draft_tbl = draft.device_table()
        # host-side speculative accounting, synced from the device
        # counters at the end of each run()
        self._spec_prop = 0
        self._spec_acc = 0
        self.seeds: dict = {}
        # failure handling (all host-side, applied at sync boundaries):
        # queue-full retry budget, default deadline, drain-boundary
        # fault injector, injectable clock for deterministic tests
        self.max_retries = int(max_retries)
        self.retry_backoff = max(1, int(retry_backoff))
        self.default_deadline_s = deadline_s
        self.injector = fault_injector
        self._clock = clock
        self._drains = 0
        self._retry_q: collections.deque = collections.deque()
        self._exh_holds: List[list] = []
        self._vocab = engine.cfg.vocab_size
        # mesh defaults to the engine's: a placed engine serves a placed
        # batcher unless the caller explicitly overrides
        self.mesh = engine.mesh if mesh is None else mesh
        scfg = engine.scfg
        self._B = scfg.max_batch
        self.paged = scfg.paged
        if self.paged:
            # block-table cache: the physical page pool is the only
            # big allocation; slot state (pos/plen/tbl/pbuf/pref)
            # joins the donated pytree per run.  The PagePool is the
            # host mirror of the in-step refcounts plus the prefix
            # trie consulted at wave build and updated at drain.
            self._pages = M.init_paged_kv(engine.cfg, scfg.n_pages,
                                          scfg.page_size,
                                          kv_dtype=scfg.kv_dtype)
            if self.mesh is not None:
                self._pages = jax.device_put(
                    self._pages, SH.paged_kv_shardings(self._pages,
                                                       self.mesh))
            self.pool = scfg.make_pool()
        else:
            self._decode = M.init_decode_state(engine.cfg, scfg.max_batch,
                                               scfg.cache_len)
            if self.mesh is not None:
                self._decode = jax.device_put(
                    self._decode, SH.cache_shardings(self._decode,
                                                     self.mesh, self._B))
        self.queue: collections.deque = collections.deque()
        self.done: dict = {}
        self.done_at: dict = {}
        # host times from the fused step's stamps (see run())
        self.admitted_at: dict = {}
        self.first_at: dict = {}
        self.dropped: list = []
        self.drop_reasons: dict = {}
        self.dropped_at: dict = {}
        self.deadline: dict = {}  # request_id -> absolute deadline
        # per-slot carryover from a max_steps-bounded run: rid, gen, last
        # token, gate features, partial token ring (+ prompt/pos/block
        # table in paged mode)
        self._carry: List[Optional[dict]] = [None] * self._B
        self._run_k: Dict[Tuple, Callable] = {}
        self.tracer = None
        self.metrics = None
        self.trace_shard = 0
        # device step counter across run() calls: trace events carry
        # absolute step numbers even on resumed/multi-wave schedules
        self._steps_total = 0
        self.attach_obs(tracer, metrics)

    def attach_obs(self, tracer=None, metrics=None) -> None:
        """Attach a ``repro.obs`` Tracer/Metrics pair (None detaches).
        The fused step and the host loop are the same either way: the
        step always stamps each request's admission and first-token
        steps, and an attached Tracer is fed from those stamps after
        each call (``run()``)."""
        self.tracer = tracer
        self.metrics = metrics
        if tracer is not None and metrics is not None \
                and tracer.metrics is None:
            tracer.metrics = metrics
        if metrics is not None and self.paged:
            self.pool.bind_metrics(metrics)

    def submit(self, request_id, prompt_tokens,
               features: Optional[np.ndarray] = None,
               deadline_s: Optional[float] = None,
               seed: Optional[int] = None):
        """Enqueue; admission happens batched in ``run()``.

        ``prompt_tokens`` is a token sequence (bare int = length-1
        prompt).  The paged path prefill-chunks it inside the fused
        step; the dense path has one global position per step, so it
        accepts single-token prompts only.  ``deadline_s`` (falls back
        to the batcher default) bounds queue + serve time: an expired
        budget drops at admission (wave build) and a mid-flight expiry
        evicts the slot at the next sync boundary.  ``seed`` keys the
        request's sampling noise when ``temperature > 0`` (default: a
        deterministic hash of the request id, matching the host
        batcher and the router's failover replay).
        """
        self.seeds[request_id] = (int(seed) if seed is not None
                                  else _default_seed(request_id))
        try:
            prompt = validate_prompt_or_drop(
                self.engine.scfg, request_id, prompt_tokens,
                self.max_tokens, self.dropped, self.drop_reasons,
                dropped_at=self.dropped_at)
        except ValueError:
            if (self.tracer is not None
                    and self.drop_reasons.get(request_id) == "empty-prompt"):
                self.tracer.dropped(request_id, "empty-prompt")
            raise
        if self.tracer is not None:
            self.tracer.submitted(request_id)
        ddl = deadline_s if deadline_s is not None else self.default_deadline_s
        dabs = None
        if ddl is not None:
            if ddl <= 0:
                _drop_request(self, request_id, "deadline")
                return False
            dabs = self._clock() + float(ddl)
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            feat_n = None if features is None else np.asarray(features)
            if self.max_retries > 0:
                _defer_full(self, request_id, prompt, feat_n, dabs)
                return True
            _drop_request(self, request_id, "queue-full")
            return False
        if dabs is not None:
            self.deadline[request_id] = dabs
        self.queue.append((
            request_id, prompt,
            None if features is None else np.asarray(features)))
        return True

    @property
    def kv_pages(self):
        """The device page pool (the KV leaves of the donated slot
        pytree, as the last ``run()`` left them); None on the dense
        path."""
        return self._pages if self.paged else None

    def pending_work(self) -> int:
        """Un-served load: queued entries + backed-off retries +
        in-flight carryover slots (the router's rebalancing signal)."""
        return (len(self.queue) + len(self._retry_q)
                + sum(c is not None for c in self._carry))

    @property
    def _pfree(self) -> np.ndarray:
        """Free-page view over the refcounted pool mirror (a page is
        free iff no live slot and no cached prefix references it)."""
        return self.pool.ref == 0

    def spec_stats(self) -> dict:
        """Cumulative speculative-decoding accounting: drafted tokens,
        accepted tokens, and the acceptance rate (the fraction of draft
        positions the LM verified — the speedup driver)."""
        prop = int(self._spec_prop)
        return {
            "spec_k": self.spec_k,
            "drafted": prop,
            "accepted": int(self._spec_acc),
            "acceptance_rate": (self._spec_acc / prop) if prop else 0.0,
        }

    # ------------------------------------------------------------- step fn
    def _make_run_k(self, n_queue: int, n_out: int, n_feat: int) -> Callable:
        # The step stamps every output row with its local step (``step``
        # counts the call's steps from 1): ``out_admit`` when the row
        # takes a slot, ``out_first`` when it yields its first token.
        # run() reads them with the outputs; a Tracer attached or not,
        # this is the same program.
        cfg = self.engine.cfg
        gate_fn = self.engine.gate_fn
        scfg = self.engine.scfg
        drop = scfg.gate_action_drop
        temp, top_k, top_p = scfg.temperature, scfg.top_k, scfg.top_p
        eos, max_tokens, Nq, R = self.eos, self.max_tokens, n_queue, n_out

        def one_step(params, qtok, qreq, qfeat, qhasf, qseed, nq, st):
            # --- fill freed slots from the device queue (FIFO, ascending
            # slot index — the reference batcher's order); qreq maps a
            # queue entry to its output row (carryover rows come first)
            free = st["free"]
            rank = jnp.cumsum(free.astype(jnp.int32)) - 1
            cand = st["head"] + rank
            take = free & (cand < nq)
            idx = jnp.clip(cand, 0, Nq - 1)
            t = st["step"] + 1
            st = dict(
                st,
                step=t,
                out_admit=st["out_admit"].at[
                    jnp.where(take, qreq[idx], R)].set(t, mode="drop"),
                req=jnp.where(take, qreq[idx], st["req"]),
                last=jnp.where(take, qtok[idx], st["last"]),
                feat=jnp.where(take[:, None], qfeat[idx], st["feat"]),
                hasf=jnp.where(take, qhasf[idx], st["hasf"]),
                seed=jnp.where(take, qseed[idx], st["seed"]),
                gen=jnp.where(take, 0, st["gen"]),
                free=free & ~take,
                head=st["head"] + take.sum(),
            )
            work = (~st["free"]).any()

            def decode_and_evict(st):
                free, req, gen = st["free"], st["req"], st["gen"]
                active = ~free
                tok = jnp.where(free, 0, st["last"])[:, None]
                if temp == 0.0:
                    nxt, dec = M.decode_step(params, st["decode"], tok,
                                             cfg, sample_greedy=True)
                else:
                    # sample keyed by (request seed, generated index):
                    # the stream is a pure function of the request, so
                    # sync_every / wave boundaries can't perturb it
                    logits, dec = M.decode_step(params, st["decode"],
                                                tok, cfg)
                    with jax.named_scope("sample"):
                        nxt = S.sample_tokens(logits, st["seed"], gen,
                                              temp, top_k, top_p)
                # slot-level admission: the fused gate's verdict evicts a
                # just-filled slot before its first token is recorded
                if gate_fn is not None:
                    with jax.named_scope("gate"):
                        labels = gate_fn(st["feat"])
                    gdrop = active & st["hasf"] & (labels == drop)
                else:
                    gdrop = jnp.zeros_like(free)
                out_drop = st["out_drop"].at[
                    jnp.where(gdrop, req, R)].set(True, mode="drop")
                live = active & ~gdrop
                widx = jnp.where(live, req, R)
                out_tok = st["out_tok"].at[
                    widx, jnp.minimum(gen, max_tokens - 1)].set(
                        nxt, mode="drop")
                out_first = st["out_first"].at[
                    jnp.where(live & (gen == 0), req, R)].set(
                        st["step"], mode="drop")
                gen = gen + live.astype(jnp.int32)
                fin = live & ((gen >= max_tokens) | (nxt == eos))
                fidx = jnp.where(fin, req, R)
                return dict(
                    st,
                    decode=dec,
                    out_first=out_first,
                    free=free | gdrop | fin,
                    gen=gen,
                    last=jnp.where(live, nxt, st["last"]),
                    out_tok=out_tok,
                    out_len=st["out_len"].at[fidx].set(gen, mode="drop"),
                    out_done=st["out_done"].at[fidx].set(True, mode="drop"),
                    out_drop=out_drop,
                )

            # no active slots after fill => queue drained too; skip the
            # decode so `pos` matches the reference batcher's early break
            st = jax.lax.cond(work, decode_and_evict, lambda s: s, st)
            return st, work

        def run_k(params, st, qtok, qreq, qfeat, qhasf, qseed, nq, k):
            # k is traced: the host passes min(sync_every, steps
            # left) so max_steps is honoured exactly (no overshoot)
            def cond(c):
                i, _, alive = c
                return (i < k) & alive

            def body(c):
                i, st, _ = c
                st, alive = one_step(params, qtok, qreq, qfeat,
                                     qhasf, qseed, nq, st)
                return i + 1, st, alive

            _, st, alive = jax.lax.while_loop(
                cond, body, (jnp.int32(0), st, jnp.bool_(True)))
            return st, alive

        return jax.jit(run_k, donate_argnums=(1,))

    def _make_run_k_paged(self, n_queue: int, n_out: int, n_feat: int,
                          p_max: int) -> Callable:
        """The paged/chunked variant of the fused serve step.

        Same schedule skeleton as the dense step (ascending-slot FIFO
        fill, gate verdict wired into eviction, done-mask drain), plus:

        * the pool is **refcounted** (``pref``, int32 per page; free =
          count 0): fill allocates each admitted request's *own*-page
          demand (``qdem``: worst-case footprint minus shared prefix
          pages, lowest free pages first, slot-major) and takes one
          reference on every table page — shared prefix pages
          (``qsh``, planned by the host's prefix trie at wave build)
          simply gain a second/third/... reference.  FIFO blocks when
          the pool can't cover the queue head's own demand;
        * a shared partial tail page (``qcow``) is **copied on write**
          into the slot's first own page at fill — the copy target has
          refcount 1 and is invisible to every other request, so a
          shared page is never mutated;
        * prefill starts at ``qstart`` (tokens already covered by
          shared pages are skipped; the final prompt token is always
          re-processed so its logits exist) and advances by up to
          ``prefill_chunk`` prompt tokens per step at the slot's own
          position offset;
        * a slot's next token is recorded only once its prompt is
          consumed (mid-prompt predictions are computed and discarded,
          matching token-by-token seeding bit for bit);
        * eviction drops one reference per table page — except, for
          completed ``reg`` slots, the full-prompt prefix pages, whose
          reference transfers to the prefix cache (the host registers
          them from the ``out_tbl`` ring at drain).  A page frees when
          its count reaches zero.

        It stamps output rows as the dense step does (``out_admit``,
        ``out_first``).
        """
        cfg = self.engine.cfg
        scfg = self.engine.scfg
        gate_fn = self.engine.gate_fn
        drop = scfg.gate_action_drop
        eos, max_tokens, Nq, R = self.eos, self.max_tokens, n_queue, n_out
        C = self.prefill_chunk
        SK = self.spec_k  # draft tokens per decoding slot per step
        Call = max(C, SK + 1) if SK else C  # chunk width of one launch
        dtable = self._draft_tbl
        V = self._vocab
        temp, top_k, top_p = scfg.temperature, scfg.top_k, scfg.top_p
        n_ps, N = scfg.pages_per_slot, scfg.n_pages
        page = scfg.page_size
        share = scfg.share_prefix
        attn_impl = scfg.attn_impl

        def one_step(params, qtok, qlen, qreq, qfeat, qhasf, qsh, qdem,
                     qstart, qcow, qreg, qseed, qwsrc, qwneed, nq, st):
            # --- fill + page reservation (FIFO, ascending slot index)
            free = st["free"]
            B = free.shape[0]
            rank = jnp.cumsum(free.astype(jnp.int32)) - 1
            cand = st["head"] + rank
            idx = jnp.clip(cand, 0, Nq - 1)
            in_q = free & (cand < nq)
            if share:
                # in-wave prefix sharing: a queue entry that READS pages
                # another entry of this wave WRITES (its writer, queue
                # index ``qwsrc``) may only be admitted once the writer
                # has filled the read chain — i.e. the writer's position
                # has reached ``qwneed`` tokens, or the writer already
                # finished (``wdone`` latch).  The cumprod keeps the
                # FIFO-prefix rule: a blocked entry blocks everything
                # behind it (no leapfrogging).
                wsrc = qwsrc[idx]
                wneed = qwneed[idx]
                live_ok = ((~st["free"])[None, :]
                           & (st["qidx"][None, :] == wsrc[:, None])
                           & (st["pos"][None, :] >= wneed[:, None])
                           ).any(axis=1)
                wait_ok = ((wsrc < 0)
                           | st["wdone"][jnp.clip(wsrc, 0, Nq - 1)]
                           | live_ok)
                ok = jnp.cumprod(
                    jnp.where(in_q, wait_ok, True).astype(jnp.int32)
                ).astype(bool)
                in_q = in_q & ok
            # own-page demand: the reservation formula minus the pages
            # the prefix trie already holds (precomputed at wave build,
            # the same rule submit-side validation enforces)
            d = jnp.where(in_q, qdem[idx], 0)
            take = in_q & (jnp.cumsum(d) <= (st["pref"] == 0).sum())
            d = jnp.where(take, d, 0)
            need = jnp.arange(n_ps)[None] < d[:, None]
            flat = need.reshape(-1)
            r = jnp.clip(jnp.cumsum(flat) - 1, 0, N - 1)
            pg = jnp.argsort(st["pref"] != 0)[r]  # lowest free pages 1st
            own = jnp.where(need, pg.reshape(B, n_ps), N)
            # table: shared prefix pages first, own pages after
            nsh = jnp.where(take, (qsh[idx] < N).sum(axis=1), 0)
            jj = jnp.arange(n_ps)[None]
            own_shift = jnp.take_along_axis(
                own, jnp.clip(jj - nsh[:, None], 0, n_ps - 1), axis=1)
            tbl_new = jnp.where(jj < nsh[:, None], qsh[idx], own_shift)
            tbl_new = jnp.where(jj < (nsh + d)[:, None], tbl_new, N)
            pref = st["pref"].at[
                jnp.where(take[:, None] & (tbl_new < N), tbl_new, N)
            ].add(1, mode="drop")
            # COW: seed the first own page with the partially-matching
            # cached page (dst has refcount 1: only this slot sees it).
            # share is static at trace time, so unshared serving never
            # pays the per-step page gather/scatter.
            if share:
                csrc = jnp.where(take, qcow[idx], N)
                cdst = jnp.where(
                    csrc < N,
                    jnp.take_along_axis(
                        tbl_new, jnp.clip(nsh, 0, n_ps - 1)[:, None],
                        axis=1)[:, 0], N)
                pages = jax.tree.map(
                    lambda pool: pool.at[:, cdst].set(
                        pool[:, jnp.clip(csrc, 0, N - 1)], mode="drop"),
                    st["pages"])
            else:
                pages = st["pages"]
            extra = {}
            if share:
                extra["qidx"] = jnp.where(take, idx, st["qidx"])
            t = st["step"] + 1
            st = dict(
                st,
                step=t,
                out_admit=st["out_admit"].at[
                    jnp.where(take, qreq[idx], R)].set(t, mode="drop"),
                req=jnp.where(take, qreq[idx], st["req"]),
                plen=jnp.where(take, qlen[idx], st["plen"]),
                pos=jnp.where(take, qstart[idx], st["pos"]),
                pbuf=jnp.where(take[:, None], qtok[idx], st["pbuf"]),
                last=jnp.where(take, 0, st["last"]),
                feat=jnp.where(take[:, None], qfeat[idx], st["feat"]),
                hasf=jnp.where(take, qhasf[idx], st["hasf"]),
                gen=jnp.where(take, 0, st["gen"]),
                reg=jnp.where(take, qreg[idx], st["reg"]),
                seed=jnp.where(take, qseed[idx], st["seed"]),
                free=free & ~take,
                head=st["head"] + take.sum(),
                tbl=jnp.where(take[:, None], tbl_new, st["tbl"]),
                pref=pref,
                pages=pages,
                **extra,
            )
            work = (~st["free"]).any()

            def decode_and_evict(st):
                free, req, gen = st["free"], st["req"], st["gen"]
                pos, plen = st["pos"], st["plen"]
                active = ~free
                rem = plen - pos
                prefilling = active & (rem > 0)
                decoding = active & ~prefilling
                if SK:
                    # decoding slots run a draft chain of up to SK+1
                    # tokens (``last`` + SK table drafts), capped so an
                    # all-accept step never overshoots max_tokens
                    c_dec = jnp.clip(max_tokens - gen, 1, SK + 1)
                else:
                    c_dec = jnp.ones_like(gen)
                c = jnp.where(
                    active,
                    jnp.where(prefilling, jnp.minimum(C, rem), c_dec), 0)
                jj = jnp.arange(Call)[None]
                gidx = jnp.clip(pos[:, None] + jj, 0, p_max - 1)
                ptoks = jnp.take_along_axis(st["pbuf"], gidx, axis=1)
                if SK:
                    # draft chain: successive successor-table gathers
                    # from the rolling last token
                    dr = [st["last"]]
                    for _ in range(Call - 1):
                        dr.append(dtable[jnp.clip(dr[-1], 0, V - 1)])
                    dchain = jnp.stack(dr, axis=1)
                    chunk = jnp.where(prefilling[:, None], ptoks, dchain)
                else:
                    chunk = jnp.where(
                        prefilling[:, None], ptoks,
                        jnp.where(jj == 0, st["last"][:, None], 0))
                chunk = jnp.where(jj < c[:, None], chunk, 0)
                if gate_fn is not None:
                    with jax.named_scope("gate"):
                        labels = gate_fn(st["feat"])
                    gdrop = active & st["hasf"] & (labels == drop)
                else:
                    gdrop = jnp.zeros_like(free)
                out_drop = st["out_drop"].at[
                    jnp.where(gdrop, req, R)].set(True, mode="drop")
                if SK == 0 and temp == 0.0:
                    nxt, pages = M.paged_decode_step(
                        params, st["pages"], st["tbl"], pos, chunk, c,
                        cfg, sample_greedy=True, attn_impl=attn_impl)
                elif SK == 0:
                    logits, pages = M.paged_decode_step(
                        params, st["pages"], st["tbl"], pos, chunk, c,
                        cfg, attn_impl=attn_impl)
                    with jax.named_scope("sample"):
                        nxt = S.sample_tokens(logits, st["seed"], gen,
                                              temp, top_k, top_p)
                if SK == 0:
                    pos = pos + c
                    rec = active & (pos >= plen)  # prompt consumed
                    live = rec & ~gdrop
                    widx = jnp.where(live, req, R)
                    out_tok = st["out_tok"].at[
                        widx, jnp.minimum(gen, max_tokens - 1)].set(
                            nxt, mode="drop")
                    gen = gen + live.astype(jnp.int32)
                    fin = live & ((gen >= max_tokens) | (nxt == eos))
                else:
                    # --- speculative verify: one chunked launch scores
                    # every chain position (the chunked-prefill kernel
                    # *is* the verify primitive)
                    if temp == 0.0:
                        tok_all, pages = M.paged_decode_step(
                            params, st["pages"], st["tbl"], pos, chunk,
                            c, cfg, sample_greedy=True,
                            all_positions=True, attn_impl=attn_impl)
                    else:
                        logits_all, pages = M.paged_decode_step(
                            params, st["pages"], st["tbl"], pos, chunk,
                            c, cfg, all_positions=True,
                            attn_impl=attn_impl)
                    jm = jnp.arange(Call - 1)[None]
                    if temp == 0.0:
                        # greedy: accept the longest draft prefix that
                        # matches the LM argmax at the previous position;
                        # position acc then holds the LM's correction —
                        # bit-identical to sequential greedy decode
                        match = ((chunk[:, 1:] == tok_all[:, :-1])
                                 & (jm < (c - 1)[:, None]))
                        acc = jnp.cumprod(
                            match.astype(jnp.int32), axis=1).sum(axis=1)
                        E = tok_all
                        tok_first = jnp.take_along_axis(
                            tok_all,
                            jnp.clip(c - 1, 0, Call - 1)[:, None],
                            axis=1)[:, 0]
                    else:
                        # standard rejection sampling: accept draft j
                        # with prob p(d_j); on first rejection resample
                        # from the masked renormalized distribution; on
                        # full accept draw the bonus token.  Noise is
                        # keyed on (seed, generated-index) so streams
                        # are invariant to acceptance history length.
                        probs = S.token_probs(logits_all, temp,
                                              top_k, top_p)
                        Vp = probs.shape[-1]
                        u = S.uniform(st["seed"][:, None],
                                      gen[:, None] + jm, salt=1)
                        p_acc = jnp.take_along_axis(
                            probs[:, :-1, :],
                            jnp.clip(chunk[:, 1:, None], 0, Vp - 1),
                            axis=2)[..., 0]
                        amask = (u < p_acc) & (jm < (c - 1)[:, None])
                        acc = jnp.cumprod(
                            amask.astype(jnp.int32), axis=1).sum(axis=1)
                        full = acc >= c - 1
                        fidx_r = jnp.where(
                            decoding, jnp.clip(acc, 0, Call - 1),
                            jnp.clip(c - 1, 0, Call - 1))
                        l_fin = jnp.take_along_axis(
                            logits_all, fidx_r[:, None, None],
                            axis=1)[:, 0]
                        p_fin = jnp.take_along_axis(
                            probs, fidx_r[:, None, None], axis=1)[:, 0]
                        kpos = gen + jnp.where(decoding, acc, 0)
                        with jax.named_scope("sample"):
                            bonus = S.sample_tokens(l_fin, st["seed"], kpos,
                                                    temp, top_k, top_p)
                        x_rej = jnp.take_along_axis(
                            chunk,
                            jnp.clip(acc + 1, 0, Call - 1)[:, None],
                            axis=1)[:, 0]
                        lanes = jnp.arange(Vp)[None]
                        p_masked = jnp.where(lanes == x_rej[:, None],
                                             jnp.float32(0.0), p_fin)
                        resamp = S.categorical(p_masked, st["seed"],
                                               kpos, salt=2)
                        final = jnp.where(decoding & ~full,
                                          resamp, bonus)
                        dshift = jnp.concatenate(
                            [chunk[:, 1:],
                             jnp.zeros((B, 1), chunk.dtype)], axis=1)
                        E = jnp.where(jj < acc[:, None], dshift, 0)
                        E = jnp.where(jj == acc[:, None],
                                      final[:, None], E)
                        tok_first = final
                    m0 = acc + 1  # accepted drafts + 1 emitted token
                    # truncate the emission at the first EOS
                    eosj = jnp.where((E == eos) & (jj < m0[:, None]),
                                     jj, Call)
                    e1 = eosj.min(axis=1)
                    m = jnp.where(e1 < Call,
                                  jnp.minimum(m0, e1 + 1), m0)
                    pos = jnp.where(decoding, pos + m, pos + c)
                    rec = active & (pos >= plen)  # prompt consumed
                    live = rec & ~gdrop
                    me = jnp.where(live,
                                   jnp.where(decoding, m, 1), 0)
                    Erow = jnp.where(decoding[:, None], E,
                                     tok_first[:, None])
                    widx = jnp.where(live, req, R)
                    col = jnp.where(jj < me[:, None],
                                    gen[:, None] + jj, max_tokens)
                    out_tok = st["out_tok"].at[
                        widx[:, None], col].set(Erow, mode="drop")
                    nxt = jnp.take_along_axis(
                        Erow, jnp.clip(me - 1, 0, Call - 1)[:, None],
                        axis=1)[:, 0]
                    gen = gen + me
                    fin = live & ((gen >= max_tokens) | (nxt == eos))
                    spec_prop = st["spec_prop"] + jnp.where(
                        decoding & live, c - 1, 0).sum()
                    spec_acc = st["spec_acc"] + jnp.where(
                        decoding & live, acc, 0).sum()
                # first token: a live row that had generated none
                out_first = st["out_first"].at[
                    jnp.where(live & (st["gen"] == 0), req, R)].set(
                        st["step"], mode="drop")
                evict = gdrop | fin
                # drop one reference per table page; a completed reg
                # slot's full-prompt pages keep theirs (it becomes the
                # prefix-cache hold, registered by the host at drain)
                jj2 = jnp.arange(n_ps)[None]
                hold = (st["reg"] & fin)[:, None] & \
                    (jj2 < (plen // page)[:, None])
                dec = evict[:, None] & (st["tbl"] < N) & ~hold
                pref = st["pref"].at[
                    jnp.where(dec, st["tbl"], N)].add(-1, mode="drop")
                fidx = jnp.where(fin, req, R)
                tail = {}
                if share:
                    # latch completion of this slot's queue entry so
                    # in-wave readers admitted later can proceed
                    tail["wdone"] = st["wdone"].at[
                        jnp.where(fin & (st["qidx"] >= 0),
                                  st["qidx"], Nq)].set(True, mode="drop")
                if SK:
                    tail["spec_prop"] = spec_prop
                    tail["spec_acc"] = spec_acc
                return dict(
                    st,
                    pages=pages,
                    pos=pos,
                    free=free | evict,
                    gen=gen,
                    last=jnp.where(live, nxt, st["last"]),
                    tbl=jnp.where(evict[:, None], N, st["tbl"]),
                    pref=pref,
                    out_tok=out_tok,
                    out_first=out_first,
                    out_len=st["out_len"].at[fidx].set(gen, mode="drop"),
                    out_done=st["out_done"].at[fidx].set(True, mode="drop"),
                    out_drop=out_drop,
                    out_tbl=st["out_tbl"].at[fidx].set(
                        st["tbl"], mode="drop"),
                    **tail,
                )

            st = jax.lax.cond(work, decode_and_evict, lambda s: s, st)
            return st, work

        def run_k(params, st, qtok, qlen, qreq, qfeat, qhasf, qsh,
                  qdem, qstart, qcow, qreg, qseed, qwsrc, qwneed,
                  nq, k):
            def cond(carry):
                i, _, alive = carry
                return (i < k) & alive

            def body(carry):
                i, st, _ = carry
                st, alive = one_step(params, qtok, qlen, qreq, qfeat,
                                     qhasf, qsh, qdem, qstart, qcow,
                                     qreg, qseed, qwsrc, qwneed, nq, st)
                return i + 1, st, alive

            _, st, alive = jax.lax.while_loop(
                cond, body, (jnp.int32(0), st, jnp.bool_(True)))
            return st, alive

        return jax.jit(run_k, donate_argnums=(1,))

    # -------------------------------------------------------------- faults
    def _apply_drain_faults(self, st, req_ids, now, step):
        """Failure handling at ONE host drain boundary: poison
        quarantine, deadline eviction, pool-exhaustion holds.

        Mutates only host-rebuildable slot leaves (``free``/``tbl``/
        ``pref``) *between* ``run_k`` calls — the jitted kernel itself
        never sees a fault, so the no-fault path stays byte-identical
        and every run with the same seeded plan replays exactly.
        ``step`` is the absolute device step of the boundary (the
        tracer's step of an eviction).  Returns the (possibly updated)
        state.
        """
        inj = self.injector
        shard = self.trace_shard
        drain = self._drains - 1  # 0-based boundary just completed
        B = self._B
        NP = self.engine.scfg.n_pages if self.paged else 0
        names = ["free", "req", "gen"]
        if self.paged:
            names += ["tbl", "pref"]
        if inj is not None:
            names.append("out_tok")
        host = jax.device_get({k2: st[k2] for k2 in names})
        free = np.asarray(host["free"]).copy()
        req = np.asarray(host["req"])
        gen = np.asarray(host["gen"])
        evict: Dict[int, str] = {}
        if inj is not None:
            out_tok = np.asarray(host["out_tok"]).copy()
            for ev in inj.corruptions(shard, drain):
                b = ev.slot
                if b < B and not free[b] and gen[b] > 0:
                    out_tok[int(req[b]),
                            min(int(gen[b]) - 1,
                                self.max_tokens - 1)] = ev.value
            # per-drain finite check: greedy argmax can never emit
            # outside [0, vocab), so an out-of-range last token marks a
            # poisoned sample — quarantine exactly that slot
            for b in range(B):
                if free[b] or gen[b] == 0:
                    continue
                t = int(out_tok[int(req[b]),
                                min(int(gen[b]) - 1, self.max_tokens - 1)])
                if not 0 <= t < self._vocab:
                    evict[b] = "quarantined"
        if self.deadline:
            for b in range(B):
                if free[b] or b in evict:
                    continue
                qi = int(req[b])
                if qi >= len(req_ids):
                    continue
                dabs = self.deadline.get(req_ids[qi])
                if dabs is not None and now > dabs:
                    evict[b] = "deadline"
        upd: Dict[str, np.ndarray] = {}
        tbl = pref = None
        if evict:
            if self.paged:
                tbl = np.asarray(host["tbl"]).copy()
                pref = np.asarray(host["pref"]).copy()
            for b, reason in evict.items():
                qi = int(req[b])
                rid = req_ids[qi]
                free[b] = True
                if self.paged:
                    valid = tbl[b][tbl[b] < NP]
                    np.subtract.at(pref, valid, 1)
                    tbl[b] = NP
                _drop_request(self, rid, reason, now, step=step)
            upd["free"] = free
            if self.paged:
                upd["tbl"] = tbl
                upd["pref"] = pref
        if self.paged:
            if inj is not None:
                for ev in inj.exhaustions(shard, drain):
                    if pref is None:
                        pref = np.asarray(host["pref"]).copy()
                    held = np.where(pref == 0)[0]
                    pref[held] += 1
                    self._exh_holds.append(
                        [self._drains + ev.hold_drains, held])
            due = [h for h in self._exh_holds if h[0] <= self._drains]
            if due:
                if pref is None:
                    pref = np.asarray(host["pref"]).copy()
                for _, pages in due:
                    pref[pages] -= 1
                self._exh_holds = [h for h in self._exh_holds
                                   if h[0] > self._drains]
            if pref is not None:
                upd["pref"] = pref
        if upd:
            upd2 = {k2: jnp.asarray(v) for k2, v in upd.items()}
            if self.mesh is not None:
                upd2 = jax.device_put(
                    upd2, SH.serve_state_shardings(upd2, self.mesh, B))
            st = dict(st, **upd2)
        return st

    # ----------------------------------------------------------------- run
    def run(self, max_steps: int = 1000) -> dict:
        """Decode until queue + slots drain (or ``max_steps``); returns
        {request_id: tokens}.  Unfinished work survives: in-flight slots
        and un-admitted queue entries resume on the next ``run()``.

        Each phase is a host span on the profiler's clock
        (``jax.profiler.TraceAnnotation``, about a microsecond when no
        profiler runs): ``serve.admit`` (retries, the gate launch,
        admission), ``serve.build`` (page plan, in-wave sharing, the
        numpy arrays), ``serve.upload`` (slot state and queue to the
        device), then per launch ``serve.launch`` (the fused step's
        dispatch), ``serve.sync`` (the done-mask read) and
        ``serve.drain`` (completions and faults; after the last launch
        the outputs, carry-over and re-queue).

        The fused step stamps each output row with the local step at
        which it took a slot (``out_admit``) and yielded its first
        token (``out_first``); they come back in the one read of the
        outputs.  ``admitted_at`` holds the admission step's host time,
        interpolated between the launch and the sync
        (``obs.step_time_interp``); ``first_at`` the time of the sync
        that handed the first token over, as a streaming client would
        see it; ``done_at`` the sync that drained the last.  An attached
        Tracer is fed from the same stamps: the schedule served is the
        same with or without one.
        """
        with jax.profiler.TraceAnnotation("serve.admit"):
            _service_retries(self)
            pending = list(self.queue)
            self.queue.clear()
            carry = [(b, c) for b, c in enumerate(self._carry)
                     if c is not None]
            if not pending and not carry:
                if self._retry_q:
                    # nothing to decode but retries are parked: an empty
                    # run() counts as one drain boundary, so backoff elapses
                    # and deferred entries eventually re-enter the queue
                    self._drains += 1
                    _service_retries(self)
                    pending = list(self.queue)
                    self.queue.clear()
                if not pending:
                    return self.done
            eng = self.engine
            # batched admission: ONE gate launch over the whole waiting queue
            keep = np.ones(len(pending), bool)
            gated = [i for i, (_, _, f) in enumerate(pending) if f is not None]
            if gated and eng.gate_fn is not None and self.pregate:
                keep[gated] = eng.admit(
                    np.stack([pending[i][2] for i in gated]))
            req_ids: List[Any] = [c["rid"] for _, c in carry]
            kept: List[Tuple[Any, list, Optional[np.ndarray]]] = []
            now0 = self._clock() if self.deadline else 0.0
            for k, (rid, prompt, feat) in enumerate(pending):
                dabs = self.deadline.get(rid)
                if dabs is not None and now0 > dabs:
                    # admission-side deadline check: an expired entry never
                    # enters the wave (or reserves pages)
                    _drop_request(self, rid, "deadline", now0)
                    continue
                if not keep[k]:
                    _drop_request(self, rid, "gate-reject")
                    continue
                req_ids.append(rid)
                kept.append((rid, prompt, feat))
            if not req_ids:
                return self.done
        with jax.profiler.TraceAnnotation("serve.build"):
            C, n = len(carry), len(kept)
            n_feat = max(
                [len(f) for _, _, f in kept if f is not None]
                + [len(c["feat"]) for _, c in carry if c["feat"] is not None],
                default=1)
            # pow2 buckets bound jit retraces across queue sizes
            Nq = max(8, 1 << (max(1, n) - 1).bit_length())
            R = max(8, 1 << (C + n - 1).bit_length())
            if self.paged:
                longest = max([len(p) for _, p, _ in kept]
                              + [len(c["prompt"]) for _, c in carry] + [1])
                p_max = max(4, 1 << (longest - 1).bit_length())
                qtok = np.zeros((Nq, p_max), np.int32)
                qlen = np.zeros(Nq, np.int32)
                scfg = eng.scfg
                NP, n_ps = scfg.n_pages, scfg.pages_per_slot
                qsh = np.full((Nq, n_ps), NP, np.int32)
                qdem = np.zeros(Nq, np.int32)
                qstart = np.zeros(Nq, np.int32)
                qcow = np.full(Nq, NP, np.int32)
                qreg = np.zeros(Nq, bool)
                qwsrc = np.full(Nq, -1, np.int32)  # in-wave writer queue idx
                qwneed = np.zeros(Nq, np.int32)  # tokens writer must reach
                self.pool.begin_wave()
            else:
                qtok = np.zeros(Nq, np.int32)
            qreq = np.zeros(Nq, np.int32)
            qseed = np.zeros(Nq, np.int32)
            qfeat = np.zeros((Nq, n_feat), np.int32)
            qhasf = np.zeros(Nq, bool)
            # qi -> (prompt, register-on-completion) for drain registration
            winfo: List[Tuple[list, bool]] = [
                (c["prompt"], c.get("reg", False)) if self.paged
                else ([], False)
                for _, c in carry]
            wplans: List = []  # kept-index -> PagePlan (stats at drain)
            for k, (rid, prompt, f) in enumerate(kept):
                qseed[k] = self.seeds.get(rid, _default_seed(rid))
                if self.paged:
                    qtok[k, : len(prompt)] = prompt
                    qlen[k] = len(prompt)
                    # prefix-trie plan: shared prefix pages, start offset,
                    # COW source, own-page demand, cache-hold budget verdict
                    plan = self.pool.plan(prompt, self.max_tokens)
                    qsh[k, : len(plan.shared)] = plan.shared
                    qdem[k] = plan.own
                    qstart[k] = plan.start
                    if plan.cow_src is not None:
                        qcow[k] = plan.cow_src
                    qreg[k] = plan.reg
                    winfo.append((prompt, plan.reg))
                    wplans.append(plan)
                else:
                    winfo.append(([], False))
                    qtok[k] = prompt[0]
                qreq[k] = C + k  # output row: carryover rows come first
                if f is not None:
                    qfeat[k, : len(f)] = f[:n_feat]
                    qhasf[k] = True
            wave_pins: List[int] = []  # host pins on in-wave shared pages
            wave_deps = False  # any reader waiting on an in-wave writer?
            if self.paged and eng.scfg.share_prefix:
                # pressure-release cached prefixes (LRU leaf-first) so the
                # wave's largest own-demand can eventually be met; pages the
                # wave itself shares are pinned
                keep_pin = set(int(p) for p in qsh[qsh < NP])
                keep_pin |= set(int(p) for p in qcow[qcow < NP])
                self.pool.ensure_free(int(qdem.max(initial=0)), keep_pin)
                # --- in-wave prefix sharing: cold entries (no cache
                # hit) of THIS wave with identical full-page prefixes
                # share pages from wave 0 instead of only benefiting
                # after one of them completes and registers.  The first
                # entry owning a prefix node WRITES it during prefill;
                # later entries READ it (their fused-step admission
                # waits until the writer's position covers the read
                # chain).
                page = eng.scfg.page_size
                cold = [k for k in range(n)
                        if qstart[k] == 0 and qcow[k] == NP
                        and bool((qsh[k] >= NP).all())
                        and len(kept[k][1]) >= page]
                counts: Dict[tuple, int] = {}
                keys_of: Dict[int, list] = {}
                for k in cold:
                    prompt = kept[k][1]
                    # node depths mirror pool._lookup: a shared page
                    # must not cover the final prompt token (the last
                    # token's KV is written at first decode)
                    keys = [tuple(prompt[: (d + 1) * page])
                            for d in range(len(prompt))
                            if (d + 1) * page <= len(prompt) - 1]
                    keys_of[k] = keys
                    for key2 in keys:
                        counts[key2] = counts.get(key2, 0) + 1
                owner: Dict[tuple, int] = {}
                claims: list = []  # node keys in claim (alloc) order
                plan_sh: Dict[int, Tuple[int, int, int]] = {}
                for k in cold:
                    keys = [k2 for k2 in keys_of[k] if counts[k2] >= 2]
                    if not keys:
                        continue
                    # nodes already owned by an earlier entry form a
                    # contiguous prefix of this chain (sharing a depth-d
                    # prefix implies sharing every shallower one)
                    read_k, wsrc = 0, -1
                    for key2 in keys:
                        if key2 not in owner:
                            break
                        read_k += 1
                        wsrc = owner[key2]
                    for key2 in keys[read_k:]:
                        owner[key2] = k
                        claims.append(key2)
                    plan_sh[k] = (read_k, len(keys), wsrc)
                free_ids = np.where(self.pool.ref == 0)[0]
                # conservative capacity check against the ORIGINAL
                # demand: the kernel must still be able to admit the
                # hungriest entry after the node pages are pinned
                if plan_sh and len(free_ids) >= (
                        len(claims) + int(qdem.max(initial=0))):
                    node_page: Dict[tuple, int] = {}
                    for i2, key2 in enumerate(claims):
                        pid = int(free_ids[i2])
                        node_page[key2] = pid
                        self.pool.ref[pid] += 1  # released at drain
                        wave_pins.append(pid)
                    for k, (read_k, nsh_k, wsrc) in plan_sh.items():
                        chain = [node_page[k2]
                                 for k2 in keys_of[k][:nsh_k]]
                        qsh[k, :] = NP
                        qsh[k, : len(chain)] = chain
                        qdem[k] -= nsh_k
                        qstart[k] = read_k * page
                        qwsrc[k] = wsrc
                        qwneed[k] = read_k * page
                        if read_k:
                            wave_deps = True
                        wplans[k] = dataclasses.replace(
                            wplans[k], shared=chain,
                            start=int(qstart[k]), own=int(qdem[k]))

            B = self._B
            free = np.ones(B, bool)
            req = np.full(B, R, np.int32)
            gen = np.zeros(B, np.int32)
            last = np.zeros(B, np.int32)
            feat = np.zeros((B, n_feat), np.int32)
            hasf = np.zeros(B, bool)
            seed = np.zeros(B, np.int32)
            out_tok = np.zeros((R, self.max_tokens), np.int32)
            if self.paged:
                scfg = eng.scfg
                pos = np.zeros(B, np.int32)
                plen = np.zeros(B, np.int32)
                pbuf = np.zeros((B, p_max), np.int32)
                tbl = np.full((B, scfg.pages_per_slot), scfg.n_pages, np.int32)
                reg = np.zeros(B, bool)
            for row, (b, c) in enumerate(carry):  # resume in-flight slots
                free[b] = False
                req[b] = row
                gen[b] = c["gen"]
                last[b] = c["last"]
                hasf[b] = c["hasf"]
                seed[b] = c.get("seed", _default_seed(c["rid"]))
                if c["feat"] is not None:
                    feat[b, : len(c["feat"])] = c["feat"][:n_feat]
                out_tok[row, : c["gen"]] = c["toks"]
                if self.paged:
                    pos[b] = c["pos"]
                    plen[b] = len(c["prompt"])
                    pbuf[b, : len(c["prompt"])] = c["prompt"]
                    tbl[b] = c["tbl"]
                    reg[b] = c.get("reg", False)
        with jax.profiler.TraceAnnotation("serve.upload"):
            st = {
                "free": jnp.asarray(free),
                "req": jnp.asarray(req),
                "gen": jnp.asarray(gen),
                "last": jnp.asarray(last),
                "feat": jnp.asarray(feat),
                "hasf": jnp.asarray(hasf),
                "seed": jnp.asarray(seed),
                "head": jnp.int32(0),
                "out_tok": jnp.asarray(out_tok),
                "out_len": jnp.zeros(R, jnp.int32),
                "out_done": jnp.zeros(R, bool),
                "out_drop": jnp.zeros(R, bool),
                # step stamps (0 = not in this call)
                "step": jnp.int32(0),
                "out_admit": jnp.zeros(R, jnp.int32),
                "out_first": jnp.zeros(R, jnp.int32),
            }
            if self.paged:
                st.update(
                    pages=self._pages,
                    pos=jnp.asarray(pos),
                    plen=jnp.asarray(plen),
                    pbuf=jnp.asarray(pbuf),
                    tbl=jnp.asarray(tbl),
                    reg=jnp.asarray(reg),
                    pref=jnp.asarray(self.pool.ref),
                    out_tbl=jnp.full((R, scfg.pages_per_slot), scfg.n_pages,
                                     jnp.int32),
                )
                if scfg.share_prefix:
                    # carried slots' queue entries are gone: qidx = -1
                    st["qidx"] = jnp.full(B, -1, jnp.int32)
                    st["wdone"] = jnp.zeros(Nq, bool)
                if self.spec_k:
                    st["spec_prop"] = jnp.int32(0)
                    st["spec_acc"] = jnp.int32(0)
                args = (jnp.asarray(qtok), jnp.asarray(qlen),
                        jnp.asarray(qreq), jnp.asarray(qfeat),
                        jnp.asarray(qhasf), jnp.asarray(qsh),
                        jnp.asarray(qdem), jnp.asarray(qstart),
                        jnp.asarray(qcow), jnp.asarray(qreg),
                        jnp.asarray(qseed), jnp.asarray(qwsrc),
                        jnp.asarray(qwneed), jnp.int32(n))
            else:
                st["decode"] = self._decode
                args = (jnp.asarray(qtok), jnp.asarray(qreq),
                        jnp.asarray(qfeat), jnp.asarray(qhasf),
                        jnp.asarray(qseed), jnp.int32(n))
            if self.mesh is not None:
                # place the donated slot pytree (decode cache per cache_pspec
                # or page pool per paged_cache_pspec, slot arrays over data,
                # rings replicated for the host drain) and the device FIFO
                # queue; every subsequent run_k call then computes under
                # GSPMD on the mesh
                from jax.sharding import NamedSharding

                st = jax.device_put(
                    st, SH.serve_state_shardings(st, self.mesh, B))
                args = tuple(
                    jax.device_put(a, NamedSharding(
                        self.mesh, SH.queue_pspec(self.mesh, Nq, a.ndim)))
                    for a in args[:-1]) + args[-1:]
            if self.paged:
                key: Tuple = (Nq, R, n_feat, p_max)
                if key not in self._run_k:
                    self._run_k[key] = self._make_run_k_paged(
                        Nq, R, n_feat, p_max)
            else:
                key = (Nq, R, n_feat)
                if key not in self._run_k:
                    self._run_k[key] = self._make_run_k(Nq, R, n_feat)
            run_k = self._run_k[key]

        inj = self.injector
        base = self._steps_total  # absolute step of this call's step 0
        seen = np.zeros(R, bool)
        seen_step = np.zeros(R, np.int64)  # boundary that drained a row
        remaining = max_steps
        alive = True
        steps_run = 0
        # (local step, host time) at the launch and at each sync: the
        # stamps' steps map to host times between them
        boundaries = [(0, self._clock())]
        while remaining > 0:
            k = min(self.sync_every, remaining)
            with jax.profiler.TraceAnnotation("serve.launch"):
                st, alive = run_k(eng.params, st, *args, jnp.int32(k))
            with jax.profiler.TraceAnnotation("serve.sync"):
                # drain every K: the done mask and the alive flag
                done_mask, alive = jax.device_get((st["out_done"], alive))
            now = self._clock()
            with jax.profiler.TraceAnnotation("serve.drain"):
                # nominal cumulative count — only the final trip can
                # exit early; the stamps' step counter clamps it below
                steps_run += k
                boundaries.append((steps_run, now))
                remaining -= k
                fresh = done_mask & ~seen
                for qi in np.where(fresh)[0]:
                    self.done_at[req_ids[qi]] = now
                    self.deadline.pop(req_ids[qi], None)
                    if self.tracer is not None:
                        # the same `now` as done_at: drain timestamps
                        # and tracer spans agree exactly
                        self.tracer.drained(req_ids[qi], t=now)
                seen_step[fresh] = steps_run
                seen = done_mask
                self._drains += 1
                # the fault path is ENTIRELY gated: with no injector, no
                # deadline and no standing exhaust hold, the drive loop
                # is the exact pre-fault byte sequence (failure is free
                # when nothing fails)
                ft = (bool(self.deadline) or bool(self._exh_holds)
                      or (inj is not None
                          and inj.pending_for(self.trace_shard)))
                if ft:
                    st = self._apply_drain_faults(st, req_ids, now,
                                                  base + steps_run)
            if not bool(alive):
                break
        with jax.profiler.TraceAnnotation("serve.drain"):
            replan = self._drain(st, bool(alive), carry, kept, req_ids,
                                 winfo, wplans, wave_pins, base,
                                 boundaries, seen, seen_step)
        if (replan and wave_deps and not bool(alive)
                and remaining > 0 and self.queue):
            # in-wave readers were left waiting on a writer that died
            # (gate drop / fault eviction): re-plan them cold — their
            # next wave sees the writer gone and shares among survivors
            return self.run(remaining)
        return self.done

    def _drain(self, st, alive: bool, carry, kept, req_ids, winfo, wplans,
               wave_pins, base: int, boundaries, seen, seen_step) -> bool:
        """After a call's last launch: ONE read of the outputs, the
        stamps and (if the step is still alive) the slots to carry;
        completions, stamps, the pool mirror, carry-over and re-queue.
        Returns whether any queue entry was admitted (``head > 0``)."""
        eng = self.engine
        C, n = len(carry), len(kept)
        names = ["out_tok", "out_len", "out_drop", "out_admit",
                 "out_first", "step", "head"]
        if self.paged:
            names += ["pref", "out_tbl"]
            if self.spec_k:
                names += ["spec_prop", "spec_acc"]
        slot_names = ["free", "req", "gen", "last", "feat", "hasf", "seed"]
        if self.paged:
            slot_names += ["pos", "plen", "pbuf", "tbl", "reg"]
        if alive:
            names += slot_names
        host = jax.device_get({k2: st[k2] for k2 in names})
        head = int(host["head"])
        if self.paged:
            self._pages = st["pages"]
            self.pool.ref[:] = host["pref"]
            if wave_pins:
                # drop the host pins on in-wave shared node pages (live
                # readers/writers still hold their fill-side refs; a
                # fully-drained chain frees here)
                np.subtract.at(self.pool.ref, np.asarray(wave_pins), 1)
            if self.spec_k:
                self._spec_prop += int(host["spec_prop"])
                self._spec_acc += int(host["spec_acc"])
            if self._exh_holds:
                # phantom holds never outlive the run: the host mirror
                # must agree with live reservations + cache holds
                for _, pages in self._exh_holds:
                    self.pool.ref[pages] -= 1
                self._exh_holds = []
            self.pool.observe_occupancy()
            # sharing stats: count exactly the entries the step admitted
            # this run (head = queue entries consumed); re-enqueued
            # entries are re-planned — and re-counted — only once they
            # actually land in a slot on a later run
            for k in range(min(head, n)):
                self.pool.record_plan(wplans[k], len(kept[k][1]))
        else:
            self._decode = st["decode"]
        out_tok, out_len = host["out_tok"], host["out_len"]
        out_drop = host["out_drop"]
        self._stamp(host, carry, req_ids, base, boundaries, seen,
                    seen_step)
        for qi in range(C + n):
            if seen[qi]:
                self.done[req_ids[qi]] = [
                    int(t) for t in out_tok[qi, : out_len[qi]]]
                if self.paged and winfo[qi][1]:
                    # the fused step kept one reference on this slot's
                    # full-prompt pages at eviction; hand them to the
                    # prefix trie (duplicates release the extra hold)
                    prompt = winfo[qi][0]
                    nfp = len(prompt) // eng.scfg.page_size
                    self.pool.register_completed(
                        prompt, [int(p) for p in host["out_tbl"][qi][:nfp]])
            elif out_drop[qi]:
                # the tracer's event is emitted at the admission stamp
                _drop_request(self, req_ids[qi], "gate-reject",
                              trace=False)
        # carry in-flight slots + re-enqueue un-admitted entries so a
        # later run() resumes the exact schedule (host-batcher semantics)
        B = self._B
        self._carry = [None] * B
        if alive:
            s = {k2: host[k2] for k2 in slot_names}
            for b in range(B):
                if s["free"][b]:
                    continue
                qi = int(s["req"][b])
                g = int(s["gen"][b])
                self._carry[b] = dict(
                    rid=req_ids[qi], gen=g, last=int(s["last"][b]),
                    hasf=bool(s["hasf"][b]),
                    feat=s["feat"][b].copy() if s["hasf"][b] else None,
                    seed=int(s["seed"][b]),
                    toks=out_tok[qi, :g].copy())
                if self.paged:
                    self._carry[b].update(
                        pos=int(s["pos"][b]),
                        prompt=[int(t)
                                for t in s["pbuf"][b, : s["plen"][b]]],
                        tbl=s["tbl"][b].copy(),
                        reg=bool(s["reg"][b]))
        # re-enqueue un-admitted entries regardless of the alive flag:
        # with in-wave sharing a reader blocked on a dead writer idles
        # the kernel out (alive False) while its entry is still pending
        for rid, prompt, f in reversed(kept[head:]):
            self.queue.appendleft((rid, prompt, f))
        return head > 0

    def _stamp(self, host, carry, req_ids, base: int, boundaries, seen,
               seen_step) -> None:
        """Host times of the step stamps (``admitted_at``, ``first_at``)
        and, with a Tracer attached, its lifecycle events at the stamps'
        absolute steps (emission deferred to the tracer's first read).

        The done step is ``out_first + out_len - 1`` (a carried row that
        was already generating: ``out_len - gen``), never past the sync
        that drained it: with speculation a step can emit several
        tokens, so there it is an upper bound."""
        C = len(carry)
        rows = len(req_ids)
        actual = int(host["step"])  # steps the call executed
        if boundaries[-1][0] > actual:
            boundaries[-1] = (max(actual, boundaries[-2][0]),
                              boundaries[-1][1])
        self._steps_total = base + actual
        interp = step_time_interp(boundaries)
        sync_steps = np.array([s for s, _ in boundaries[1:]])
        adm = host["out_admit"][:rows]
        fst = host["out_first"][:rows]
        for qi in np.nonzero(adm)[0]:
            self.admitted_at[req_ids[qi]] = interp(int(adm[qi]))
        for qi in np.nonzero(fst)[0]:
            j = min(int(np.searchsorted(sync_steps, fst[qi])),
                    len(sync_steps) - 1)
            self.first_at[req_ids[qi]] = boundaries[j + 1][1]
        if self.tracer is None:
            return
        tracer, shard = self.tracer, self.trace_shard
        rids = list(req_ids)
        gen0 = [int(c["gen"]) for _, c in carry]
        out_len, out_drop = host["out_len"], host["out_drop"]
        seen, seen_step = seen.copy(), seen_step.copy()

        def emit():
            for qi in range(rows):
                rid = rids[qi]
                a, f = int(adm[qi]), int(fst[qi])
                if a:
                    tracer.admitted(rid, t=interp(a), step=base + a,
                                    shard=shard)
                if out_drop[qi] and not seen[qi]:
                    # the in-step gate verdict evicts on the admit step
                    d = a or 1
                    tracer.dropped(rid, "gate-reject", t=interp(d),
                                   step=base + d)
                    continue
                if f:
                    tracer.first_token(rid, t=interp(f), step=base + f)
                if seen[qi]:
                    n_tok = int(out_len[qi])
                    d = (f + n_tok - 1 if f else
                         n_tok - (gen0[qi] if qi < C else 0))
                    d = max(min(d, int(seen_step[qi]), actual), f, 1)
                    tracer.finished(rid, n_tokens=n_tok, t=interp(d),
                                    step=base + d)

        # the per-request emission runs at export time, not on the
        # serve path
        tracer.defer(emit)
