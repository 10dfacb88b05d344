"""Batched W4A8 serving loop over a quantized paged KV-cache pool.

Serving model: ``Server`` owns `slots` concurrent sequences (slot = batch
row). Requests join free slots; each engine step decodes one token for every
active slot. Prefill for a new request runs row-wise (batch-1) and is
*streamed into pages*: the prompt is fed through the model in page-aligned
chunks and each chunk's K/V is quantized straight into the pool
(runtime.kv_cache.append_prefill_chunk), so the engine never holds a
monolithic (slots, max_seq, ...) cache — nor even a transient per-request
max_seq scratch. This is the scheduling skeleton of a vLLM-style paged
engine adapted to fixed-shape jit programs (page table and per-slot lengths
are jit *inputs*; shapes never change -> one compiled decode step).

The paged pool is the *single* decode path — every model family runs on it:

  * decoder-only transformers (GQA and MLA attention, dense or MoE) keep
    per-layer K/V (or compressed latent) pages; MLA decode runs entirely
    inside the latent flash-decoding kernel (ops.paged_mla_decode_attn).
  * enc-dec (Whisper-style) decoders add *write-once cross pages*: the
    encoder runs once at admission, every decoder layer's cross K/V is
    quantized into immutable pages (kv_cache.write_cross_pages), and
    admission charges ``pages(prompt) + pages(encoder_seq)`` from the same
    free list.
  * recurrent families (SSM / xLSTM, and the Zamba2 hybrid's Mamba2
    backbone) hold their fixed-size decode state in *state slabs*: one
    slab per running request, allocated at admission, steal/spill-able
    exactly like pages — just never grown. The hybrid's shared-attention
    KV rides an ordinary page pool with the invocation index as the
    layer axis.

Scheduling (``scheduler`` knob):
  * ``"token_budget"`` (default): admission charges only the prompt's pages
    plus ``headroom_pages`` of decode headroom (plus the encoder pages /
    one slab where the family needs them); every step allocates pages on
    demand as rows cross page boundaries. On pool exhaustion the scheduler
    preempts the lowest-priority running request by *stealing its pages*
    (and slab): the victim's payload (codes + scales + recurrent state,
    all layers) is spilled to host memory and its pages returned to the
    pool, so it resumes token-identically — bit-identical contents are
    restored into whatever pages are free — once capacity returns.
    Watermarks and a steal cooldown give anti-thrash hysteresis;
    readmission is one global longest-waiting-first wait line over spilled
    *and* fresh requests, keyed by (step entered the line, arrival seq) —
    the head of the line is never overtaken while it does not fit, and a
    budget-evicted spill keeps the place it already earned. Host spill
    residency is bounded by
    ``spill_budget_bytes``: when exceeded, the oldest spill is *evicted* —
    its request re-queues at the head of the line and re-prefills its full
    context instead of restoring bytes (host memory can no longer OOM on
    pathological steal storms).
  * ``"reserve"``: the legacy reserve-on-admit policy — worst-case pages
    (prompt + max_new) are reserved up front, so admitted requests never
    stall but slot utilization collapses under long-tail ``max_new``. Kept
    as the serving benchmark's baseline.

Streaming-prefill chunks are *bucketed*: chunk lengths and page-table
widths are padded to powers of two (pad tokens masked everywhere — page
writes, attention, logits row), so a high-entropy prompt-length workload
compiles O(log max_seq) prefill programs instead of one per distinct
(chunk_len, table_width) pair. Families with recurrent state stream exact
chunks instead (pad tokens cannot be masked out of a recurrence's carry).

``ServerConfig.cache`` (a :class:`runtime.kv_cache.CachePolicy`) selects
the page payload *per page class*: ``active_fmt`` for every page a write
path can still touch ("fp8_e4m3" packed FP8 codes with per-(page, head) M2
scales ~0.52x the bytes of bf16, or None for bf16 pages), ``frozen_fmt``
for prefix-cache-registered pages (``"fp4_e2m1"`` transcodes each page
FP8 -> packed FP4 exactly once at the freeze point, halving frozen-page
bytes again), and ``cross_fmt`` for write-once enc-dec cross pages. The
flat ``kv_fmt`` string knob still maps onto
``CachePolicy(active_fmt=...)`` through a DeprecationWarning shim. Every
format runs the same paged decode attention with per-slot *true*
lengths — rows carry their own positions and length masks end to end;
in a mixed-precision pool, page-table entries ``>= n_pages + 1`` address
the packed FP4 frozen region and the kernels select the decode format
per page by id class.

Page ownership is **refcounted**, and full scale-frozen prompt pages are
**content-addressable** (``prefix_cache=True``, pure page families only):
once the prefill stream passes a page its per-(page, head) M2 scales are
frozen at amax and the codes never requantize again, so the page content
is a pure function of its token-id prefix. A host-side radix index
(runtime.kv_cache.PrefixCache) registers every full prompt page after its
prefill; admission walks a request's prompt through the index and maps
every hit straight into the slot's page table (refcount++, zero prefill
compute), streaming only the uncached tail through the prefill. The
boundary page is always private — only *full* pages are ever shared — so
the decode append's in-place requantize can never touch a shared page:
copy-on-write falls out structurally. Pages whose refcount drops to zero
park in an LRU reusable set that the allocator reclaims *before* any live
request is stolen from; preemption spills only privately-owned payload and
re-resolves the shared prefix through the index on resume (falling back to
a tail re-prefill when the cached pages were reclaimed meanwhile).
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import functools
import hashlib
import math
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import models
from repro.models.transformer import segments_for
from repro.runtime import kv_cache as kvc
from repro.runtime import sampling as smp
from repro.runtime.faults import (FaultPlan, PoolCorruptionError,
                                  ServingError)
from repro.runtime.kv_cache import CachePolicy
from repro.runtime.sampling import SamplingParams

__all__ = ["Request", "RequestResult", "TokenEvent", "Server",
           "ServerConfig", "SchedulerConfig", "MeshPlan", "CachePolicy",
           "SamplingParams", "FaultPlan", "PoolCorruptionError",
           "ServingError"]


def _decode_step(params, caches, tokens, cache_index, poison, samp,
                 cfg, a_fmt, backend):
    """The engine step, as a plain traceable function. ``_decode_step_jit``
    below is the shared single-device jit of it; a mesh-driving Server
    jits the SAME function with ``out_shardings`` pinning the cache
    outputs to its canonical per-mesh-axis pool layouts (placement can
    never drift step to step, so the fixed-trace property holds on a
    mesh exactly as it does on one device).

    The layer scan carries the paged pool whole and updates it in place;
    the step does not donate ``caches``, so XLA copies the pool once at the
    step's entry into the buffer it returns, and a reference taken before
    the step still reads the pool as it was.

    ``cfg`` is a frozen (hashable) ArchConfig, so the compiled program
    cache is shared across Server instances — a restarted or side-by-side
    server reuses every prefill-chunk and decode executable instead of
    recompiling. ``backend`` (the kernel backend the step traces under)
    is static too: servers on different backends never share a program.

    Returns ``(nxt, row_ok, caches)``: ``nxt`` is the per-row next token
    — sampled in-graph from the logits by ``samp``, a 5-tuple of per-row
    arrays (temperature, top_k, top_p, seed, emitted-count; see
    runtime.sampling). Greedy rows (temperature 0) take the argmax, so
    the pre-sampling engine's output is reproduced bit-exactly; all of
    it is fixed-trace — sampling params are jit *inputs*, never retrace
    keys. ``row_ok`` is the per-row isfinite sentinel — True iff every
    logit in the row is finite — and is the engine's detection path for
    FP8's operational sharp edge (a NaN code point or overflow
    saturating through the cache poisons the row's logits). ``poison``
    is a per-row bool *input* (no retrace): fault injection sets it to
    force NaN upstream of the sentinel, so chaos tests exercise the same
    detection path production does."""
    with _backend_scope(backend):
        logits, caches = models.decode_step(params, cfg, tokens, caches,
                                            cache_index, a_fmt=a_fmt)
    logits = jnp.where(poison[:, None], jnp.float32(jnp.nan), logits)
    row_ok = jnp.all(jnp.isfinite(logits), axis=-1)
    with jax.named_scope("sample"):
        nxt = smp.sample_tokens(logits, *samp)
    return nxt, row_ok, caches


_STEP_STATIC = ("cfg", "a_fmt", "backend")
_decode_step_jit = jax.jit(_decode_step, static_argnames=_STEP_STATIC)


@functools.partial(jax.jit, static_argnames=_STEP_STATIC)
def _encode_cross_jit(params, frames, caches, cross_table, cfg, a_fmt,
                      backend):
    """Enc-dec admission step: encoder forward + write-once cross pages."""
    with _backend_scope(backend):
        return models.encode_cross_pages(params, cfg, frames, caches,
                                         cross_table, a_fmt=a_fmt)


def _span(name: str):
    """A host span of the engine step (``serve.*``) on the profiler's
    clock. While no trace is captured it is an object that costs about a
    microsecond and records nothing."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def _backend_scope(name: str):
    """Select a kernel backend for one trace. Keeps a Server's backend
    choice scoped to its own step programs instead of leaking into every
    other model in the process."""
    from repro.kernels import ops as _kops

    prev = _kops.get_backend()
    _kops.set_backend(name)
    try:
        yield
    finally:
        _kops.set_backend(prev)


def _migrate_legacy_kwarg(message: str, *, conflict: Optional[str] = None,
                          stacklevel: int = 3):
    """One shim for every legacy->current config-migration spelling
    (``kv_fmt`` -> ``CachePolicy``, flat ``Server(...)`` kwargs ->
    ``ServerConfig``): raise ``TypeError`` with ``conflict`` when the
    caller mixed the old and new spellings, else emit the
    ``DeprecationWarning`` and let the caller normalize the value.
    ``stacklevel`` points the warning at the deprecated call site."""
    if conflict is not None:
        raise TypeError(conflict)
    warnings.warn(message, DeprecationWarning, stacklevel=stacklevel)


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _is_hybrid(cfg) -> bool:
    return (cfg.ssm is not None and cfg.ssm.kind == "mamba2"
            and cfg.family == "hybrid")


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Admission/preemption policy knobs (see the module docstring).

    ``policy``: ``"token_budget"`` (default — prompt pages + headroom at
    admission, on-demand growth, preemption by page steal) or
    ``"reserve"`` (legacy worst-case reserve-on-admit; the serving
    benchmark's baseline). The remaining knobs only act under
    ``token_budget``:
      * ``headroom_pages``: decode headroom charged at admission on top
        of the prompt's pages — the first page boundary never stalls.
      * ``low_watermark``: pages that must stay free *after* admitting
        fresh work while other requests run (growth slack; hysteresis
        against admit-then-steal thrash).
      * ``resume_watermark``: extra free pages, beyond the spilled
        context, required to resume a preempted request while other
        requests run (hysteresis against steal/resume ping-pong).
      * ``steal_cooldown``: steps a freshly admitted/resumed request is
        protected from preemption (unless no other victim exists).
      * ``prefill_chunk_pages``: streaming-prefill chunk size, in pages.
      * ``spill_budget_bytes``: cap on host bytes held by spills; on
        overflow the oldest spill is evicted and its request re-queued
        for a full re-prefill (None = unbounded).
    Both watermarks are bypassed when nothing is running — the pool is
    then fully available, so progress is always made when physically
    possible.

    ``engine`` selects the step architecture (orthogonal to ``policy``):
      * ``"mixed"`` (default) — every ``Server.step()`` carries all
        active decode rows *plus* up to ``prefill_token_budget`` tokens
        of one request's next prefill chunk, fused into a single jitted
        program: decode never stalls while a prompt streams in.
        Families the fusion does not apply to (recurrent/slab, enc-dec,
        multi-device meshes) fall back to alternating automatically —
        ``Server.engine`` reports the resolved choice.
      * ``"alternating"`` — the legacy shape: whole prompts stream at
        admission (serial chunk steps), decode steps carry decode rows
        only. Kept as the bench baseline and the fallback target.
    ``prefill_token_budget`` is the per-step prefill chunk size in
    *tokens* for both engines — the mixed step's piggyback cap and the
    alternating stream's chunk length (None = ``prefill_chunk_pages``
    worth), so the engines stay chunk-for-chunk comparable. It is
    rounded down to a page multiple (min one page) so chunk starts stay
    page-aligned — the ``append_prefill_chunk`` contract."""

    policy: str = "token_budget"
    headroom_pages: int = 1
    low_watermark: int = 0
    resume_watermark: int = 1
    steal_cooldown: int = 2
    prefill_chunk_pages: int = 4
    spill_budget_bytes: Optional[int] = None
    engine: str = "mixed"
    prefill_token_budget: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Device-mesh layout for one serving engine: a ('data', 'model')
    ``jax.sharding.Mesh`` over ``data * model`` local devices, plus the
    per-mesh-axis layout every pool leaf and weight shard follows:

      * GQA KV pages, their per-(page, head) scales and the decode
        attention shard by KV head along 'model' (head counts are
        asserted divisible at Server construction);
      * MLA latent pages replicate (no head axis) while the absorbed
        query heads shard along 'model';
      * MoE decode routes expert-parallel (expert-stacked W4A8 weights
        sharded over the mesh, partial outputs all-reduced);
      * W4A8 weight shards are placed by ``launch.sharding.serve_rules``.

    The host-side scheduler stays a single brain above all of it: page
    tables, refcounts, the prefix radix index, spill CRCs and ``audit()``
    are host-global, and spill/restore gathers/scatters per shard
    implicitly (``np.asarray`` of a sharded leaf is the global array).

    ``total == 1`` (the default, and ``ServerConfig.mesh=None``) keeps
    the single-device engine byte-for-byte: no Mesh is ever built and
    every code path is exactly the pre-mesh one (asserted by tests)."""

    data: int = 1
    model: int = 1

    def __post_init__(self):
        if self.data < 1 or self.model < 1:
            raise ValueError(
                f"MeshPlan axes must be >= 1, got data={self.data} "
                f"model={self.model}")

    @property
    def total(self) -> int:
        return self.data * self.model

    def build(self):
        """Build the ('data', 'model') Mesh over the first ``total``
        local devices (CPU CI simulates them via
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
        from repro.launch.mesh import make_mesh

        ndev = len(jax.devices())
        if self.total > ndev:
            raise ValueError(
                f"MeshPlan(data={self.data}, model={self.model}) needs "
                f"{self.total} devices but only {ndev} are visible (set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "before importing jax to simulate a CPU mesh)")
        return make_mesh((self.data, self.model), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Frozen Server construction spec (replaces the old 19-kwarg flat
    ``Server.__init__``; those kwargs still map here through a
    ``DeprecationWarning`` shim).

    ``kernel_backend``: 'pallas' routes every PackedLinear matmul in
    prefill/decode through the fused single-pass W4A8 kernel, and paged
    decode attention (GQA and MLA-latent) through the flash-decoding
    page-gather kernels; 'ref' forces the jnp oracles; None keeps the
    process-wide setting.

    ``cache``: a :class:`runtime.kv_cache.CachePolicy` — the KV-cache
    precision policy, per page class: ``active_fmt`` for writable pages
    ('fp8_e4m3' packed codes + per-(page, head) M2 scales, or None for
    bf16), ``frozen_fmt`` for prefix-cache-registered pages
    ('fp4_e2m1' transcodes each page to packed FP4 exactly once at
    freeze time), ``cross_fmt`` for write-once enc-dec cross pages and
    ``frozen_pages`` sizing the dedicated frozen region. Recurrent
    state slabs always hold exact f32 state regardless.

    ``kv_fmt``: DEPRECATED — the old flat payload string. Still
    accepted (with a ``DeprecationWarning``) and normalized onto
    ``cache=CachePolicy(active_fmt=kv_fmt)``; mixing it with an
    explicit non-default ``cache`` raises ``TypeError``.

    ``page_size``: tokens per page. ``pool_pages``: pool capacity in
    pages (default: full backing — slots * pages per slot, plus the
    encoder pages for enc-dec). ``pool_slabs``: state slabs for
    recurrent families (default: one per slot — full backing).

    ``scheduler``: a nested :class:`SchedulerConfig`.

    ``mesh``: a nested :class:`MeshPlan` — None (or a 1-device plan)
    keeps today's single-device engine byte-for-byte; a larger plan
    makes this Server drive a ('data', 'model') device mesh with KV
    pages/decode attention sharded by head, MLA latents replicated,
    MoE decode expert-parallel and weights placed by ``serve_rules``
    (pure page families only: GQA/MLA decoders, no enc-dec cross pages
    or recurrent state slabs).

    ``prefix_cache``: content-addressed sharing of full, scale-frozen
    prompt pages across requests (refcounted pages + host-side radix
    index; see the module docstring). Active only for pure page
    families: enc-dec decoder K/V depends on the encoder frames, not
    just the token prefix, and recurrent families cannot skip a prefill
    chunk — both fall back to exclusive prefills automatically.

    Failure semantics (see runtime/README.md):
      * ``strict=True`` (default): ``run_until_drained`` raises
        ``ServingError`` on starvation — fail-fast for tests/bench.
        ``strict=False`` degrades per request instead: permanently
        unadmittable work retires with ``status='failed'`` and the
        drain completes (production mode: one oversized or starved
        request never takes the batch down).
      * ``audit_every=N``: every N decode steps, run the full pool
        ownership audit (``Server.audit()``) in-line and raise
        ``PoolCorruptionError`` on any violation (0 = off)."""

    slots: int = 4
    max_seq: int = 512
    a_fmt: Optional[str] = "fp8_e4m3"
    kernel_backend: Optional[str] = None
    cache: CachePolicy = CachePolicy()
    kv_fmt: Optional[str] = None  # deprecated -> cache=CachePolicy(active_fmt=)
    page_size: int = 64
    pool_pages: Optional[int] = None
    pool_slabs: Optional[int] = None
    scheduler: SchedulerConfig = SchedulerConfig()
    mesh: Optional[MeshPlan] = None
    prefix_cache: bool = True
    strict: bool = True
    audit_every: int = 0

    def __post_init__(self):
        if self.kv_fmt is None:
            return
        _migrate_legacy_kwarg(
            "ServerConfig(kv_fmt=...) is deprecated; pass "
            "ServerConfig(cache=CachePolicy(active_fmt=...))",
            conflict=("pass either cache=CachePolicy(...) or the "
                      "deprecated kv_fmt=..., not both")
            if self.cache != CachePolicy() else None,
            stacklevel=4)
        # normalize so ServerConfig(kv_fmt=f) == ServerConfig(
        # cache=CachePolicy(active_fmt=f)) — the shimmed spelling is
        # indistinguishable downstream (token-identical serving)
        object.__setattr__(self, "cache",
                           CachePolicy(active_fmt=self.kv_fmt))
        object.__setattr__(self, "kv_fmt", None)


# legacy flat-kwarg -> config-field mapping for the deprecation shim
_LEGACY_SCHED_KW = ("headroom_pages", "low_watermark", "resume_watermark",
                    "steal_cooldown", "prefill_chunk_pages",
                    "spill_budget_bytes")
_LEGACY_TOP_KW = ("slots", "max_seq", "a_fmt", "kernel_backend", "kv_fmt",
                  "page_size", "pool_pages", "pool_slabs", "prefix_cache",
                  "strict", "audit_every")


def _config_from_legacy(kwargs: Dict) -> ServerConfig:
    """Map the pre-redesign flat ``Server.__init__`` kwargs onto a
    ``ServerConfig`` (+ nested ``SchedulerConfig``). Unknown names raise
    TypeError exactly like a normal bad keyword would."""
    sched = {k: kwargs.pop(k) for k in _LEGACY_SCHED_KW if k in kwargs}
    if "scheduler" in kwargs:
        sched["policy"] = kwargs.pop("scheduler")
    top = {k: kwargs.pop(k) for k in _LEGACY_TOP_KW if k in kwargs}
    if kwargs:
        raise TypeError(
            f"Server() got unexpected keyword argument(s) {sorted(kwargs)}")
    if "kv_fmt" in top:
        # map straight onto the policy here so the flat-kwarg call warns
        # exactly once (the shim in ServerConfig.__post_init__ would warn
        # a second time for the same call site)
        top["cache"] = CachePolicy(active_fmt=top.pop("kv_fmt"))
    return ServerConfig(scheduler=SchedulerConfig(**sched), **top)


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One engine emission: a decoded token for a request, or (with
    ``finished=True`` and ``token=None``) the request's terminal event.
    ``index`` is the emitted-token index (0 = the prefill's seed token),
    ``t`` the host perf_counter timestamp at decode — the raw material
    for TTFT / inter-token-latency measurement. Buffered by the Server
    only while a front-end has switched ``collect_events`` on."""

    rid: int
    token: Optional[int]
    index: int
    t: float
    finished: bool = False
    status: Optional[str] = None  # terminal status on the finished event


@dataclasses.dataclass(frozen=True)
class RequestResult:
    """Immutable outcome of one served request — what
    ``run_until_drained`` returns and the async front-end resolves to,
    split from the mutable in-flight ``Request``. ``status`` is the one
    source of truth for how the request ended: ``"ok"`` (hit max_new),
    ``"truncated"`` (retired at the max_seq bound with fewer tokens) or
    ``"failed"`` (quarantined; ``error`` has the diagnostic).
    ``token_times`` holds the per-token host timestamps the engine
    recorded at decode — ``ttft``/``itl`` derive latency from them.
    ``admitted_at`` (first granted a slot) and ``first_chunk_at`` (first
    prefill chunk launched) split the time to first token into queue,
    prefill-lane wait and prefill; None where the request never got
    that far."""

    rid: int
    tokens: Tuple[int, ...]
    status: str
    error: Optional[str]
    prompt_len: int
    preemptions: int  # times this request's pages were stolen
    evictions: int  # times its host spill was dropped (re-prefilled)
    submitted_at: float
    token_times: Tuple[float, ...]
    admitted_at: Optional[float] = None
    first_chunk_at: Optional[float] = None

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    @property
    def truncated(self) -> bool:
        return self.status == "truncated"

    @property
    def ttft(self) -> Optional[float]:
        """Seconds from submit to the first token (None if none came)."""
        if not self.token_times:
            return None
        return self.token_times[0] - self.submitted_at

    @property
    def itl(self) -> Tuple[float, ...]:
        """Inter-token gaps in seconds (empty with < 2 tokens)."""
        ts = self.token_times
        return tuple(b - a for a, b in zip(ts, ts[1:]))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list
    max_new: int = 16
    sampling: SamplingParams = SamplingParams()  # frozen -> safe default
    priority: int = 0  # higher = steal from it last; ties -> slack, then age
    deadline_step: Optional[int] = None  # SLO: engine step to finish by;
    # victim selection steals the most slack first within a priority class
    # (slack = deadline - step - tokens remaining; None = infinite slack)
    frames: Optional[np.ndarray] = None  # enc-dec: (encoder_seq, d) embeddings
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = "ok"  # terminal status: "ok" | "truncated" | "failed"
    error: Optional[str] = None  # diagnostic when status == "failed"
    preemptions: int = 0  # times this request's pages were stolen
    evictions: int = 0  # times its host spill was dropped (re-prefilled)
    resume_ctx: Optional[list] = None  # evicted: full context to re-prefill
    since: int = 0  # server-managed: step this request entered the wait line
    seq: int = 0  # server-managed: global arrival sequence (tie-break)
    t_submit: float = 0.0  # server-managed: perf_counter at submit()
    # server-managed, set once (preemption, resume and eviction keep them):
    # perf_counter when first granted a slot, and just before the launch
    # of the program carrying its first prefill chunk
    t_admit: Optional[float] = None
    t_first_chunk: Optional[float] = None
    token_times: list = dataclasses.field(default_factory=list)
    _frames_digest: Optional[str] = dataclasses.field(
        default=None, repr=False, compare=False)

    def frames_digest(self) -> str:
        """Content digest of the encoder frames (enc-dec requests only),
        computed once per request. The prefix cache chains this request's
        radix root on it: decoder K/V depends on the frames through
        cross-attention, so identical token prefixes under different
        frames must never share pages — and under *identical* frames they
        safely can (sha256 over the exact frame bytes: no float tolerance,
        bit-equality or nothing)."""
        if self._frames_digest is None:
            assert self.frames is not None
            self._frames_digest = hashlib.sha256(
                np.ascontiguousarray(self.frames).tobytes()).hexdigest()
        return self._frames_digest

    @property
    def truncated(self) -> bool:
        """Retired at the max_seq bound with < max_new tokens out. Folded
        into ``status`` — one source of truth for how the request ended."""
        return self.status == "truncated"

    def result(self) -> RequestResult:
        """Snapshot this (retired) request as an immutable result."""
        return RequestResult(
            rid=self.rid, tokens=tuple(self.out), status=self.status,
            error=self.error, prompt_len=len(self.prompt),
            preemptions=self.preemptions, evictions=self.evictions,
            submitted_at=self.t_submit, token_times=tuple(self.token_times),
            admitted_at=self.t_admit, first_chunk_at=self.t_first_chunk)


@dataclasses.dataclass
class _Spill:
    """A preempted request's resumable state: the exact *privately-owned*
    page / slab payload (codes + scales + recurrent state per pool leaf,
    all layers) at preemption time. Shared-frozen prefix pages are not
    spilled — they stay resident in the content index (parked at refcount
    0 if nobody else maps them) and are re-resolved by token id on resume.
    Restoring the private bytes into any free pages/slab behind the
    re-acquired prefix reproduces the pool state bit-exactly, so the
    resumed request generates token-identical output. The wait-line key
    (``req.since``/``req.seq``) lives on the request and survives both
    resume and budget eviction — one global longest-waiting-first line."""

    req: Request
    ctx_len: int  # tokens of KV spilled (prompt + generated-so-far)
    shared_pages: int  # leading content-shared pages (not in the payload)
    payload: List[Dict[str, np.ndarray]]  # per engine unit: leaf -> array
    nbytes: int  # host bytes this spill holds (spill_budget accounting)
    crc: int = 0  # CRC32 of the pristine payload (kvc.payload_checksum),
    # re-verified before a resume commits: bit rot while spilled falls
    # back to a tail re-prefill instead of restoring garbage into the pool
    rng_seed: int = 0  # sampling RNG root at preemption — with ``emitted``
    emitted: int = 0  # (tokens sampled so far) this is the complete RNG
    # state of the stream: token i's key is fold_in(PRNGKey(seed), i), so
    # a resume continues the stream token-identically from index
    # ``emitted``. Both ride on the Request too (sampling.seed / len(out));
    # the spill carries them explicitly so _resume can assert the
    # restored stream position matches the bytes being restored


class Server:
    def __init__(self, params, cfg, config: Optional[ServerConfig] = None,
                 *, faults: Optional[FaultPlan] = None, **legacy):
        """``config``: a frozen :class:`ServerConfig` (every construction
        knob lives there; scheduler policy knobs nest in its
        ``scheduler: SchedulerConfig``). ``faults`` is runtime state, not
        configuration — a ``runtime.faults.FaultPlan`` consulted at the
        engine's injection hook points; None (default) keeps every hook a
        no-op, and injection never changes the jitted programs (the NaN
        poison is a jit *input*).

        The pre-redesign flat kwargs (``slots=``, ``max_seq=``,
        ``scheduler="token_budget"``, ``headroom_pages=``, ...) still
        work through a ``DeprecationWarning`` shim that maps them onto a
        ``ServerConfig`` — but cannot be mixed with an explicit
        ``config``."""
        if legacy:
            _migrate_legacy_kwarg(
                "flat Server(...) kwargs are deprecated; pass "
                "Server(params, cfg, ServerConfig(...)) — scheduler knobs "
                "nest under ServerConfig(scheduler=SchedulerConfig(...))",
                conflict=("pass either a ServerConfig or legacy flat "
                          f"kwargs, not both (got config= and "
                          f"{sorted(legacy)})")
                if config is not None else None,
                stacklevel=3)
            config = _config_from_legacy(legacy)
        if config is None:
            config = ServerConfig()
        sched = config.scheduler
        if sched.policy not in ("token_budget", "reserve"):
            raise ValueError(f"unknown scheduler policy {sched.policy!r}")
        if sched.engine not in ("mixed", "alternating"):
            raise ValueError(f"unknown scheduler engine {sched.engine!r}")
        self.config = config
        slots, max_seq = config.slots, config.max_seq
        policy, page_size = config.cache, config.page_size
        pool_pages, pool_slabs = config.pool_pages, config.pool_slabs
        a_fmt, prefix_cache = config.a_fmt, config.prefix_cache
        self.kernel_backend = config.kernel_backend
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_seq = max_seq
        self.a_fmt = a_fmt
        self.policy = policy
        self.kv_fmt = policy.active_fmt  # legacy read-side alias
        self.scheduler = sched.policy
        self.headroom_pages = sched.headroom_pages
        self.low_watermark = sched.low_watermark
        self.resume_watermark = sched.resume_watermark
        self.steal_cooldown = sched.steal_cooldown
        self.prefill_chunk_pages = sched.prefill_chunk_pages
        self.spill_budget_bytes = sched.spill_budget_bytes
        # mixed-step prefill piggyback budget, in tokens: rounded down to a
        # page multiple (min one page) so chunk starts stay page-aligned —
        # the append_prefill_chunk contract every pool invariant rides on
        budget = sched.prefill_token_budget
        if budget is None:
            budget = sched.prefill_chunk_pages * config.page_size
        self.prefill_token_budget = max(
            config.page_size, (budget // config.page_size) * config.page_size)
        self.strict = config.strict
        self.audit_every = config.audit_every
        self.faults = faults
        self.active: List[Optional[Request]] = [None] * slots
        self.queue: List[Request] = []
        self.preempted: List[_Spill] = []
        self.finished: List[Request] = []
        self.stats = {
            # engine steps that launched a decode or mixed program
            "steps": 0,
            # decoding rows, summed over steps
            "slot_steps": 0,
            # tokens emitted by decoding rows (not prefill seed tokens)
            "decoded_tokens": 0,
            # every jitted launch: encode/prefill/decode/mixed
            "programs": 0,
            # steps whose program carried a prefill chunk (mixed engine;
            # the alternating engine prefills in admission, outside steps)
            "prefill_steps": 0,
            # context tokens streamed through prefill programs
            "prefill_tokens": 0,
            # preemptions (a running request's pages stolen), spill restores
            "preemptions": 0,
            "resumes": 0,
            # private pages taken from preempted requests
            "pages_stolen": 0,
            # host spills dropped by the spill budget (re-prefilled)
            "spill_evictions": 0,
            # requests retired at the max_seq bound short of max_new
            "truncated": 0,
            # prompt pages, and their tokens, mapped from the prefix cache
            "prefix_hit_pages": 0,
            "prefix_hit_tokens": 0,
            # parked prefix-cache pages reclaimed by the allocator
            "prefix_reclaims": 0,
            # resumes whose shared prefix was reclaimed (tail re-prefill)
            "resume_fallbacks": 0,
            # requests quarantined (status "failed")
            "failed": 0,
            # spills whose checksum failed on resume (re-prefilled)
            "spill_integrity_failures": 0,
            # freeze-time FP8 -> FP4 page transcodes
            "fp4_frozen_pages": 0,
        }
        self._step_no = 0
        # engine tick: advances every step() *call*, decoded or not — the
        # clock fault hooks key on (a blocked alloc tick always passes,
        # so injected exhaustion is transient by construction)
        self._tick = 0
        self._alloc_faulted = False
        self._submit_seq = 0
        self._spill_bytes = 0
        # distinct (padded_chunk_len, table_width) prefill signatures fed to
        # the jitted step — with a fixed cfg this IS the prefill trace
        # count, which bucketing bounds to O(log max_seq)
        self.prefill_traces: set = set()

        self._encdec = cfg.encoder_layers > 0
        self._hybrid = _is_hybrid(cfg)
        self.page_size = page_size
        self.pages_per_slot = math.ceil(max_seq / page_size)
        self._cross_pp = (kvc.pages_needed(cfg.encoder_seq, page_size)
                          if self._encdec else 0)

        # ---- device mesh (MeshPlan) --------------------------------------
        # total == 1 (or mesh=None) never builds a Mesh: the engine runs
        # today's exact single-device code path, bit-for-bit
        self._mesh = None
        self._heads_sharding = None
        self._pool_shardings = None
        plan = config.mesh
        if plan is not None and plan.total > 1:
            if self._encdec or self._hybrid or cfg.ssm is not None or any(
                    seg.mixer not in ("gqa", "mla")
                    for seg in segments_for(cfg)):
                raise ValueError(
                    "MeshPlan(total>1) serves pure page families only "
                    "(GQA/MLA decoders); enc-dec cross pages and recurrent "
                    "state slabs are single-device")
            if cfg.n_heads % plan.model:
                raise ValueError(
                    f"n_heads={cfg.n_heads} must be divisible by "
                    f"MeshPlan.model={plan.model} (decode attention "
                    "shards by head)")
            if any(seg.mixer == "gqa" for seg in segments_for(cfg)) \
                    and cfg.n_kv_heads % plan.model:
                raise ValueError(
                    f"n_kv_heads={cfg.n_kv_heads} must be divisible by "
                    f"MeshPlan.model={plan.model} (KV pages and their "
                    "scales co-shard by KV head)")
            self._mesh = plan.build()
            self._heads_sharding = NamedSharding(
                self._mesh, P(None, None, "model", None))
        if self._mesh is None:
            self._decode = functools.partial(_decode_step_jit, cfg=cfg,
                                             a_fmt=a_fmt)
        # mesh > 1: the per-server jit of the SAME trace function is
        # installed by _shard_state() once the pools exist (it pins the
        # cache outputs to the canonical pool shardings)

        # ---- pools: one unit per (path into the cache tree, kind) --------
        # every unit's leaves are (lead, pool_size + 1, ...): lead = stacked
        # layers (or hybrid shared-block invocations), index 1 = page/slab id
        # with the last id reserved (null page / null slab)
        self._units: List[Tuple[tuple, str]] = []
        kv_n, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        self._n_slabs = 0
        if cfg.ssm is not None:
            self._n_slabs = pool_slabs or slots
        n_pages = pool_pages or slots * (self.pages_per_slot
                                         + (self._cross_pp if self._encdec
                                            else 0))
        # mixed-precision frozen pages exist only where the prefix cache
        # does: the FP4 region is written exclusively by the freeze-time
        # transcode, so a family that can never freeze a page (recurrent/
        # hybrid state, prefix_cache=False) has no use for it. Enc-dec
        # decoders DO freeze pages: their radix chains hang off a
        # per-frames-digest root (Request.frames_digest), so sharing is
        # conditioned on the encoder input, not just the token prefix
        supports_prefix = (prefix_cache
                           and not self._hybrid and cfg.ssm is None
                           and all(seg.mixer in ("gqa", "mla")
                                   for seg in segments_for(cfg)))
        if policy.mixed and not supports_prefix:
            raise ValueError(
                "CachePolicy(frozen_fmt=...) needs an active prefix cache: "
                "frozen FP4 pages hold only content-shared prefix pages, "
                "which exist for pure page families with prefix_cache=True")
        self._mixed = policy.mixed
        self._n_frozen = ((policy.frozen_pages or n_pages)
                          if self._mixed else 0)
        frozen_fmt = policy.frozen if self._mixed else None
        if self._hybrid:
            from repro.models.hybrid import n_attn_invocations
            from repro.models.ssm import init_mamba2_cache

            one = init_mamba2_cache(cfg, self._n_slabs + 1)
            mamba = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), one)
            self.pools = {"mamba": mamba}
            self._units.append((("mamba",), "slab"))
            n_inv = n_attn_invocations(cfg)
            if n_inv:
                self.pools["shared_kv"] = kvc.init_gqa_pool(
                    n_inv, n_pages, page_size, kv_n, hd, policy.active)
                self._units.append((("shared_kv",), "kv"))
        else:
            self.pools = []
            for i, seg in enumerate(segments_for(cfg)):
                seg_pools = {}
                if seg.mixer == "gqa":
                    seg_pools["kv"] = kvc.init_gqa_pool(
                        seg.count, n_pages, page_size, kv_n, hd,
                        policy.active, frozen_fmt=frozen_fmt,
                        n_frozen=self._n_frozen)
                    self._units.append(((i, "kv"), "kv"))
                    if seg.cross:
                        seg_pools["cross"] = kvc.init_cross_pool(
                            seg.count, n_pages, page_size, kv_n, hd,
                            policy.cross)
                        self._units.append(((i, "cross"), "cross"))
                elif seg.mixer == "mla":
                    seg_pools["kv"] = kvc.init_mla_pool(
                        seg.count, n_pages, page_size, cfg.mla.kv_lora_rank,
                        cfg.mla.qk_rope_dim, policy.active,
                        frozen_fmt=frozen_fmt, n_frozen=self._n_frozen)
                    self._units.append(((i, "kv"), "kv"))
                elif seg.mixer == "xlstm_pair":
                    from repro.models.xlstm import (init_mlstm_cache,
                                                    init_slstm_cache)

                    for name, init in (("mlstm", init_mlstm_cache),
                                       ("slstm", init_slstm_cache)):
                        one = init(cfg, self._n_slabs + 1)
                        seg_pools[name] = jax.tree.map(
                            lambda a: jnp.broadcast_to(
                                a, (seg.count,) + a.shape), one)
                        self._units.append(((i, name), "slab"))
                elif seg.mixer == "mamba2":
                    from repro.models.ssm import init_mamba2_cache

                    one = init_mamba2_cache(cfg, self._n_slabs + 1)
                    seg_pools["ssm"] = jax.tree.map(
                        lambda a: jnp.broadcast_to(
                            a, (seg.count,) + a.shape), one)
                    self._units.append(((i, "ssm"), "slab"))
                else:  # pragma: no cover
                    raise ValueError(f"unknown mixer {seg.mixer!r}")
                self.pools.append(seg_pools)

        self._has_pages = any(kind in ("kv", "cross")
                              for _, kind in self._units)
        self._has_slabs = any(kind == "slab" for _, kind in self._units)
        # pristine one-slab state per slab unit: recycled slabs are reset to
        # this at allocation (pages are fully overwritten by the prefill
        # stream, but a recurrent prefill *continues* from whatever state
        # its slab holds — a previous owner's leftovers must not leak in)
        self._slab_init = {
            ui: {name: np.asarray(leaf[:, :1])
                 for name, leaf in self._unit(path).items()}
            for ui, (path, kind) in enumerate(self._units) if kind == "slab"
        }
        # (recurrent-only families hold exact f32 state slabs: there is no
        # page payload for the cache policy to select, and it goes unused)
        self._n_pages = n_pages if self._has_pages else 0
        # recurrent state cannot mask pad tokens out of its carry, so
        # slab-holding families stream exact chunk lengths instead
        self._bucket_prefill = not self._has_slabs
        # resolved step architecture: the mixed (fused prefill+decode) step
        # applies to pure single-device page families only — recurrent
        # state cannot ride a padded fused row, enc-dec admission runs the
        # encoder eagerly, and the sharded engine keeps the alternating
        # shape its token-identity suite pins down. Everything else falls
        # back to alternating steps (Server.engine reports the choice).
        self._mixed_step = (sched.engine == "mixed" and self._has_pages
                            and not self._has_slabs and not self._encdec
                            and self._mesh is None)
        if self._mesh is not None:
            self._shard_state(cfg, a_fmt)

        self.free_pages: List[int] = list(range(self._n_pages))
        # frozen-region allocator (mixed-precision pools only): frozen
        # pages live in a unified *logical* id space behind the active
        # pool — id = _frozen_base + row index into the *_fz stores — so
        # page tables, refcounts and the prefix index need no second
        # namespace. The region's only writer is the freeze-time transcode.
        self.free_frozen: List[int] = [self._frozen_base + i
                                       for i in range(self._n_frozen)]
        # refcounted ownership: page_refs[pid] = number of slots mapping the
        # page right now. Private pages have refcount 1; content-shared
        # prefix pages can be mapped by many slots at once; refcount-0
        # registered pages park in the prefix cache's reusable LRU. Indexed
        # by logical id, so it spans the active pool, the (never-mapped)
        # null page, and the frozen region.
        self.page_refs = np.zeros(self._n_pages + 1 + self._n_frozen,
                                  np.int32)
        self.slot_pages: List[List[int]] = [[] for _ in range(slots)]
        # leading run of content-shared (frozen, registered) pages per slot;
        # everything past it in slot_pages is private (refcount 1, writable
        # at the boundary, spillable)
        self.slot_shared: List[int] = [0] * slots
        self._prefix: Optional[kvc.PrefixCache] = (
            kvc.PrefixCache(page_size)
            if (prefix_cache and self._has_pages and not self._has_slabs)
            else None)
        self.page_table = np.full(
            (slots, max(1, self.pages_per_slot if self._has_pages else 1)),
            self._null_page, np.int32)
        self.free_slabs: List[int] = list(range(self._n_slabs))
        self.slot_slab: List[int] = [-1] * slots
        self.slab_table = np.full((slots,), self._n_slabs, np.int32)
        self.slot_cross: List[List[int]] = [[] for _ in range(slots)]
        self.cross_table = np.full((slots, max(1, self._cross_pp)),
                                   self._null_page, np.int32)
        self.enc_lengths = np.zeros((slots,), np.int32)
        self.lengths = np.zeros(slots, dtype=np.int32)
        self._slot_seq = [0] * slots  # admission sequence of the occupant
        self._slot_since = [0] * slots  # step admitted/resumed (cooldown)
        # clean poison masks for the jitted step (fault injection swaps in
        # a real mask; reused so the no-fault path allocates nothing)
        self._no_poison = jnp.zeros((slots,), jnp.bool_)
        self._no_poison1 = jnp.zeros((1,), jnp.bool_)
        self._no_poison_m = jnp.zeros((slots + 1,), jnp.bool_)
        # per-slot sampling params threaded into the jitted step as five
        # flat arrays (greedy defaults on idle rows); refreshed from the
        # active requests every step — fixed-trace, never a retrace key
        self._samp = smp.slot_arrays(slots)
        # the mixed step's sampling rows: one per slot plus the prefill row
        self._samp_m = smp.slot_arrays(slots + 1)
        # engine emissions for the streaming front-end: decoded-token and
        # terminal events, buffered only while ``collect_events`` is on
        # (a sync run_until_drained caller would otherwise grow the
        # buffer unboundedly with nobody draining it)
        self.collect_events = False
        self._events: List[TokenEvent] = []

    @property
    def engine(self) -> str:
        """The *resolved* step architecture: ``"mixed"`` when the fused
        prefill+decode step is in effect, ``"alternating"`` when the
        engine fell back (recurrent/slab and enc-dec families, meshes) or
        was configured that way. May differ from
        ``config.scheduler.engine`` — that is the request, this is what
        actually runs."""
        return "mixed" if self._mixed_step else "alternating"

    @property
    def _null_page(self) -> int:
        """The reserved null page id (index P of every page pool)."""
        return getattr(self, "_n_pages", 0)

    @property
    def _frozen_base(self) -> int:
        """First frozen-region logical id: table entries >= this address
        the packed FP4 frozen stores (row ``pid - base``). Equals the
        active store's row count (P+1), matching the kernels' id-class
        select."""
        return self._null_page + 1

    def _unit(self, path):
        node = self.pools
        for p in path:
            node = node[p]
        return node

    def _set_unit(self, path, value):
        node = self.pools
        for p in path[:-1]:
            node = node[p]
        node[path[-1]] = value

    # -- mesh placement --------------------------------------------------------
    def _shard_state(self, cfg, a_fmt):
        """Place the engine's device state on the mesh and install the
        per-server decode jit. Params follow ``serve_rules`` (heads/ffn/
        vocab TP over 'model', experts EP over the whole mesh); every pool
        leaf follows its per-mesh-axis layout from ``serve_pool_pspecs``
        (GQA codes + shifts sharded by KV head, smax and MLA latents
        replicated). The recorded sharding tree doubles as the decode
        jit's cache ``out_shardings`` — placement is pinned, so the step
        compiles exactly once per input signature (fixed trace) — and as
        the re-pin target after host-driven pool writes."""
        from repro.launch import sharding as shardlib

        mesh = self._mesh
        self.params = jax.device_put(
            self.params,
            shardlib.serve_param_shardings(cfg, self.params, mesh))
        self._pool_shardings = [
            {key: {name: NamedSharding(
                mesh, shardlib.serve_pool_pspecs(pool, mesh)[name])
                for name in pool}
             for key, pool in seg_pools.items()}
            for seg_pools in self.pools]
        self.pools = jax.tree.map(
            lambda leaf, s: jax.device_put(leaf, s),
            self.pools, self._pool_shardings)
        repl = NamedSharding(mesh, P())
        self._decode = functools.partial(
            jax.jit(_decode_step, static_argnames=_STEP_STATIC,
                    out_shardings=(repl, repl, self._pool_shardings)),
            cfg=cfg, a_fmt=a_fmt)

    def _pin_pools(self):
        """Re-place every pool leaf on its canonical mesh sharding after a
        host-driven scatter (spill restore, quarantine scrub, freeze-time
        transcode): eager ``.at[].set`` updates follow their operands, so
        this keeps the layout byte-identical to what the decode jit's
        ``out_shardings`` pin — a no-op when already placed, and a no-op
        entirely off-mesh."""
        if self._mesh is None:
            return
        self.pools = jax.tree.map(
            lambda leaf, s: jax.device_put(leaf, s),
            self.pools, self._pool_shardings)

    def shard_residency(self) -> Dict[str, int]:
        """Resident pool bytes per device — the per-shard page residency
        the sharded serving bench and ``examples/serve_w4a8.py --mesh``
        report. Off-mesh this is the single default device's total."""
        per: Dict[str, int] = {}
        for path, _ in self._units:
            for leaf in self._unit(path).values():
                shards = getattr(leaf, "addressable_shards", None)
                if shards is None:  # non-jax leaf (tests with np stubs)
                    continue
                for sh in shards:
                    key = str(sh.device)
                    per[key] = per.get(key, 0) + int(sh.data.nbytes)
        return dict(sorted(per.items()))

    def _backend(self) -> str:
        """The kernel backend this server's steps trace under — its own
        choice, else the process-wide one — passed to every jitted step as
        a static argument, so the compiled program is keyed on it."""
        from repro.kernels import ops as _kops

        return self.kernel_backend or _kops.get_backend()

    @contextlib.contextmanager
    def _trace_scope(self):
        """Every engine trace (encoder run, prefill chunk, decode step)
        enters through here. On a mesh it installs the trace-time sharding
        globals: the kernels' shard_map mesh (kernels.ops), the
        expert-parallel MoE decode impl (models.moe_a2a) and the
        head-sharding hint (models.layers). All are restored on exit, so
        side-by-side servers (or a train step in the same process) never
        see another engine's placement. (The kernel backend is a static
        argument of the jitted steps, see ``_backend``.)"""
        if self._mesh is None:
            yield
            return
        from repro.kernels import ops as _kops
        from repro.models import layers as _layers
        from repro.models import moe_a2a as _moe

        prev_mesh = _kops.get_serving_mesh()
        prev_impl = _moe.get_moe_impl()
        prev_res = _layers._RESIDUAL_SHARDING[0]
        prev_heads = _layers._HEADS_SHARDING[0]
        _kops.set_serving_mesh(self._mesh)
        if self.cfg.moe is not None:
            _moe.set_moe_impl("ep_decode", self._mesh)
        _layers.set_residual_sharding(prev_res, self._heads_sharding)
        try:
            yield
        finally:
            _kops.set_serving_mesh(prev_mesh)
            _moe.set_moe_impl(*prev_impl)
            _layers.set_residual_sharding(prev_res, prev_heads)

    # -- page accounting -------------------------------------------------------
    def _worst_case_pages(self, req: Request) -> int:
        """Pages this request can ever hold (prompt + max_new capped at
        max_seq, plus the write-once encoder pages for enc-dec)."""
        if not self._has_pages:
            return 0
        return kvc.pages_needed(
            min(len(req.prompt) + req.max_new, self.max_seq),
            self.page_size) + self._cross_pp

    def _free_capacity(self) -> int:
        """Active-class pages allocatable right now: the free list plus the
        prefix cache's refcount-0 reusable LRU — reclaimed (blanked) before
        any live request is ever stolen from. In a mixed-precision pool
        every registered (and so every parked) page is frozen-class, which
        a private allocation can never use: only the free list counts."""
        if self._alloc_faulted:
            # injected transient exhaustion: the allocator reports dry for
            # this tick, so admission defers and growth falls back to the
            # normal steal response — exactly what a real stall triggers
            return 0
        n = len(self.free_pages)
        if self._prefix is not None and not self._mixed:
            n += self._prefix.n_reusable
        return n

    def _take_page(self) -> int:
        """One blank active-class page for a new private allocation: the
        free list first, then reclaim the LRU refcount-0 cached page
        (dropping its content from the prefix index). A mixed pool never
        reclaims here — its parked pages are frozen-class and would hand
        the allocator an id no write path may target."""
        if self.free_pages:
            return self.free_pages.pop(0)
        assert not self._mixed, "allocator called with zero free capacity"
        pid = self._prefix.reclaim()
        assert pid is not None, "allocator called with zero free capacity"
        self.stats["prefix_reclaims"] += 1
        return pid

    def _take_frozen(self) -> Optional[int]:
        """One blank frozen-region logical id for a freeze-time transcode:
        the frozen free list first, then reclaim the LRU parked page (in a
        mixed pool every registered page is frozen-class, so reclaim always
        yields a frozen id here). None when the region is fully live —
        the caller stops registering and leaves the tail private FP8."""
        if self.free_frozen:
            return self.free_frozen.pop(0)
        pid = self._prefix.reclaim()
        if pid is not None:
            self.stats["prefix_reclaims"] += 1
        return pid

    def _release_page(self, pid: int):
        """Drop one mapping of ``pid``. At refcount 0 a registered page
        parks in the prefix cache's reusable LRU (still bit-reusable by a
        future identical prefix); an unregistered page returns to its
        class's free list (frozen logical ids >= _frozen_base go back to
        the frozen region's list)."""
        self.page_refs[pid] -= 1
        assert self.page_refs[pid] >= 0, f"double-free of page {pid}"
        if self.page_refs[pid] > 0:
            return
        if self._prefix is not None and self._prefix.registered(pid):
            self._prefix.park(pid)
        elif pid >= self._frozen_base:
            self.free_frozen.append(pid)
        else:
            self.free_pages.append(pid)

    def _parked_among(self, pids: List[int]) -> int:
        """How many of these prefix hits sit in the reusable LRU (refcount
        0) *and* count as active-class allocatable capacity. They stop
        being allocatable the moment the admission maps them — feasibility
        must charge them against the free pool. Frozen-class hits never
        counted in ``_free_capacity`` to begin with, so they charge 0."""
        return sum(1 for pid in pids
                   if pid < self._n_pages and self.page_refs[pid] == 0)

    def _prefix_root(self, req: Request) -> int:
        """Radix-chain root for this request's prefix walks/inserts.
        Pure-token families share the global root; enc-dec requests chain
        off a per-frames-digest root node (decoder K/V depends on the
        encoder frames through cross-attention, so a token prefix is only
        shareable *under the same frames* — different frames get disjoint
        chains by construction, collision-safe with zero probability
        argument: the root node id differs)."""
        if not self._encdec:
            return kvc._PREFIX_ROOT
        return self._prefix.root_for(req.frames_digest())

    def _map_shared(self, slot: int, pids: List[int]):
        """Map content-shared prefix pages into an empty slot (refcount++;
        unpark any that sat in the reusable LRU). Zero prefill compute —
        the pages already hold the frozen K/V for these tokens."""
        assert not self.slot_pages[slot]
        for pid in pids:
            if self.page_refs[pid] == 0:
                self._prefix.unpark(pid)
            self.page_refs[pid] += 1
        self.slot_pages[slot] = list(pids)
        self.slot_shared[slot] = len(pids)
        self.page_table[slot, :len(pids)] = pids

    def _alloc(self, slot: int, npg: int) -> List[int]:
        ids = [self._take_page() for _ in range(npg)]
        for pid in ids:
            self.page_refs[pid] += 1
        self.slot_pages[slot].extend(ids)
        owned = self.slot_pages[slot]
        self.page_table[slot, :len(owned)] = owned
        return ids

    def _alloc_cross(self, slot: int) -> List[int]:
        ids = [self._take_page() for _ in range(self._cross_pp)]
        for pid in ids:
            self.page_refs[pid] += 1
        self.slot_cross[slot] = ids
        self.cross_table[slot, :len(ids)] = ids
        return ids

    def _alloc_slab(self, slot: int, reset: bool = True) -> int:
        sid = self.free_slabs.pop(0)
        self.slot_slab[slot] = sid
        self.slab_table[slot] = sid
        if reset:  # a resume overwrites the slab with its spill right after
            ids = jnp.asarray([sid], jnp.int32)
            for ui, (path, kind) in enumerate(self._units):
                if kind != "slab":
                    continue
                pool = dict(self._unit(path))
                for name, arr in self._slab_init[ui].items():
                    pool[name] = pool[name].at[:, ids].set(jnp.asarray(arr))
                self._set_unit(path, pool)
        return sid

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request):
        if not len(req.prompt):
            raise ValueError(
                f"request {req.rid}: empty prompt (decode needs at least "
                "one context token to seed the first logits row)")
        if req.max_new < 1:
            raise ValueError(
                f"request {req.rid}: max_new={req.max_new} must be >= 1")
        # same fail-fast contract as the prompt checks: a bad sampling
        # bound surfaces here as a clear ValueError, never as an opaque
        # in-graph mask (top_p <= 0 would silently kill every token)
        req.sampling.validate(req.rid)
        lo, hi = min(req.prompt), max(req.prompt)
        if lo < 0 or hi >= self.cfg.vocab_size:
            raise ValueError(
                f"request {req.rid}: prompt token ids must be in "
                f"[0, {self.cfg.vocab_size}), got "
                f"{lo if lo < 0 else hi} (an out-of-vocab id would surface "
                "as an opaque in-graph embedding gather)")
        if len(req.prompt) >= self.max_seq:
            # fail fast here: the streaming prefill would otherwise run out
            # of reserved pages mid-chunk with an opaque shape error
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)} must be "
                f"< max_seq={self.max_seq} (no room left to decode)")
        if self._encdec:
            if req.frames is None:
                raise ValueError(
                    f"request {req.rid}: enc-dec serving needs per-request "
                    "encoder frames (Request.frames)")
            if req.frames.shape[0] != self.cfg.encoder_seq:
                raise ValueError(
                    f"request {req.rid}: frames length {req.frames.shape[0]} "
                    f"!= encoder_seq={self.cfg.encoder_seq} (pad the input; "
                    "the encoder program is fixed-shape)")
        if self._has_pages and self._worst_case_pages(req) > self._n_pages:
            # fail fast on requests no retirement can ever fit
            raise ValueError(
                f"request {req.rid}: needs {self._worst_case_pages(req)} pages "
                f"but the pool has {self._n_pages}; raise pool_pages or "
                "shrink prompt/max_new")
        req.since = self._step_no
        req.seq = self._submit_seq
        req.t_submit = time.perf_counter()
        self._submit_seq += 1
        self.queue.append(req)  # (since, seq) is monotonic here: stays sorted

    def _enqueue(self, req: Request):
        """Re-insert an evicted request into the queue at its wait-line
        position: it keeps the (since, seq) it already earned, so eviction
        moves a spill between containers without losing its place."""
        keys = [(r.since, r.seq) for r in self.queue]
        self.queue.insert(bisect.bisect(keys, (req.since, req.seq)), req)

    def _admit(self):
        for slot in range(self.slots):
            if self.active[slot] is not None:
                continue
            if not (self.preempted or self.queue):
                break
            if not self._admit_one(slot):
                break  # head of line does not fit: wait (no overtaking)

    def _slack(self, req: Request) -> float:
        """Deadline slack in engine steps: how many steps this request can
        lose before it misses its SLO (deadline − now − tokens it still has
        to decode). No deadline = infinite slack (nothing to miss) — and so
        is a deadline that is already unmeetable (slack <= 0): shielding a
        request whose SLO is lost either way would only make a still-
        meetable peer miss too, so a dead deadline stops protecting."""
        if req.deadline_step is None:
            return math.inf
        slack = (req.deadline_step - self._step_no
                 - (req.max_new - len(req.out)))
        return slack if slack > 0 else math.inf

    def _pick_victim(self) -> Optional[int]:
        """Steal victim: lowest priority first, then — ROADMAP scheduler
        item (c) — the most deadline slack within that priority class (a
        request about to miss its SLO is shielded; one with no deadline, or
        with steps to spare, yields first), then the most recently arrived.
        Requests inside the steal cooldown are protected unless no other
        victim exists."""
        cands = [s for s, r in enumerate(self.active) if r is not None]
        if not cands:
            return None
        warm = [s for s in cands
                if self._step_no - self._slot_since[s] >= self.steal_cooldown]
        pick_from = warm or cands
        return min(pick_from,
                   key=lambda s: (self.active[s].priority,
                                  -self._slack(self.active[s]),
                                  -self._slot_seq[s]))

    def _slab_available(self, want_priority: int) -> bool:
        """True if a slab is free, or (token-budget scheduler only) one can
        be stolen for a waiter whose priority strictly beats the victim's.
        Reserve-on-admit never preempts — that is its whole contract — so
        under it slab exhaustion simply defers admission."""
        if not self._has_slabs:
            return True
        if self.free_slabs:
            return True
        if self.scheduler != "token_budget":
            return False
        victim = self._pick_victim()
        if victim is not None and self.active[victim].priority < want_priority:
            self._preempt(victim)
            return True
        return False

    def _admit_one(self, slot: int) -> bool:
        """Admit the longest-waiting candidate into ``slot``. Spilled and
        fresh requests share ONE ordered wait line keyed on (since, seq):
        a spill evicted by the budget keeps the place it already earned —
        it does not fall behind every younger spill just because it moved
        from ``preempted`` to ``queue`` — and the head of the line is never
        overtaken when it does not fit."""
        any_active = any(r is not None for r in self.active)
        free = self._free_capacity()
        spill = None
        if self.scheduler == "token_budget" and self.preempted:
            spill = min(self.preempted,
                        key=lambda sp: (sp.req.since, sp.req.seq))
        fresh = self.queue[0] if self.queue else None
        if spill is not None and fresh is not None and \
                (fresh.since, fresh.seq) < (spill.req.since, spill.req.seq):
            spill = None  # the fresh head has waited longer
        if spill is not None:
            req = spill.req
            shared_pids: List[int] = []
            if spill.shared_pages:
                ctx = list(req.prompt) + list(req.out[:-1])
                shared_pids = self._prefix.walk(
                    ctx, max_pages=spill.shared_pages,
                    root=self._prefix_root(req))
                if len(shared_pids) < spill.shared_pages:
                    # part of the shared prefix was reclaimed while this
                    # request sat spilled: the private payload no longer
                    # abuts a resolvable prefix — drop the bytes and fall
                    # back to a tail re-prefill through the normal
                    # admission walk (whatever hits remain still count)
                    self.stats["resume_fallbacks"] += 1
                    self._evict_spill(spill)
                    return self._admit_one(slot)
            need = 0
            if self._has_pages:
                # parked hits count in _free_capacity() but stop being
                # allocatable the moment this admission maps them — charge
                # them against the free pool or _alloc could run dry
                free -= self._parked_among(shared_pids)
                need = min(kvc.pages_needed(spill.ctx_len, self.page_size)
                           - spill.shared_pages + self.headroom_pages,
                           self._worst_case_pages(req) - self._cross_pp
                           - spill.shared_pages)
                need += self._cross_pp
                margin = self.resume_watermark if any_active else 0
                if free - need < margin:
                    return False
            if not self._slab_available(req.priority):
                return False
            if kvc.payload_checksum(spill.payload) != spill.crc:
                # bit rot while spilled: restoring these bytes would put
                # silent garbage in the pool. Drop them and fall back to
                # the eviction-style tail re-prefill — the request still
                # finishes, token-identically, it just pays the prefill
                self.stats["spill_integrity_failures"] += 1
                self._evict_spill(spill)
                return self._admit_one(slot)
            self.preempted.remove(spill)
            self._spill_bytes -= spill.nbytes
            self._resume(slot, spill, shared_pids, need - self._cross_pp)
            return True
        if fresh is None:
            return False
        req = fresh
        ctx = req.resume_ctx if req.resume_ctx is not None else req.prompt
        ctx_len = len(ctx)
        hits: List[int] = []
        if self._prefix is not None:
            # the last context token always streams through the prefill
            # (its logits seed decode), so cap the walk one token short —
            # this also keeps the boundary page private by construction
            hits = self._prefix.walk(
                ctx, max_pages=(ctx_len - 1) // self.page_size,
                root=self._prefix_root(req))
        need = 0
        if self._has_pages:
            free -= self._parked_among(hits)  # mapping a parked hit uses it
            if self.scheduler == "reserve":
                need = self._worst_case_pages(req) - len(hits)
                if free < need:
                    return False
            else:
                need = min(kvc.pages_needed(ctx_len, self.page_size)
                           - len(hits) + self.headroom_pages,
                           self._worst_case_pages(req) - self._cross_pp
                           - len(hits))
                need += self._cross_pp
                margin = self.low_watermark if any_active else 0
                if free - need < margin:
                    return False
        if not self._slab_available(req.priority):
            return False
        self.queue.pop(0)
        self.active[slot] = req
        if req.t_admit is None:
            req.t_admit = time.perf_counter()
        self._slot_seq[slot] = req.seq
        self._slot_since[slot] = self._step_no
        if self._has_pages:
            if hits:
                self._map_shared(slot, hits)
                self.lengths[slot] = len(hits) * self.page_size
                self.stats["prefix_hit_pages"] += len(hits)
                self.stats["prefix_hit_tokens"] += len(hits) * self.page_size
            self._alloc(slot, need - self._cross_pp)
            if self._encdec:
                self._alloc_cross(slot)
        if self._has_slabs:
            self._alloc_slab(slot)
        if self._mixed_step:
            # mixed engine: the context streams through subsequent fused
            # engine steps (one budgeted chunk piggybacked per step), so
            # admission only maps/allocates pages. Run the write-target
            # freeze check here — the stream's pages were just mapped and
            # stay private (only this slot's own _register_prefix can
            # freeze them) until the stream completes.
            if self._prefix is not None:
                self._prefix.assert_unfrozen(
                    self.slot_pages[slot][
                        int(self.lengths[slot]) // self.page_size:
                        kvc.pages_needed(ctx_len, self.page_size)],
                    frozen_base=self._frozen_base)
            # the mixed step derives the stream context and fresh-ness from
            # the request itself (prompt + out[:-1]; fresh iff no out), so
            # a resume marker has nothing left to carry
            req.resume_ctx = None
        else:
            self._prefill_slot(slot, req)
        return True

    # -- streaming paged prefill ----------------------------------------------
    def _state_for(self, rows, lengths, chunk_len=None):
        """Build the PagedState for ``rows`` (a slice or index list)."""
        return kvc.PagedState(
            page_table=jnp.asarray(self.page_table[rows]),
            lengths=jnp.asarray(lengths),
            chunk_len=chunk_len,
            cross_table=(jnp.asarray(self.cross_table[rows])
                         if self._encdec else None),
            enc_lengths=(jnp.asarray(self.enc_lengths[rows])
                         if self._encdec else None),
            slabs=(jnp.asarray(self.slab_table[rows])
                   if self._has_slabs else None),
        )

    def _chunk_plan(self, slot: int, n: int, pos: int, budget: int):
        """Plan one streaming-prefill chunk for ``slot`` at stream position
        ``pos`` of an ``n``-token context: the true chunk length ``take``,
        its power-of-two bucketed pad length ``padded``, the bucketed page
        table width ``w`` and the (1, w) trimmed table. Shared by the
        serial prefill loop and the mixed engine step, so both compile the
        same O(log max_seq) family of chunk shapes. Only pages holding
        real data up to the chunk's true end are mapped: a bucketed
        chunk's zeroed pad writes overhang the last data page, and
        append_prefill_chunk's contract is that those positions must point
        at the null page — not at allocated headroom (harmless while
        private, corruption once shared)."""
        page = self.page_size
        take = min(budget, n - pos)
        if self._bucket_prefill:
            padded = min(_next_pow2(take), budget)
            w = _next_pow2(pos // page + kvc.pages_needed(padded, page))
        else:
            padded = take
            w = (kvc.pages_needed(pos + take, page) if self._has_pages
                 else 1)
        own = self.slot_pages[slot]
        table = np.full((1, w), self._null_page, np.int32)
        m = min(w, len(own), kvc.pages_needed(pos + take, page))
        table[0, :m] = own[:m]
        return take, padded, w, table

    def _prefill_slot(self, slot: int, req: Request):
        """Prefill a (re)admitted request: stream its context through the
        model in page-aligned chunks, each chunk's K/V written straight
        into this slot's pages inside the jitted forward (no contiguous
        max_seq scratch cache). When admission mapped content-shared prefix
        pages, ``lengths[slot]`` already covers them and only the uncached
        tail streams here. Chunk lengths and page-table widths are bucketed
        to powers of two (pad + mask) so trace count is O(log max_seq);
        recurrent families stream exact chunks (pad tokens cannot be masked
        out of a recurrence). Enc-dec requests first run the encoder once,
        writing every decoder layer's cross K/V into the slot's write-once
        cross pages. Afterwards every full prompt page is registered in the
        prefix index (scale-frozen from here on: only the private boundary
        page is ever requantized again)."""
        ctx = req.resume_ctx if req.resume_ctx is not None else list(req.prompt)
        fresh = req.resume_ctx is None
        req.resume_ctx = None
        n = len(ctx)
        page = self.page_size
        if self._encdec:
            frames = jnp.asarray(req.frames, jnp.float32)[None]
            table = jnp.asarray(self.cross_table[slot:slot + 1])
            with self._trace_scope():
                self.pools = _encode_cross_jit(self.params, frames,
                                               self.pools, table,
                                               cfg=self.cfg, a_fmt=self.a_fmt,
                                               backend=self._backend())
            self.stats["programs"] += 1
            self.enc_lengths[slot] = self.cfg.encoder_seq

        chunk = self.prefill_token_budget
        start = int(self.lengths[slot])  # > 0: shared prefix already mapped
        if self._prefix is not None:
            # the stream writes pages [start/page, ceil(n/page)) — none of
            # them may be shared-frozen (boundary pages stay private), and
            # in a mixed pool none may be a packed FP4 logical id
            self._prefix.assert_unfrozen(
                self.slot_pages[slot][start // page:
                                      kvc.pages_needed(n, page)],
                frozen_base=self._frozen_base)
        # the final chunk's in-graph sample seeds the stream (emitted-token
        # index = len(out): 0 for a fresh prefill; a resume re-prefill
        # discards the draw, so the index is never consumed twice)
        samp1 = smp.slot_arrays(1)
        smp.fill_slot(samp1, 0, req.sampling, len(req.out))
        samp1 = smp.as_tuple(samp1)
        nxt = None
        ok = True
        pos = start
        while pos < n:
            take, padded, w, table = self._chunk_plan(slot, n, pos, chunk)
            toks = ctx[pos: pos + take] + [0] * (padded - take)
            # chunk_len rides along for every prefill chunk (not just
            # bucketed ones): models use it both to mask pad positions and
            # to tell a 1-token chunk apart from a decode step
            chunk_len = jnp.asarray([take], jnp.int32)
            state = self._state_for(slice(slot, slot + 1),
                                    np.asarray([pos], np.int32), chunk_len)
            state = state._replace(page_table=jnp.asarray(table))
            if req.t_first_chunk is None:
                req.t_first_chunk = time.perf_counter()
            with self._trace_scope():
                nxt, row_ok, pools = self._decode(
                    self.params, self.pools, jnp.asarray([toks], jnp.int32),
                    state, self._no_poison1, samp1, backend=self._backend())
            self.pools = pools
            self.stats["programs"] += 1
            ok = ok and bool(np.asarray(row_ok)[0])
            self.prefill_traces.add((padded, w))
            pos += take
        self.lengths[slot] = n
        self.stats["prefill_tokens"] += n - start
        if not ok:
            # non-finite logits during this request's prefill: quarantine
            # the request alone. Its pages are NOT registered in the
            # prefix index (frozen garbage would poison every future hit)
            # and no seed token is appended — retire through the normal
            # path so pages/slab accounting stays intact
            self._fail_slot(slot, req,
                            f"non-finite logits during prefill of request "
                            f"{req.rid} ({n} context tokens)",
                            scrub_null=True)
            return
        if self._prefix is not None:
            self._register_prefix(slot, req)
        if fresh:
            self._emit_token(req, int(np.asarray(nxt)[0]))

    def _register_prefix(self, slot: int, req: Request):
        """Promote this slot's full prompt pages to shared-frozen: register
        them in the content index so later requests with the same prefix
        map them for free. Only the *prompt* region is registered — its
        pages were written by the (deterministic) prefill stream in one
        shot, so their frozen content is bit-reusable by any owner;
        decode-grown pages went through per-step requantization and are
        not. If another slot registered the same chain first (e.g. the
        walk was capped short of an exactly-page-aligned prompt), adopt the
        canonical page and release our duplicate — dedup keeps the shared
        pages one contiguous leading run.

        Mixed-precision policy (``CachePolicy.frozen_fmt="fp4_e2m1"``):
        registration IS the freeze point, so this is where each page is
        transcoded FP8 -> packed FP4, exactly once — the frozen region's
        only write. Per newly-full prompt page: adopt the already-frozen
        canonical if the chain exists, else allocate a frozen logical id,
        ``kv_cache.transcode_page`` the FP8 page into it, remap the slot to
        the frozen id and release the FP8 source back to the free list.
        When the frozen region runs dry the loop stops gracefully — the
        remaining prompt pages simply stay private FP8 (unshared but
        correct), keeping the shared run contiguous."""
        page = self.page_size
        n_full = len(req.prompt) // page
        shared = self.slot_shared[slot]
        if n_full <= shared:
            return  # nothing new beyond the already-mapped prefix
        own = self.slot_pages[slot]
        if not self._mixed:
            canon = self._prefix.insert(req.prompt[:n_full * page],
                                        own[:n_full],
                                        root=self._prefix_root(req))
            for i in range(shared, n_full):
                if canon[i] != own[i]:  # duplicate content: adopt canonical
                    dup = own[i]
                    if self.page_refs[canon[i]] == 0:
                        self._prefix.unpark(canon[i])
                    self.page_refs[canon[i]] += 1
                    own[i] = canon[i]
                    self.page_table[slot, i] = canon[i]
                    self._release_page(dup)  # private, refcount 1 -> free
            self.slot_shared[slot] = n_full
            return
        # mixed: every registered page lives in the packed FP4 region
        canon = self._prefix.walk(req.prompt, max_pages=n_full,
                                  root=self._prefix_root(req))
        end = shared
        for i in range(shared, n_full):
            src = own[i]
            if i < len(canon):  # identical prefix already frozen: adopt it
                fid = canon[i]
                if self.page_refs[fid] == 0:
                    self._prefix.unpark(fid)
            else:
                fid = self._take_frozen()
                if fid is None:
                    break  # frozen region fully live: tail stays private
                for path, kind in self._units:
                    if kind == "kv":
                        self._set_unit(path, kvc.transcode_page(
                            self._unit(path), src,
                            fid - self._frozen_base))
                self.stats["fp4_frozen_pages"] += 1
            self.page_refs[fid] += 1
            own[i] = fid
            self.page_table[slot, i] = fid
            self._release_page(src)  # the FP8 source, refcount 1 -> free
            end = i + 1
        self._pin_pools()  # freeze-time transcodes wrote the fz region
        if end > shared:
            self._prefix.insert(req.prompt[:end * page], own[:end],
                                root=self._prefix_root(req))
        self.slot_shared[slot] = end

    # -- preemption by page steal ----------------------------------------------
    def _preempt(self, slot: int):
        """Steal this slot's pages (and slab): spill its *private* payload
        (codes + scales + recurrent state, bit-exact) to host memory and
        drop every page mapping. Content-shared prefix pages are not
        spilled — their frozen bytes stay resident in the prefix index
        (parked at refcount 0 if no other slot maps them) and are
        re-resolved by token id on resume."""
        req = self.active[slot]
        ctx_len = int(self.lengths[slot])
        shared = self.slot_shared[slot]
        npg = kvc.pages_needed(ctx_len, self.page_size)
        priv = self.slot_pages[slot][shared:npg]
        payload = []
        nbytes = 0
        for path, kind in self._units:
            pool = self._unit(path)
            if kind == "kv":
                ids = jnp.asarray(priv, jnp.int32)
            elif kind == "cross":
                ids = jnp.asarray(self.slot_cross[slot], jnp.int32)
            else:  # slab
                ids = jnp.asarray([self.slot_slab[slot]], jnp.int32)
            # only private pages spill, and those are always active-class:
            # the frozen-region ``*_fz`` leaves (different row count, ids
            # are logical) and the zero-size format marker never ride along
            part = {name: np.asarray(leaf[:, ids])
                    for name, leaf in pool.items()
                    if "_fz" not in name and leaf.size}
            nbytes += sum(a.nbytes for a in part.values())
            payload.append(part)
        # integrity checksum over the pristine bytes; the fault hook runs
        # *after* it (tampering models bit rot during host residency, so
        # the resume-time verify is what must catch it)
        crc = kvc.payload_checksum(payload)
        if self.faults is not None:
            payload = self.faults.spill_payload(req.rid, payload)
        req.since = self._step_no  # re-enters the wait line now
        self.preempted.append(_Spill(req=req, ctx_len=ctx_len,
                                     shared_pages=shared, payload=payload,
                                     nbytes=nbytes, crc=crc,
                                     rng_seed=req.sampling.seed,
                                     emitted=len(req.out)))
        self._spill_bytes += nbytes
        req.preemptions += 1
        self.stats["preemptions"] += 1
        self.stats["pages_stolen"] += (len(self.slot_pages[slot]) - shared
                                       + len(self.slot_cross[slot]))
        for pid in self.slot_pages[slot]:
            self._release_page(pid)
        for pid in self.slot_cross[slot]:
            self._release_page(pid)
        self.slot_pages[slot] = []
        self.slot_cross[slot] = []
        self.slot_shared[slot] = 0
        self.page_table[slot] = self._null_page
        self.cross_table[slot] = self._null_page
        self.enc_lengths[slot] = 0
        if self.slot_slab[slot] >= 0:
            self.free_slabs.append(self.slot_slab[slot])
            self.slot_slab[slot] = -1
            self.slab_table[slot] = self._n_slabs
        self.lengths[slot] = 0
        self.active[slot] = None

    def _evict_spill(self, sp: _Spill):
        """Drop a spill's host bytes and re-queue its request for a full
        context re-prefill. The request keeps its wait-line key
        (``since``/``seq`` earned at preemption), so eviction moves it
        between containers without losing its place — readmission order
        stays one global longest-waiting-first line, and a budget eviction
        can no longer push the *oldest* waiter behind every younger spill."""
        self.preempted.remove(sp)
        self._spill_bytes -= sp.nbytes
        req = sp.req
        # KV context at preemption = prompt + out[:-1] (the newest token
        # was produced but not yet fed back); re-prefilling exactly that
        # context lets decode continue by feeding out[-1] as usual. A
        # request spilled mid-prefill (no tokens out yet, mixed engine)
        # re-enters as fresh — marking it resumed would swallow the seed
        # token its first completed prefill is supposed to emit
        req.resume_ctx = (list(req.prompt) + list(req.out[:-1])
                          if req.out else None)
        req.evictions += 1
        self.stats["spill_evictions"] += 1
        self._enqueue(req)

    def _enforce_spill_budget(self):
        """ROADMAP (b): host spills are bounded. When resident spill bytes
        exceed ``spill_budget_bytes``, evict oldest-first: drop the spill's
        bytes and re-queue its request — at its existing wait-line position
        — with its full context (prompt + tokens generated so far) marked
        for re-prefill; the request still finishes, token-identically, it
        just pays a prompt re-prefill instead of a byte restore.

        Runs at the top of every engine step, never from inside
        ``_preempt``: a steal can fire mid-admission (``_slab_available``),
        and evicting there would mutate ``queue``/``preempted`` under
        ``_admit_one``'s feet — the admitted request's ``queue.pop(0)``
        would pop the freshly re-queued eviction instead. Enforcing at the
        step boundary means the budget can overshoot by the spills of a
        single scheduling round, and evicted requests re-enter admission
        in the same step they are dropped."""
        if self.spill_budget_bytes is None:
            return
        while (self._spill_bytes > self.spill_budget_bytes
               and self.preempted):
            self._evict_spill(min(self.preempted,
                                  key=lambda s: (s.req.since, s.req.seq)))

    def _resume(self, slot: int, spill: _Spill, shared_pids: List[int],
                need_kv: int):
        """Restore a spilled request: map its re-resolved content-shared
        prefix pages (zero bytes moved — the frozen content never left the
        pool), then restore the private payload bit-exactly into fresh
        pages/slab behind them (token-identical: page/slab ids are logical,
        the model only sees the tables)."""
        self.active[slot] = spill.req
        self._slot_seq[slot] = spill.req.seq  # keeps its original age
        self._slot_since[slot] = self._step_no
        new_kv: List[int] = []
        new_cross: List[int] = []
        if self._has_pages:
            if shared_pids:
                self._map_shared(slot, shared_pids)
            new_kv = self._alloc(slot, need_kv)
            if self._prefix is not None:  # restore targets must be writable
                self._prefix.assert_unfrozen(new_kv,
                                             frozen_base=self._frozen_base)
            if self._encdec:
                new_cross = self._alloc_cross(slot)
                self.enc_lengths[slot] = self.cfg.encoder_seq
        if self._has_slabs:
            self._alloc_slab(slot, reset=False)  # restored from spill below
        npg_priv = (kvc.pages_needed(spill.ctx_len, self.page_size)
                    - spill.shared_pages)
        for (path, kind), part in zip(self._units, spill.payload):
            if kind == "kv":
                ids = jnp.asarray(new_kv[:npg_priv], jnp.int32)
            elif kind == "cross":
                ids = jnp.asarray(new_cross, jnp.int32)
            else:  # slab
                ids = jnp.asarray([self.slot_slab[slot]], jnp.int32)
            pool = dict(self._unit(path))
            for name, arr in part.items():
                pool[name] = pool[name].at[:, ids].set(jnp.asarray(arr))
            self._set_unit(path, pool)
        self._pin_pools()  # host scatter -> back onto the canonical layout
        self.lengths[slot] = spill.ctx_len
        # RNG continuity: the spill carries the request's complete sampling
        # state (seed + emitted count). The key for the next draw is
        # fold_in(PRNGKey(seed), len(out)) — recomputed from the request,
        # so the spilled copy is an integrity check, not a live register.
        assert spill.rng_seed == spill.req.sampling.seed
        assert spill.emitted == len(spill.req.out), (
            f"request {spill.req.rid}: spill recorded {spill.emitted} "
            f"emitted tokens but the request holds {len(spill.req.out)} — "
            "the resumed RNG stream would diverge")
        self.stats["resumes"] += 1

    def _steal_for(self, needer: int) -> bool:
        """Free pages by preempting the cooldown-aware lowest-priority
        victim (see _pick_victim). The needer itself is a valid victim —
        if it is the lowest-priority request running, it is the one that
        yields."""
        victim = self._pick_victim()
        if victim is None:
            return False
        self._preempt(victim)
        return True

    def _grow(self):
        """On-demand page allocation: before the decode step, every active
        row whose next token crosses into an unallocated page gets one from
        the pool — reclaiming refcount-0 cached pages first, and stealing
        from the lowest-priority request only when even the reusable set is
        dry. Rows are served in priority order (then arrival order), so a
        steal always benefits the higher-priority work."""
        if not self._has_pages:
            return
        order = sorted(
            (s for s, r in enumerate(self.active) if r is not None),
            key=lambda s: (-self.active[s].priority, self._slot_seq[s]))
        for slot in order:
            while self.active[slot] is not None:
                need_idx = int(self.lengths[slot]) // self.page_size
                if need_idx < len(self.slot_pages[slot]):
                    break
                if self._free_capacity():
                    self._alloc(slot, 1)
                elif not self._steal_for(slot):
                    break  # pragma: no cover — needer itself is a candidate

    # -- streaming emissions ---------------------------------------------------
    def _emit_token(self, req: Request, token: int):
        """Append a decoded token to the request and (when a front-end is
        listening) buffer its TokenEvent with the decode timestamp — the
        raw material for TTFT / inter-token latency."""
        t = time.perf_counter()
        req.out.append(token)
        req.token_times.append(t)
        if self.collect_events:
            self._events.append(TokenEvent(
                rid=req.rid, token=token, index=len(req.out) - 1, t=t))

    def _emit_finished(self, req: Request):
        if self.collect_events:
            self._events.append(TokenEvent(
                rid=req.rid, token=None, index=len(req.out),
                t=time.perf_counter(), finished=True, status=req.status))

    def pop_events(self) -> List[TokenEvent]:
        """Drain the buffered engine emissions (empty unless a front-end
        switched ``collect_events`` on). Every decoded token yields one
        event in decode order; every retirement (ok / truncated / failed)
        yields a terminal event with the request's status."""
        ev, self._events = self._events, []
        return ev

    # -- retirement ----------------------------------------------------------
    def _retire(self, slot: int, req: Request):
        req.done = True
        self.active[slot] = None
        self.finished.append(req)
        self._emit_finished(req)
        # freed pages are NOT zeroed (that would rewrite the whole pool per
        # retirement): recycled pages are overwritten by the prefill stream,
        # and decode appends mask positions past the new owner's length
        # before recomputing page scales, so stale codes can never leak.
        # Registered prompt pages whose refcount drops to 0 here park in
        # the prefix cache's reusable LRU instead of the free list — the
        # request's system-prompt K/V outlives it for the next hit
        for pid in self.slot_pages[slot]:
            self._release_page(pid)
        for pid in self.slot_cross[slot]:
            self._release_page(pid)
        self.slot_pages[slot] = []
        self.slot_cross[slot] = []
        self.slot_shared[slot] = 0
        self.page_table[slot] = self._null_page
        self.cross_table[slot] = self._null_page
        self.enc_lengths[slot] = 0
        if self.slot_slab[slot] >= 0:
            self.free_slabs.append(self.slot_slab[slot])
            self.slot_slab[slot] = -1
            self.slab_table[slot] = self._n_slabs
        self.lengths[slot] = 0

    # -- request-level failure isolation --------------------------------------
    def _scrub_slot(self, slot: int, include_null: bool = False):
        """Zero every pool page / slab a quarantined row may have written:
        its private non-registered pages, cross pages and slab — plus the
        shared null page when a failing prefill's bucketed overhang wrote
        there. Necessary, not cosmetic: a non-finite upstream activation
        writes NaN K/V codes, and NaN survives attention's zero-weight
        masking (0 * NaN = NaN) — a recycled free-list page or the null
        page holding NaN bytes would fail *healthy* rows, breaking exactly
        the isolation the quarantine guarantees. Registered pages are
        excluded: they were frozen by a healthy prefill (the CoW invariant
        keeps a failing row's writes out of them)."""
        priv = list(self.slot_pages[slot][self.slot_shared[slot]:])
        if include_null:
            priv.append(self._null_page)
        kv_ids = jnp.asarray(priv, jnp.int32) if priv else None
        cross_ids = (jnp.asarray(self.slot_cross[slot], jnp.int32)
                     if self.slot_cross[slot] else None)
        slab_ids = (jnp.asarray([self.slot_slab[slot]], jnp.int32)
                    if self.slot_slab[slot] >= 0 else None)
        for path, kind in self._units:
            ids = {"kv": kv_ids, "cross": cross_ids}.get(kind, slab_ids)
            if ids is None:
                continue
            pool = self._unit(path)
            for name in pool:
                # scrub only the active-class stores: a quarantined row can
                # never have written the frozen region (transcode is its
                # only writer), and the ids here would misindex its rows
                if "_fz" in name or not pool[name].size:
                    continue
                pool[name] = pool[name].at[:, ids].set(0)
            self._set_unit(path, pool)
        self._pin_pools()  # host scatter -> back onto the canonical layout

    def _fail_slot(self, slot: int, req: Request, error: str,
                   scrub_null: bool = False):
        """Quarantine one active row: scrub the pool bytes it wrote, mark
        it failed and retire it through the normal path — its pages/slab
        free (or park) with refcounts intact, every other row keeps
        decoding. The per-process blast radius of a poisoned row is
        exactly that row."""
        self._scrub_slot(slot, include_null=scrub_null)
        req.status = "failed"
        req.error = error
        self.stats["failed"] += 1
        self._retire(slot, req)

    def _fail_request(self, req: Request, error: str):
        """Fail a request that holds no pool state (queued or already
        spilled-and-dropped): it retires straight into ``finished``."""
        req.status = "failed"
        req.error = error
        req.done = True
        self.stats["failed"] += 1
        self.finished.append(req)
        self._emit_finished(req)

    def _fail_pending(self, reason: str):
        """Non-strict starvation response: fail every queued and spilled
        request individually (dropping spill bytes) instead of raising a
        drain-wide error — active rows are untouched and keep decoding."""
        for sp in list(self.preempted):
            self.preempted.remove(sp)
            self._spill_bytes -= sp.nbytes
            self._fail_request(sp.req, reason)
        for req in list(self.queue):
            self.queue.remove(req)
            self._fail_request(req, reason)

    def _pending_diagnostics(self) -> List[Dict]:
        """One diagnostic dict per request still waiting or running —
        attached to ServingError so strict-mode callers see *why* each
        straggler could not finish."""
        diag = []
        for req in self.queue:
            ctx = req.resume_ctx if req.resume_ctx is not None else req.prompt
            diag.append({
                "rid": req.rid, "state": "queued", "since": req.since,
                "out_tokens": len(req.out), "ctx_len": len(ctx),
                "pages_needed": (kvc.pages_needed(len(ctx), self.page_size)
                                 + self._cross_pp if self._has_pages else 0)})
        for sp in self.preempted:
            diag.append({
                "rid": sp.req.rid, "state": "spilled", "since": sp.req.since,
                "out_tokens": len(sp.req.out), "ctx_len": sp.ctx_len,
                "pages_needed": (kvc.pages_needed(sp.ctx_len, self.page_size)
                                 + self._cross_pp if self._has_pages else 0),
                "spill_bytes": sp.nbytes})
        for s, req in enumerate(self.active):
            if req is not None:
                diag.append({
                    "rid": req.rid, "state": "active", "slot": s,
                    "out_tokens": len(req.out),
                    "ctx_len": int(self.lengths[s])})
        return diag

    # -- engine step ----------------------------------------------------------
    @staticmethod
    def _ctx_target(req: Request) -> int:
        """The KV length at which ``req`` is fully prefilled and decoding:
        its prompt plus every emitted token except the newest (produced but
        not yet fed back). A slot below this target is mid-prefill."""
        return len(req.prompt) + max(len(req.out) - 1, 0)

    def _extend_shared(self, slot: int, ctx: List[int]):
        """Stream-start prefix re-walk for the mixed engine. Between this
        request's admission and the first chunk of its stream, a sibling
        stream may have registered exactly the prefix this slot is about
        to recompute — a window the alternating engine never has (its
        prefill completes inside admission, so the walk and the stream
        are atomic). Re-walk the index and adopt any newly frozen pages:
        map each over the private page admission allocated for the same
        position (released back to the pool — or appended, when a spill
        restored fewer pages than the walk now covers) and advance the
        stream past them. Adopted content is bit-identical to what the
        stream would have written, by the same determinism argument
        admission-time hits rely on."""
        page = self.page_size
        shared = self.slot_shared[slot]
        req = self.active[slot]
        hits = self._prefix.walk(ctx, max_pages=(len(ctx) - 1) // page,
                                 root=self._prefix_root(req))
        if len(hits) <= shared:
            return
        own = self.slot_pages[slot]
        for i in range(shared, len(hits)):
            pid = hits[i]
            if self.page_refs[pid] == 0:
                self._prefix.unpark(pid)
            self.page_refs[pid] += 1
            if i < len(own):
                self._release_page(own[i])
                own[i] = pid
            else:
                own.append(pid)
            self.page_table[slot, i] = pid
        self.slot_shared[slot] = len(hits)
        self.lengths[slot] = len(hits) * page
        self.stats["prefix_hit_pages"] += len(hits) - shared
        self.stats["prefix_hit_tokens"] += (len(hits) - shared) * page

    def _grow_for_chunk(self, slot: int):
        """Make sure ``slot`` owns every page its next prefill chunk will
        write. Fresh admission allocates the whole context up front, but a
        request resumed from a mid-prefill spill only got its already-
        written pages restored — the remaining stream pages are allocated
        here, chunk by chunk, with the same reclaim-then-steal ladder
        ``_grow`` uses (the needer itself is a valid victim)."""
        page = self.page_size
        while self.active[slot] is not None:
            req = self.active[slot]
            end = min(int(self.lengths[slot]) + self.prefill_token_budget,
                      self._ctx_target(req))
            if kvc.pages_needed(end, page) <= len(self.slot_pages[slot]):
                break
            if self._free_capacity():
                self._alloc(slot, 1)
            elif not self._steal_for(slot):
                break  # pragma: no cover — needer itself is a candidate

    def _pick_prefill_slot(self) -> Optional[int]:
        """The mixed engine's per-step prefill decision: the mid-prefill
        slot whose request has waited longest (the same longest-waiting-
        first key admission uses), or None when every active row is
        decoding. One slot per step — the chunk budget is the fused
        program's prefill lane and it is not split across requests."""
        best = None
        for s, req in enumerate(self.active):
            if req is None or int(self.lengths[s]) >= self._ctx_target(req):
                continue
            key = (req.since, req.seq)
            if best is None or key < best[0]:
                best = (key, s)
        return None if best is None else best[1]

    def step(self):
        """One engine step. Alternating engine (or a mixed step with
        nothing streaming): one decode token for every active slot.
        Mixed engine with a request mid-prefill: the same decode rows
        PLUS up to ``prefill_token_budget`` tokens of that request's next
        chunk ride in one fused jitted program — decode never stalls
        behind a long prompt. Per-slot true lengths, the page table (and
        for enc-dec the cross table / for recurrent families the slab
        ids) ride into the jitted step as inputs — per-row positions and
        length masks, one fixed-shape program per (chunk bucket, table
        bucket). Returns True if any slot made progress.

        Host spans on the profiler's clock: ``serve.step`` around the
        whole call; inside it ``serve.schedule`` (admission — with the
        alternating engine's whole prefill — page growth and the prefill
        pick), then ``serve.prepare`` (host arrays and their transfer),
        ``serve.launch`` (the call into the jitted step), ``serve.sync``
        (the wait for its results), ``serve.commit`` (emit, retire,
        register) and, when it runs, ``serve.audit``."""
        with _span("serve.step"):
            with _span("serve.schedule"):
                plan = self._schedule()
            if plan is None:
                return False
            pf_slot, decoding, pmask = plan
            if pf_slot is not None:
                self._step_mixed(pf_slot, decoding, pmask)
            else:
                self._step_decode(pmask)
            if self.audit_every and self._step_no % self.audit_every == 0:
                with _span("serve.audit"):
                    self.audit()
        return True

    def _schedule(self):
        """The step's host-side decisions: spill budget, admission, page
        growth and the mixed engine's prefill pick. Returns None when no
        slot is active (nothing to launch), else ``(pf_slot, decoding,
        pmask)``: the slot whose chunk rides this step (None: decode
        only), the decoding slots, and the fault plan's poison mask."""
        self._tick += 1
        self._alloc_faulted = (self.faults is not None
                               and self.faults.alloc_blocked(self._tick))
        self._enforce_spill_budget()
        self._admit()
        if self.scheduler == "token_budget":
            self._grow()
        pf_slot = self._pick_prefill_slot() if self._mixed_step else None
        if pf_slot is not None:
            r = self.active[pf_slot]
            if (self._prefix is not None
                    and int(self.lengths[pf_slot])
                    == self.slot_shared[pf_slot] * self.page_size):
                self._extend_shared(
                    pf_slot, list(r.prompt) + list(r.out[:-1]))
            self._grow_for_chunk(pf_slot)
            pf_slot = self._pick_prefill_slot()  # a steal may have hit it
        if not any(self.active):
            return None
        self._step_no += 1
        self.stats["steps"] += 1
        # decoding rows are the active slots at their context target; in
        # the alternating engine that is every active slot (prefill runs
        # to completion inside admission), in the mixed engine mid-prefill
        # slots are excluded — they stream, they don't decode yet
        decoding = [s for s, r in enumerate(self.active) if r is not None
                    and int(self.lengths[s]) >= self._ctx_target(r)]
        self.stats["slot_steps"] += len(decoding)
        if self._prefix is not None:
            # copy-on-write invariant: the page each row's append will
            # requantize (its boundary page — for a mid-prefill row, the
            # first page its next chunk writes) must be private — a shared
            # frozen page in that position would corrupt every other owner
            self._prefix.assert_unfrozen(
                (self.slot_pages[s][int(self.lengths[s]) // self.page_size]
                 for s, r in enumerate(self.active) if r is not None),
                frozen_base=self._frozen_base)
        pmask = (self.faults.poison_rows(self._step_no, self.slots)
                 if self.faults is not None else None)
        return pf_slot, decoding, pmask

    def _step_decode(self, pmask):
        """Pure-decode engine step: every active row is at its context
        target and decodes one token."""
        with _span("serve.prepare"):
            tok = np.zeros((self.slots, 1), dtype=np.int32)
            for s, req in enumerate(self.active):
                if req is not None and req.out:
                    tok[s, 0] = req.out[-1]
                if req is not None:
                    # count = tokens emitted so far = RNG index of this
                    # draw; recomputed from the request each step, so the
                    # stream position survives steals and resumes for free
                    smp.fill_slot(self._samp, s, req.sampling, len(req.out))
                else:
                    smp.clear_slot(self._samp, s)
            poison = (jnp.asarray(pmask) if pmask is not None and pmask.any()
                      else self._no_poison)
            state = self._state_for(slice(None), self.lengths)
            tok = jnp.asarray(tok)
            samp = smp.as_tuple(self._samp)
        with _span("serve.launch"), self._trace_scope():
            nxt_dev, row_ok, self.pools = self._decode(
                self.params, self.pools, tok, state, poison, samp,
                backend=self._backend())
        self.stats["programs"] += 1
        with _span("serve.sync"):
            nxt = np.asarray(nxt_dev)
            okrow = np.asarray(row_ok)
        with _span("serve.commit"):
            for s, req in enumerate(self.active):
                if req is not None:
                    self._finish_decode_row(s, req, okrow[s], nxt[s], pmask)

    def _finish_decode_row(self, s: int, req: Request, ok: bool, nxt,
                           pmask):
        """Commit one decode row's step result: emit / retire, or
        quarantine exactly this request when the in-graph isfinite
        sentinel tripped (its garbage token is never appended; pages/slab
        retire through the normal path) while the rest of the batch keeps
        going."""
        if not ok:
            if pmask is not None and pmask[s]:
                self.faults.note_nan(self._step_no, s, req.rid)
            self._fail_slot(s, req,
                            f"non-finite logits at decode step "
                            f"{self._step_no} (slot {s})")
            return
        self._emit_token(req, int(nxt))
        self.lengths[s] += 1
        self.stats["decoded_tokens"] += 1
        if len(req.out) >= req.max_new or self.lengths[s] >= self.max_seq - 1:
            if len(req.out) < req.max_new:
                # hit the max_seq - 1 context bound: the request ends
                # short of its token budget — flag it instead of
                # retiring silently as if it were satisfied
                req.status = "truncated"
                self.stats["truncated"] += 1
            self._retire(s, req)

    def _step_mixed(self, pf_slot: int, decoding: List[int], pmask):
        """The fused mixed engine step: one jitted program carrying every
        decoding row's next token plus up to ``prefill_token_budget``
        tokens of ``pf_slot``'s next prefill chunk.

        Anatomy: the token row is ``(1, slots + padded)`` — one decode
        token per slot (garbage for non-decoding slots) followed by the
        bucketed chunk. The cache index is the full-batch decode
        PagedState with a nested batch-1 ``prefill`` state for the chunk;
        mid-prefill slots (including ``pf_slot`` itself) ride with their
        lengths zeroed so their garbage decode-lane appends null-redirect
        instead of requantizing a page mid-stream. Logits come back
        ``(slots + 1, V)``: one row per slot plus the chunk's last true
        token, each sampled by its own fixed-trace sampling row and
        covered by its own isfinite quarantine sentinel. Chunk and table
        sizes are power-of-two bucketed by the same _chunk_plan the
        serial loop uses, so trace count stays O(log max_seq)."""
        req = self.active[pf_slot]
        ctx = list(req.prompt) + list(req.out[:-1])
        n = len(ctx)
        pos = int(self.lengths[pf_slot])
        with _span("serve.prepare"):
            take, padded, w, table = self._chunk_plan(
                pf_slot, n, pos, self.prefill_token_budget)
            is_decoding = np.zeros((self.slots,), bool)
            is_decoding[decoding] = True
            tok = np.zeros((1, self.slots + padded), np.int32)
            dec_lengths = np.where(is_decoding, self.lengths,
                                   0).astype(np.int32)
            for s in range(self.slots):
                r = self.active[s]
                if r is not None and is_decoding[s]:
                    tok[0, s] = r.out[-1]
                    smp.fill_slot(self._samp_m, s, r.sampling, len(r.out))
                else:
                    smp.clear_slot(self._samp_m, s)
            tok[0, self.slots: self.slots + take] = ctx[pos: pos + take]
            # the chunk row samples at RNG index len(out): consumed as the
            # stream's seed token only by a fresh request's final chunk —
            # intermediate (and resume re-prefill) draws are discarded, and
            # the stateless fold_in keying means the index is never burned
            smp.fill_slot(self._samp_m, self.slots, req.sampling,
                          len(req.out))
            if pmask is not None and pmask.any():
                # the chunk row inherits pf_slot's poison: a fault injected
                # into the streaming request mid-prefill must trip the
                # chunk row's sentinel (its decode-lane row is garbage and
                # ignored)
                poison = jnp.asarray(
                    np.concatenate([pmask, pmask[pf_slot:pf_slot + 1]]))
            else:
                poison = self._no_poison_m
            pre_state = kvc.PagedState(
                page_table=jnp.asarray(table),
                lengths=jnp.asarray([pos], np.int32),
                chunk_len=jnp.asarray([take], jnp.int32))
            state = self._state_for(slice(None), dec_lengths)
            state = state._replace(prefill=pre_state)
            tok = jnp.asarray(tok)
            samp = smp.as_tuple(self._samp_m)
        if req.t_first_chunk is None:
            req.t_first_chunk = time.perf_counter()
        with _span("serve.launch"), self._trace_scope():
            nxt_dev, row_ok, self.pools = self._decode(
                self.params, self.pools, tok, state, poison, samp,
                backend=self._backend())
        self.stats["programs"] += 1
        self.stats["prefill_steps"] += 1
        self.prefill_traces.add((padded, w))
        with _span("serve.sync"):
            nxt = np.asarray(nxt_dev)
            okrow = np.asarray(row_ok)
        with _span("serve.commit"):
            self.lengths[pf_slot] = pos + take
            self.stats["prefill_tokens"] += take
            if not okrow[self.slots]:
                # non-finite logits in the chunk row: quarantine the
                # streaming request alone. Its pages are NOT registered in
                # the prefix index (frozen garbage would poison every
                # future hit) and no seed token is appended — retire
                # through the normal path so pages/slab accounting stays
                # intact
                if pmask is not None and pmask[pf_slot]:
                    self.faults.note_nan(self._step_no, pf_slot, req.rid)
                self._fail_slot(pf_slot, req,
                                f"non-finite logits during prefill of "
                                f"request {req.rid} ({n} context tokens)",
                                scrub_null=True)
            elif pos + take == n:
                if self._prefix is not None:
                    self._register_prefix(pf_slot, req)
                if not req.out:  # fresh: the final chunk's draw seeds decode
                    self._emit_token(req, int(nxt[self.slots]))
            for s in decoding:
                r = self.active[s]
                if r is not None:
                    self._finish_decode_row(s, r, okrow[s], nxt[s], pmask)

    def run_until_drained(self, max_steps: int = 10_000) -> List[RequestResult]:
        """Step until queue, preempted set and slots are all empty; returns
        one immutable ``RequestResult`` snapshot per request finished during
        this call (in retirement order). The mutable ``Request`` stays the
        engine's working record; callers get the frozen view.

        Starvation guard: if an engine step makes no progress while work is
        still waiting (queued or preempted-but-never-resumed — e.g. the pool
        was fully stolen and nothing can be readmitted), ``strict=True``
        raises ``ServingError`` — carrying the requests that *did* finish
        during this call plus per-request pending diagnostics, so callers
        recover partial results — instead of spinning to ``max_steps`` and
        silently dropping the stragglers. ``strict=False`` instead fails
        exactly the unadmittable requests (``status='failed'`` with the
        starvation diagnostic as ``Request.error``) and completes the
        drain: request-level isolation for production traffic. A step
        blocked only by an injected transient allocator fault is not
        starvation — capacity returns on the next tick."""
        start = len(self.finished)
        for _ in range(max_steps):
            if self.step():
                continue
            if not self.queue and not self.preempted:
                break
            if self._alloc_faulted:
                continue  # injected transient exhaustion, not starvation
            msg = (
                f"serving starved: {len(self.queue)} queued + "
                f"{len(self.preempted)} preempted request(s) cannot be "
                f"(re)admitted with {self._free_capacity()}/{self._n_pages} "
                f"allocatable pool pages (incl. reusable cached) and "
                f"{len(self.free_slabs)}/{self._n_slabs} "
                "slabs free and no active work to retire — the pool is "
                "too small for the waiting context (or pages leaked)")
            if not self.strict:
                self._fail_pending(msg)
                continue  # active rows (if any) still drain normally
            raise ServingError(
                msg,
                finished=[r.result() for r in self.finished[start:]],
                pending=self._pending_diagnostics())
        else:
            pending = (len(self.queue) + len(self.preempted)
                       + sum(r is not None for r in self.active))
            if pending:
                raise ServingError(
                    f"run_until_drained: max_steps={max_steps} exhausted "
                    f"with {pending} request(s) still pending",
                    finished=[r.result() for r in self.finished[start:]],
                    pending=self._pending_diagnostics())
        return [r.result() for r in self.finished[start:]]

    # -- accounting ------------------------------------------------------------
    def audit(self) -> Dict:
        """Full pool-ownership audit: the invariants the scheduler fuzz
        tests assert, promoted to a production check (run it ad hoc, or
        every N decode steps via ``audit_every``). Raises a structured
        ``PoolCorruptionError`` — every violation plus a state dump — if
        anything is broken; returns a summary dict when clean.

        Invariants: page refcounts equal table occupancy; the mapped /
        parked / free sets are pairwise disjoint and partition the pool
        (no leaks, no double-frees); the device page table mirrors the
        host slot lists; each slot's pages are a leading shared-frozen
        registered run followed by exclusively-owned unregistered private
        pages; no active row's boundary (write-target) page is frozen;
        slabs are exclusively owned, owned + free partition the slab
        pool, and the slab table mirrors ownership."""
        from collections import Counter

        v: List[str] = []
        base = self._frozen_base
        all_ids = (list(range(self._n_pages))
                   + list(range(base, base + self._n_frozen)))
        mapped = Counter()
        for ids in self.slot_pages:
            mapped.update(ids)
        for ids in self.slot_cross:
            mapped.update(ids)
        for pid in all_ids:
            if self.page_refs[pid] != mapped.get(pid, 0):
                v.append(f"page {pid}: refcount {int(self.page_refs[pid])} "
                         f"!= {mapped.get(pid, 0)} table mappings")
        free = self.free_pages + self.free_frozen
        parked = self.reusable_pages
        if len(free) != len(set(free)):
            v.append(f"double-freed pages in the free lists: {free}")
        if any(pid >= base for pid in self.free_pages) or \
                any(pid < base for pid in self.free_frozen):
            v.append(f"free-list class mixup: active {self.free_pages} / "
                     f"frozen {self.free_frozen} (frozen base {base})")
        for kind_a, kind_b, inter in (
                ("mapped", "free", set(mapped) & set(free)),
                ("mapped", "parked", set(mapped) & set(parked)),
                ("free", "parked", set(free) & set(parked))):
            if inter:
                v.append(f"pages both {kind_a} and {kind_b}: {sorted(inter)}")
        if sorted(set(mapped) | set(free) | set(parked)) != sorted(all_ids):
            lost = set(all_ids) - set(mapped) - set(free) - set(parked)
            v.append(f"pages leaked from the pool: {sorted(lost)}")
        for slot, ids in enumerate(self.slot_pages):
            if not np.array_equal(self.page_table[slot, :len(ids)], ids):
                v.append(f"slot {slot}: page table "
                         f"{self.page_table[slot, :len(ids)].tolist()} != "
                         f"owned pages {ids}")
            for i, pid in enumerate(ids):
                if i < self.slot_shared[slot]:
                    if self._prefix is None or \
                            not self._prefix.registered(pid):
                        v.append(f"slot {slot}: shared page {pid} not "
                                 "registered in the prefix index")
                    if self._mixed and pid < base:
                        v.append(f"slot {slot}: shared page {pid} is "
                                 "active-class in a mixed-precision pool "
                                 "(freeze-time transcode missed it)")
                else:
                    if self.page_refs[pid] != 1:
                        v.append(f"slot {slot}: private page {pid} has "
                                 f"refcount {int(self.page_refs[pid])} "
                                 "(copy-on-write violated)")
                    if self._prefix is not None and \
                            self._prefix.registered(pid):
                        v.append(f"slot {slot}: private page {pid} is "
                                 "registered (would be written while "
                                 "shared-frozen)")
                    if pid >= base:
                        v.append(f"slot {slot}: private page {pid} is a "
                                 "frozen FP4 logical id — no write path "
                                 "may own a packed page")
        for slot, ids in enumerate(self.slot_cross):
            for pid in ids:
                if pid >= base:
                    v.append(f"slot {slot}: cross page {pid} is a frozen "
                             "FP4 logical id (cross pages live in their "
                             "own write-once pool)")
        if self._prefix is not None:
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                bidx = int(self.lengths[s]) // self.page_size
                if bidx < len(self.slot_pages[s]) and \
                        self._prefix.registered(self.slot_pages[s][bidx]):
                    v.append(f"slot {s}: boundary (write-target) page "
                             f"{self.slot_pages[s][bidx]} is frozen")
        owned = [s for s in self.slot_slab if s >= 0]
        if len(owned) != len(set(owned)):
            v.append(f"slab double-owned: {owned}")
        if sorted(owned + self.free_slabs) != list(range(self._n_slabs)):
            v.append(f"slabs leaked: owned {sorted(owned)} + free "
                     f"{sorted(self.free_slabs)} != 0..{self._n_slabs - 1}")
        for slot in range(self.slots):
            want = self.slot_slab[slot] if self.slot_slab[slot] >= 0 \
                else self._n_slabs
            if int(self.slab_table[slot]) != want:
                v.append(f"slot {slot}: slab table "
                         f"{int(self.slab_table[slot])} != owned {want}")
        if v:
            dump = {
                "step": self._step_no, "tick": self._tick,
                "page_refs": self.page_refs.tolist(),
                "slot_pages": [list(p) for p in self.slot_pages],
                "slot_cross": [list(p) for p in self.slot_cross],
                "slot_shared": list(self.slot_shared),
                "free_pages": list(self.free_pages),
                "free_frozen": list(self.free_frozen),
                "parked_pages": list(parked),
                "slot_slab": list(self.slot_slab),
                "free_slabs": list(self.free_slabs),
                "lengths": self.lengths.tolist(),
                "active_rids": [r.rid if r is not None else None
                                for r in self.active],
            }
            raise PoolCorruptionError(v, dump)
        return {"step": self._step_no,
                "pages_mapped": len(mapped), "pages_free": len(free),
                "pages_parked": len(parked),
                "frozen_mapped": sum(1 for pid in mapped if pid >= base),
                "frozen_free": len(self.free_frozen),
                "slabs_owned": len(owned),
                "slabs_free": len(self.free_slabs),
                "active": sum(r is not None for r in self.active),
                "violations": 0}

    def utilization(self) -> float:
        """Mean fraction of slots that decoded per engine step — the number
        the token-budget scheduler raises under long-tail max_new."""
        if not self.stats["steps"]:
            return 0.0
        return self.stats["slot_steps"] / (self.stats["steps"] * self.slots)

    def engine_utilization(self) -> float:
        """Decoded tokens per jitted program launch, normalized by slot
        count — the whole-engine number the mixed step raises over the
        alternating engine. The alternating engine spends entire programs
        on serial prefill chunks that decode nothing; the mixed engine
        piggybacks those chunks on decode steps, so every launch carries
        the full decode batch. Counts every launch: encode, prefill
        chunks, decode and mixed steps."""
        if not self.stats["programs"]:
            return 0.0
        return (self.stats["decoded_tokens"]
                / (self.stats["programs"] * self.slots))

    @property
    def reusable_pages(self) -> List[int]:
        """Refcount-0 registered pages parked in the prefix cache's LRU:
        allocatable like free pages, still bit-reusable by a matching
        prefix until reclaimed."""
        return self._prefix.reusable_ids() if self._prefix is not None else []

    def prefix_hit_rate(self) -> float:
        """Fraction of prefilled-or-hit context tokens served straight
        from the prefix cache (zero prefill compute)."""
        total = self.stats["prefix_hit_tokens"] + self.stats["prefill_tokens"]
        return self.stats["prefix_hit_tokens"] / total if total else 0.0

    def kv_bytes_per_token(self) -> float:
        """Pool bytes per token slot across the whole layer stack (page
        units only) — the number the FP8 pool halves vs bf16."""
        return sum(kvc.pool_bytes_per_token(self._unit(path))
                   for path, kind in self._units if kind in ("kv", "cross"))

    def kv_bf16_bytes_per_token(self) -> float:
        return sum(kvc.bf16_bytes_per_token(self._unit(path))
                   for path, kind in self._units if kind in ("kv", "cross"))

    def cache_residency(self) -> Dict:
        """Per-class residency accounting for the mixed-precision cache:
        how many pages of each class hold live (mapped or parked-reusable)
        content, what they cost per token, and the blended bytes-per-token
        across everything resident. ``frozen_bytes_per_token`` /
        ``active_bytes_per_token`` is the page-class density ratio the
        serving bench gates (<= 0.55 for packed FP4 behind FP8)."""
        kv_units = [path for path, kind in self._units
                    if kind in ("kv", "cross")]
        page = self.page_size
        active_pb = sum(kvc.page_bytes(self._unit(p)) for p in kv_units)
        frozen_pb = sum(kvc.page_bytes(self._unit(p), frozen=True)
                        for p in kv_units)
        parked = set(self.reusable_pages)
        base = self._frozen_base
        n_active = sum(1 for pid in range(self._n_pages)
                       if self.page_refs[pid] > 0 or pid in parked)
        n_frozen = sum(1 for pid in range(base, base + self._n_frozen)
                       if self.page_refs[pid] > 0 or pid in parked)
        live_bytes = n_active * active_pb + n_frozen * frozen_pb
        tokens = (n_active + n_frozen) * page
        return {
            "n_active_live": int(n_active),
            "n_frozen_live": int(n_frozen),
            "active_bytes_per_token": active_pb / page if page else 0.0,
            "frozen_bytes_per_token": (frozen_pb / page
                                       if self._mixed else 0.0),
            "live_bytes": float(live_bytes),
            "resident_tokens": int(tokens),
            "bytes_per_token": float(live_bytes / tokens) if tokens else 0.0,
        }
