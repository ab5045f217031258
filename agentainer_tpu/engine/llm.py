"""Continuous-batching JAX inference engine — the heart of the data plane.

Replaces the reference's external LLM calls (examples/gpt-agent/app.py:98-109
POSTs to OpenAI) with an in-process prefill+decode engine on the agent's
TPU chips (BASELINE.json configs #2/#3). TPU-first design decisions:

- **one compiled decode step, static shapes**: a fixed slot-batch
  ``[max_batch]`` decodes every active sequence each step at its own cache
  position (ragged positions via the model's scatter cache); idle slots
  write to a reserved scratch slot — no recompiles as requests come and go;
- **bucketed prefill**: prompts pad up to power-of-two buckets so prefill
  compiles a handful of shapes, padding writes land on positions later
  overwritten before any query can attend to them;
- **TTFT = prefill**: the first token is sampled from the prefill logits,
  so time-to-first-token is one prefill pass, not prefill + a decode step;
- **sessions own KV**: a chat session keeps its cache slot between turns
  (multi-turn TTFT stays flat); idle sessions evict LRU when slots run out;
- **idempotent by request id**: completed results are memoized, so a
  journal replay that races the original returns the stored result instead
  of generating twice (the engine-side half of the crash-replay contract).

The engine runs its JAX work on a dedicated worker thread; the aiohttp
handlers (engine/llm_serve.py) talk to it through a thread-safe queue and
asyncio futures.
"""

from __future__ import annotations

import asyncio
import collections
import functools
import os
import queue
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Partitionable threefry, set before any engine program is traced: the
# legacy (non-partitionable) implementation computes WRONG values when a
# random-init is jitted with out_shardings over a mesh with more than one
# nontrivial axis and a spec that uses only a subset of them (jax 0.4.37:
# P("tp", None) on a mesh with a second axis silently corrupted the embed
# table, and the engine decoded garbage).
# Partitionable threefry is sharding-invariant by construction. It changes
# the random stream, so every in-process engine/model comparison shares
# the new stream; no test pins absolute values from the old one.
jax.config.update("jax_threefry_partitionable", True)

from .. import faults
from ..core.spec import unserved_layout
from ..models.configs import ModelConfig, get_config
from ..models.llama import (
    KVCache,
    PagedKVCache,
    _moe_mlp,
    _moe_mlp_routed,
    forward,
    init_cache,
    init_params,
    moe_sorted_from,
    ring_plan,
)
from ..ops.attention import pages_to_rows, plan_cache_attention, rows_to_pages
from ..ops.moe import kernel_by_default, row_tile, sorted_rows
from ..utils.launches import Launches
from ..utils.spans import Spans
from .sampling import NEG_INF, sample, sample_step
from .tokenizer import load_tokenizer

PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024)
# The step programs' XLA module names, as a device trace shows them. They
# live here once: ``_step_jit`` names each program by them, the launch ledger
# (``utils/launches.py``, ``/metrics`` ``launches``) counts under them, so
# ledger and trace join by name, and ``tests/test_spans.py`` pins them
# against the lowered programs.
JIT_PREFILL = "jit_prefill"
JIT_DECODE_N = "jit_decode_n"
JIT_PREFILL_WITH_DECODE = "jit_prefill_with_decode"
JIT_FIRST_TOKEN = "jit_first_token"
JIT_VERIFY = "jit_verify"
JIT_FUSED = "jit_fused_body"
# the programs whose launch steps the decode lanes (``decode_steps`` counts
# them) and those that feed a prompt's chunk (``prefill_launches``)
_DECODE_PROGRAMS = (JIT_DECODE_N, JIT_PREFILL_WITH_DECODE, JIT_VERIFY, JIT_FUSED)
_PREFILL_PROGRAMS = (JIT_PREFILL, JIT_PREFILL_WITH_DECODE)

# Paged KV arena (block tables): the pool's page granularity in tokens.
# 64 keeps every PREFILL_BUCKET level ≥ 64 page-aligned (zero-copy prefix
# sharing with no partial tail) while a near-empty session pins one page,
# not a whole max_seq slot.
PAGE_SIZE_DEFAULT = 64

# Self-speculative decoding (prompt-lookup drafting + batched multi-token
# verification). The verify ladder mirrors the decode-chunk ladder: one
# compiled k-token verify program per bucket, warmed at startup, the round's
# bucket chosen as the smallest covering the longest draft in the batch.
SPEC_VERIFY_BUCKETS = (2, 4, 8)

# In-loop device speculation (ISSUE 17): the fused while_loop's own n-gram
# drafter matches each lane's trailing 3/2-gram against a fixed window of
# its recent token history (carried ON DEVICE across loops) and verifies up
# to FUSED_SPEC_K drafted tokens as a batched branch of the same loop body —
# the lane never exits the loop to speculate. Window width trades match
# recall against per-iteration compare cost ([B, W, 3] equality — trivial
# next to a forward); 64 covers the tool-call/JSON span lengths the host
# drafter feeds on.
FUSED_HIST_W = 64
FUSED_SPEC_K = 4
# Dynamic fused rung: the loop bound is a RUNTIME operand, so one compiled
# executable serves every rung and the uncontended dispatch rides a rung
# this many times the configured decode_chunk — amortizing per-dispatch
# overhead (host bookkeeping, transfers, readback processing) that the
# b1/b4 decode-loop bench showed dominating fused ITL.
FUSED_RUNG_MULT = 4
# acceptance-rate EMA: fast-collapsing (a handful of all-rejected rounds
# sends gamma to 0) so adversarial/low-match traffic degrades to the plain
# decode ladder instead of paying verify forwards that never accept
SPEC_EMA_ALPHA = 0.4
SPEC_EMA_FLOOR = 0.125
# consecutive draft-lookup misses before a lane stops triggering the
# (pipeline-draining) speculation path; collapsed/missing lanes re-probe
# every SPEC_PROBE_EVERY decode steps so a workload shift is noticed
SPEC_MISS_BACKOFF = 4
SPEC_PROBE_EVERY = 32
# the drafter's reverse n-gram scan is pure Python on the worker thread,
# serialized inside the (synchronous) verify round: cap how far back it
# looks so a 4096-token context can't turn every lookup miss into
# milliseconds of host stall on the decode critical path
SPEC_LOOKUP_WINDOW = 1024


# -- what a family's cache can hold, decided in ONE place -------------------
#
# A K/V arena's rows are positional: they can be truncated (speculative
# rewind), copied at a boundary (prefix forks), paged, overwritten by a
# parked lane's garbage and moved off the device and back, all harmlessly.
# A recurrent state (the hybrid block's KDA or GDN layers) is one value per lane
# that only ever moves forward: none of that holds for it. The features
# below either work for a family or are OFF for it with the reason given —
# logged at build and reported in ``/metrics`` (``cache``) — and asking for
# one explicitly is an error at build, never a silent fallback.
_RECURRENT_OFF = {
    "speculative": "a rejected draft would have to rewind the recurrent state",
    "fused_decode": "the fused loop's in-loop speculation rewinds, and its masks are not the state's",
    "paged_kv": "a per-lane state has no pages; the positional rows (latent, or k and v) stay a dense arena",
    "kv_tiering": "the host tier moves k and v only, never a state beside them",
    "prefix_cache": "a fork needs the state AT the boundary, and none is kept there",
    "mesh": "the hybrid block is served on one chip (a share of the experts, where it has any, is cfg.experts_held)",
}


# A window layer's leaf is a ring (models/llama.WindowKVCache): the row of
# position p is ``p mod R``, a lane keeps its last R rows of such a layer, and
# a launch may write at most ``R - window + 1`` rows of one lane. Its rows are
# positional, so snapshots, restores, eviction, re-admission, parking and the
# mixed step hold (the ring ships whole). What reads or copies rows BY POSITION
# FROM 0, or writes more rows a launch than the ring was sized for, does not,
# until the mechanism named with it exists (ROADMAP Reach B2).
_WINDOW_OFF = {
    "speculative": "the verify launch and its rewind are not taken through the ring: its rows a launch (and the fused rung's) are not in the ring's size, and no verify program reads the ring's lower bound",
    "fused_decode": "the fused loop's in-loop speculation writes drafted rows and rewinds; its masks are the dense arena's, not the ring's",
    "paged_kv": "the page pool gives every layer a page for every position; window layers would need a pool that takes a page back once the window has passed it",
    "kv_tiering": "the host tier moves k and v rows from position 0; a ring leaf beside them is not parked or promoted",
    "prefix_cache": "a fork copies rows 0..n of every layer into a fresh lane; a ring keeps only the last R rows, so a fork would have to copy the last R rows at the boundary, and none are kept there",
    "mesh": "the shard_map'd kernels and GSPMD's einsum path take the dense arena; the ring's index maps are the one-chip kernels'",
}


# A cache of latent rows and nothing else (the hybrid block with MLA in every
# layer and no linear mixer): one ``[n_mla, B, S, W]`` leaf of POSITIONAL rows,
# no per-lane state. Truncating, copying at a boundary, paging, parking or
# overwriting such rows would all be harmless, as for k and v: none of the
# reasons in ``_RECURRENT_OFF`` holds. What keeps each feature off is that its
# mechanism was written for the pair of leaves (k, v) and has not been taken
# through a named latent leaf (ROADMAP Reach B3).
_LATENT_OFF = {
    "speculative": "the verify program and its rewind read and truncate k and v leaves; no verify step is built over the latent leaf (a rejected draft's rows would be overwritten harmlessly)",
    "fused_decode": "the fused loop carries k and v and speculates inside the loop; it has no body over the latent leaf",
    "paged_kv": "the page pool and its block tables hold k and v pages; the latent kernels' index maps address a dense stack, not a table of pages",
    "kv_tiering": "the host tier parks and promotes k and v rows; a latent leaf is not in its transfers (a lane's rows would move like any positional rows)",
    "prefix_cache": "the prefix arena copies k and v rows 0..n into a fresh lane; a fork of latent rows at a boundary would be as harmless, and no program copies the latent leaf",
    "mesh": "the hybrid block is served on one chip (a share of the experts is cfg.experts_held); the latent kernels have no shard_map form",
}


def cache_features(cfg: ModelConfig, asked: dict) -> tuple[dict, dict]:
    """``asked``: feature → what the caller gave (``None``: nothing, take
    the default). Returns (feature → on/off, feature → reason it is off for
    this family). Raises where a feature this family's cache cannot hold was
    asked for by name."""
    defaults = {"speculative": True, "prefix_cache": True}
    off, what = _cache_off(cfg)
    if off is None:
        return {k: bool(defaults.get(k, False) if v is None else v) for k, v in asked.items()}, {}
    refused = [k for k, v in asked.items() if v]
    if refused:
        raise ValueError(
            f"model {cfg.name!r} {what}; not served with it: "
            + "; ".join(f"{k} ({off[k]})" for k in refused)
        )
    return {k: False for k in asked}, {k: off[k] for k in asked}


def _cache_off(cfg: ModelConfig) -> tuple[dict | None, str]:
    """The features a family's cache turns off, with what the cache is: chosen
    by the cache's leaves (a ring, a per-lane state, a latent leaf), whichever
    block builds them."""
    if cfg.n_window:
        return _WINDOW_OFF, "keeps its window layers' rows in a ring"
    if cfg.linear_kind is not None:
        return _RECURRENT_OFF, "keeps a recurrent state in its cache"
    if cfg.is_hybrid:
        return _LATENT_OFF, "keeps latent rows in a leaf the k/v mechanisms do not take"
    return None, ""


def fleet_default_applies(config_name: str, feature: str) -> bool:
    """Whether a policy of the daemon's (its ``ATPU_*`` write-back, read by the
    serving shim) reaches a deployment of ``config_name``. Of the three that
    travel so, ``kv_tiering`` alone is something a cache can refuse: there the
    daemon's default is nobody asking, so it falls away with its reason
    reported; ``model.options`` of the deployment itself still is an ask."""
    try:
        cfg = get_config(config_name)
    except KeyError:
        return True
    return feature not in (_cache_off(cfg)[0] or ())


class SnapshotDeferred(Exception):
    """KV snapshot postponed: the engine is busy (or the global limiter is
    saturated) and durability is not yet overdue. Retry on a later turn."""


class EngineShutdown(RuntimeError):
    """The engine worker is gone; queued work can never complete. Raised
    into every abandoned future instead of letting callers hang forever."""


class RequestAborted(RuntimeError):
    """Base for per-request terminations that are POLICY, not faults: the
    request will never produce (more) tokens because nobody is waiting for
    them. Passed through to callers typed (like EngineShutdown) so the
    serve layer can map each to its HTTP status."""


class RequestExpired(RequestAborted):
    """Deadline passed before (or while) the request was served."""


class RequestCancelled(RequestAborted):
    """Explicit cancel(request_id) — client disconnected or operator abort."""


class EngineOverloaded(RuntimeError):
    """Submit-time shed: queue+waiting+active depth crossed the watermark.
    Raised synchronously from generate() BEFORE enqueueing, so overload
    backpressure costs the caller nothing but this exception. Carries a
    retry hint for the 429 Retry-After header."""

    def __init__(self, depth: int, watermark: int, retry_after_s: float = 1.0):
        super().__init__(f"engine overloaded: depth {depth} >= watermark {watermark}")
        self.depth = depth
        self.watermark = watermark
        self.retry_after_s = retry_after_s


class PagePoolExhausted(EngineOverloaded):
    """Paged-arena allocation failed even after evicting idle residents:
    the pool is genuinely full of in-flight + pinned pages. A POLICY
    backpressure signal, not a fault — subclasses EngineOverloaded so the
    serve layer maps it to 429 + Retry-After and the journal keeps the
    entry replayable (no acked loss)."""

    def __init__(self, need: int, free: int):
        super().__init__(depth=need, watermark=free)
        self.args = (
            f"KV page pool exhausted: need {need} page(s), {free} free",
        )


class TierPromoteFailed(EngineOverloaded):
    """Host-tier promotion failed (injected engine.kv_promote fault, or
    the pool couldn't fit the swap-in even after pressure demotion): the
    session STAYS parked — its context is preserved — and the triggering
    turn surfaces as 429 + Retry-After, so a retry finds the session
    still promotable. Subclasses EngineOverloaded for the same policy
    mapping as genuine pool exhaustion."""

    def __init__(self, session: str):
        super().__init__(depth=0, watermark=0)
        self.args = (f"KV tier promotion failed for session {session!r}",)


class EngineDraining(RuntimeError):
    """SIGTERM drain in progress: no new admissions; in-flight work is
    being finished and sessions snapshotted before exit."""


class PrefillFailed(RuntimeError):
    """Prefill broke for ONE request while the engine survived (the worker
    loop's per-request isolation). For a fixed prompt this is essentially
    deterministic — a poisoned input, not a transient — so the serve layer
    marks the 500 with PREFILL_POISON_HEADER and the proxy charges poison
    accounting (two strikes dead-letters the journal entry) instead of
    riding the full respawn/backoff ladder."""


class RidersFault(Exception):
    """The decode failpoint fired before a prefill chunk's launch that was
    to carry the decode lanes: the lanes' fault (``__cause__``), batch-wide
    like any decode fault, not the prompt's."""


def _as_prefill_failure(e: Exception) -> Exception:
    """Classify a prefill-tick exception: policy terminations pass through
    typed (they map to their own HTTP statuses); anything else becomes
    PrefillFailed."""
    if isinstance(e, (RequestAborted, EngineOverloaded, EngineShutdown)):
        return e
    return PrefillFailed(f"{type(e).__name__}: {e}")


def _step_jit(module: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` as the XLA module ``module`` (JAX names a module
    ``jit_`` + the function's name), whichever variant of the step ``fn`` is."""
    fn.__name__ = module.removeprefix("jit_")
    return jax.jit(fn, **jit_kwargs)


def _phase(name: str, attrs=None):
    """Run a worker-thread method of the engine under the span ``name``.
    ``attrs(self, *args)``: what the span's trace event carries (read from a
    capture; other spans with attributes are opened inline)."""

    def wrap(fn):
        @functools.wraps(fn)
        def timed(self, *args, **kwargs):
            with self._spans.span(name, **(attrs(self, *args) if attrs else {})):
                return fn(self, *args, **kwargs)

        return timed

    return wrap


def _sharded_random_init(cfg: ModelConfig, dtype, mesh, specs: dict) -> dict:
    """Random-init DIRECTLY into shards: ``jit(init, out_shardings=...)``
    makes every chip allocate only its own slice of every weight, so a
    meshed engine whose model needs more than one chip's HBM never
    materializes the whole pytree on the default device first (VERDICT r3
    missing #3 — init-then-reshard OOMs chip 0 exactly when tp matters).
    """
    from ..parallel.sharding import shardings_from_specs

    shardings = shardings_from_specs(mesh, specs)
    fn = jax.jit(lambda k: init_params(cfg, k, dtype=dtype), out_shardings=shardings)
    return fn(jax.random.PRNGKey(0))


@dataclass
class GenRequest:
    id: str
    session: str
    prompt_ids: list[int]
    max_tokens: int
    temperature: float
    loop: asyncio.AbstractEventLoop
    future: asyncio.Future
    # absolute wall-clock give-up instant (None = no deadline): checked at
    # admission (fail fast before prefill) and per worker iteration while
    # in flight (park the lane, free the slot)
    deadline_at: float | None = None
    submitted_at: float = field(default_factory=time.monotonic)
    prefill_started_at: float | None = None
    # final prefill chunk + first-token injection dispatched; the tail of
    # TTFT after this instant is pure device/readback latency
    prefill_done_at: float | None = None
    ttft_ms: float | None = None
    # keep generating through EOS until max_tokens (benchmarks/load tests
    # that need a stream of fixed length; tiny random-weight models hit EOS
    # whenever argmax lands on it)
    ignore_eos: bool = False
    # nucleus/top-k filters, per request (0 / 1.0 = disabled): live in the
    # device carry as per-lane arrays so one compiled sampler serves a
    # batch mixing filtered and unfiltered lanes
    top_k: int = 0
    top_p: float = 1.0
    generated: list[int] = field(default_factory=list)
    # tokens sampled device-side so far (first token + dispatched decode
    # steps, including in-flight chunks): the remaining budget bounds how
    # large a decode chunk is worth dispatching
    dispatched: int = 0
    # SSE streaming: called from the worker thread as `emit(start, ids)`
    # right after tokens land in `generated` (start = offset of ids[0]).
    # Batches arrive FIFO and contiguous — the single worker thread is the
    # only appender. None (the default, and every buffered request) keeps
    # the readback paths byte-identical to pre-streaming behavior.
    emit: Any = None

    def emit_appended(self, n_new: int) -> None:
        """Report the last ``n_new`` tokens of ``generated`` to the emit
        callback (no-op without one). Never raises into the worker loop: a
        dead stream consumer must not fail the generation — the buffered
        result is still the journal's archive."""
        if self.emit is None or n_new <= 0:
            return
        try:
            self.emit(len(self.generated) - n_new, self.generated[-n_new:])
        except Exception:
            pass


@dataclass
class RestoreCmd:
    """Worker-queue command: write a KV snapshot into a slot (restores a
    session after an engine restart — BASELINE.json config #3)."""

    session: str
    k: Any  # np [L, pos, KV, hd]
    v: Any
    position: int
    pending_token: int | None
    loop: asyncio.AbstractEventLoop
    future: asyncio.Future
    # every leaf of the snapshot by name (a K/V family's are k and v above)
    leaves: dict | None = None


@dataclass
class SnapshotCmd:
    """Worker-queue command: stage a session's KV prefix into fresh device
    buffers (fixed bucket shapes) for host serialization. Running on the
    worker thread makes it race-free against the donating decode/prefill
    dispatches — the staged output buffers are new arrays that survive any
    later donation of the cache itself (VERDICT r4 weak #2: a snapshot
    thread's captured cache reference was invalidated by the next decode)."""

    session: str
    loop: asyncio.AbstractEventLoop
    future: asyncio.Future


@dataclass
class ParkCmd:
    """Worker-queue command: demote an idle session's KV off the device
    into the host RAM tier (kv_tiering). Resolves with the exact staged
    (k, v, position, pending_token) host arrays — the caller packs them
    into the store-durable SNAP_VERSION 3 blob (the cold tier) — or None
    when the session is unknown/busy or the demote failpoint fired."""

    session: str
    loop: asyncio.AbstractEventLoop
    future: asyncio.Future


@dataclass
class PrewarmCmd:
    """Worker-queue command: promote a host-tier session back onto the
    device AHEAD of its next turn (the proxy's next-arrival hint), so the
    returning request admits against already-resident KV. Resolves True
    when the session is device-resident afterwards."""

    session: str
    loop: asyncio.AbstractEventLoop
    future: asyncio.Future


@dataclass
class TieredEntry:
    """One parked session in the host RAM tier. ``k``/``v`` hold the
    position-trimmed KV prefix as host numpy — either the cache's exact
    dtype (tier_quantize=0) or int8 page tensors with per-page scales
    (``k_scale``/``v_scale``; 2–4x density at a bounded rounding cost).
    Self-speculation state parks with the KV so a promoted session drafts
    exactly like one that never left the device."""

    k: Any
    v: Any
    position: int
    pending_token: int | None
    nbytes: int
    parked_at: float
    quantized: bool = False
    k_scale: Any = None
    v_scale: Any = None
    pages: int = 0
    spec_hist: list[int] = field(default_factory=list)
    spec_ema: float = 1.0
    spec_miss: int = 0


@dataclass
class PrefixEntry:
    """One cached token-prefix in the prefix arena: the KV a prefill wrote
    for ``tokens`` (exact bucket length), held in fresh device buffers that
    outlive any later donation of the main cache. ``tokens`` is kept so a
    lookup verifies exact token equality — a rolling-hash collision must
    degrade to a miss, never serve another prompt's context."""

    k: Any  # [L, bucket, KV, hd], compute dtype (exact — no fp16 round-trip)
    v: Any
    tokens: tuple
    nbytes: int
    created: float
    last_used: float
    hits: int = 0
    # paged arena: instead of private k/v buffers the entry PINS pool
    # pages (refcounted, read-only) — zero-copy registration and forking.
    # A non-page-aligned level additionally owns one copied tail page
    # holding the partial last page (``tail_len`` live tokens).
    pages: list[int] | None = None
    tail_page: int | None = None
    tail_len: int = 0


@dataclass
class PagedSession:
    """A resident session in the paged arena: its KV lives in ``pages``
    (physical page ids, logical order), NOT in a lane — so a session
    between turns holds only its pages' HBM and zero compute lanes, and
    residency is bounded by the pool, not ``max_batch``. ``pages[:shared]``
    are refcount-shared prefix pages mapped read-only (the session never
    writes below its fork point, so sharing needs no guard beyond the
    partial-tail copy-on-write done at fork time)."""

    name: str
    pages: list[int] = field(default_factory=list)
    shared: int = 0
    position: int = 0
    pending_token: int | None = None
    # bound compute lane while a request is in flight; None between turns
    lane: int | None = None
    last_used: float = 0.0
    # admission-time pending token AND position, kept so a pool-exhaustion
    # failure can roll the session back to its pre-request state instead of
    # dropping it (position advances mid-request: the prefix map sets it at
    # admission and every speculative accept syncs it — neither belongs to
    # a request that ultimately failed with 429)
    admit_pending: int | None = None
    admit_position: int = 0
    admit_spec_hist: list[int] = field(default_factory=list)
    # self-speculation state persists across turns WITH the session (the
    # lane mirrors it while bound and syncs back at finish)
    spec_hist: list[int] = field(default_factory=list)
    spec_ema: float = 1.0
    spec_miss: int = 0


@dataclass
class Slot:
    idx: int
    session: str = ""
    position: int = 0  # next cache position to write
    # fresh-context prompts (prefill starting from position 0) are tracked
    # here so the final prefill chunk can register their bucket-prefixes in
    # the prefix arena; continuing sessions carry None (their context since
    # position 0 is not reconstructible from the request alone)
    prefix_ctx: list[int] | None = None
    request: GenRequest | None = None
    # prompt tokens not yet prefilled: chunked prefill feeds these through
    # the model a chunk at a time, interleaved with decode steps, so one
    # long prompt can't stall every active generation's ITL
    pending_prompt: list[int] = field(default_factory=list)
    last_used: float = 0.0
    # the final sampled token of the previous reply was never fed through the
    # model; it is prepended to the session's next prompt so the KV context
    # stays exact across turns
    pending_token: int | None = None
    # bumped whenever the slot is reassigned or its position resets; lets a
    # concurrent snapshot detect that its prefix went stale mid-serialize
    epoch: int = 0
    # host mirror of the DEVICE-side decode position for this slot's lane
    # (the pipelined decode chains positions on device; chunks already in
    # flight were dispatched at this offset)
    dev_position: int = 0
    # decoding = this slot's lane in the device carry is live (its first
    # token was injected and decode chunks are advancing it)
    decoding: bool = False
    # self-speculation state: the token stream fed through this slot's KV
    # across the session's turns (the drafter's lookup corpus), the
    # acceptance-rate EMA driving per-lane draft length, and lookup-miss /
    # probe bookkeeping bounding speculation's cost on low-match traffic
    spec_hist: list[int] = field(default_factory=list)
    spec_ema: float = 1.0
    spec_miss: int = 0
    spec_probe_at: int = -(10**9)
    # paged arena: the PagedSession bound to this lane while a request is
    # in flight (None in dense mode and between turns)
    psess: PagedSession | None = None


class LLMEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        tokenizer,
        max_batch: int,
        max_seq: int,
        decode_chunk: int = 8,
        prefill_chunk: int = 256,
        tp: int = 1,
        ep: int = 1,
        devices: list | None = None,
        mesh=None,
        routed_moe: bool | None = None,
        moe_capacity_factor: float = 2.0,
        adaptive_decode: bool = True,
        prefix_cache: bool | None = None,
        prefix_cache_bytes: int = 0,
        deadlines: bool = True,
        shed_watermark: int = 0,
        speculative: bool | None = None,
        spec_gamma_max: int = 8,
        paged_kv: bool = False,
        page_size: int = PAGE_SIZE_DEFAULT,
        kv_pages: int = 0,
        fused_decode: bool = False,
        inloop_spec: bool = True,
        approx_topk: bool = False,
        kv_tiering: bool = False,
        tier_quantize: int = 1,
        streaming: bool = False,
    ):
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.max_batch = max_batch
        # what this family's cache can hold (``cache_features``): None means
        # "not asked", so a default never trips the refusal
        feats, self._cache_off = cache_features(
            cfg,
            {
                "speculative": speculative,
                "prefix_cache": prefix_cache,
                "paged_kv": paged_kv or None,
                "fused_decode": fused_decode or None,
                "kv_tiering": kv_tiering or None,
                "mesh": (max(1, tp) * max(1, ep) > 1) or None,
            },
        )
        speculative, prefix_cache = feats["speculative"], feats["prefix_cache"]
        # the hybrid block: its own plan, cache pytree and per-lane decode
        # controls (``stop``, ``eos``), whatever its layers' kinds
        self._hybrid = cfg.is_hybrid
        # a per-lane state that only moves forward (a linear mixer's): what
        # admission zeroes for a fresh context
        self._recurrent = cfg.linear_kind is not None
        # window layers beside global ones: a ring leaf beside the arena
        # (the K/V block's ``WindowKVCache`` or the hybrid block's ``wk``, ``wv``)
        self._windowed = bool(cfg.n_window)
        # a cache of NAMED leaves (the hybrid block's, or k, v + the ring):
        # snapshots and restores move a dict of them, not the pair (k, v)
        self._named_leaves = self._hybrid or self._windowed
        if self._cache_off:
            kinds = (
                f"kinds={'+'.join(sorted(set(cfg.layer_kinds)))} "
                + ("(positional rows + per-lane state)" if self._recurrent else "(positional rows, no per-lane state)")
                + (f" + a ring of the last rows x{cfg.n_window} (window {cfg.window})" if self._windowed else "")
                if self._hybrid
                else f"global rows x{cfg.n_global} + a ring of the last rows x{cfg.n_window} (window {cfg.window})"
            )
            print(
                f"[llm-engine] cache: {kinds}; off for this family: "
                + "; ".join(f"{k}: {v}" for k, v in self._cache_off.items()),
                flush=True,
            )
        # Paged KV arena (block tables): sessions hold lists of fixed-size
        # pages from a global pool instead of dense [max_seq] slots, so
        # resident sessions are bounded by the pool, prefix sharing maps
        # refcounted pages zero-copy, and speculative rewind truncates page
        # tails. paged_kv=False keeps the dense arena — the A/B baseline
        # (mirrors adaptive_decode / prefix_cache / speculative).
        self.paged = bool(paged_kv)
        # Fused on-device decode loop: a per-ladder-rung compiled
        # lax.while_loop runs up to `chunk` forward+sample+append steps
        # entirely on device (per-lane EOS/budget masking, whole-batch
        # early exit) with ONE readback at loop exit — the per-chunk
        # host sync the ladder only shrank. fused_decode=False keeps the
        # per-chunk scan dispatch exactly as-is (the A/B baseline).
        self.fused_decode = bool(fused_decode)
        # Segmented approx top-k sampler (opt-in; exact shared-sort sampler
        # is the default). Static per engine: it picks which sample_step
        # pipeline every compiled decode path bakes in.
        self.approx_topk = bool(approx_topk)
        # SSE token streaming (opt-in): gates whether the serve layer
        # honors stream=true on /chat. The engine side is just the
        # per-request emit callback — sampling/batching are untouched, so
        # streaming=False keeps buffered behavior byte-identical.
        self.streaming = bool(streaming)
        self.page_size = max(8, int(page_size or PAGE_SIZE_DEFAULT))
        if self.paged:
            # the logical arena must tile exactly into pages
            max_seq = (
                (max_seq + self.page_size - 1) // self.page_size
            ) * self.page_size
        self.max_seq = max_seq
        # pages per full logical sequence (the block-table width)
        self._n_blocks = max(1, self.max_seq // self.page_size)
        # pool sizing: default matches the dense arena's HBM exactly
        # (max_batch × max_seq tokens of KV) so paged-vs-dense capacity is
        # an apples-to-apples A/B at unchanged budget; +max_batch dedicated
        # scratch pages (one per lane) absorb parked-lane and padding
        # writes without ever touching a session's pages
        self._data_pages = (
            max(1, int(kv_pages)) if kv_pages else max_batch * self._n_blocks
        )
        self._total_pages = self._data_pages + max_batch
        self.decode_chunk = max(1, decode_chunk)
        # Adaptive decode-chunk policy (admission-aware scheduling): a small
        # ladder of kernel-looped chunk sizes is compiled at warmup; the
        # dispatcher shrinks to the smallest bucket while anyone is waiting
        # for admission/prefill (the fixed chunk wall WAS the ~180 ms
        # admission half of single-chip TTFT) and reverts to the full chunk
        # at steady state so ITL/HBM efficiency is untouched.
        self.adaptive_decode = bool(adaptive_decode)
        if self.adaptive_decode:
            ladder = {self.decode_chunk}
            c = 1
            while c < self.decode_chunk:
                ladder.add(c)
                c *= 2
            self._decode_ladder = sorted(ladder)
        else:
            self._decode_ladder = [self.decode_chunk]
        # snap DOWN to a bucket: a non-bucket chunk size would pad every
        # non-final chunk up to the next bucket (wasted prefill compute)
        clamped = min(max(PREFILL_BUCKETS[0], prefill_chunk), PREFILL_BUCKETS[-1])
        self.prefill_chunk = max(b for b in PREFILL_BUCKETS if b <= clamped)
        self.tp = max(1, tp)
        self.ep = max(1, ep)
        # routed (token-dispatch) MoE is the default wherever experts shard
        # over ep — the dense path would burn ~E/k× the MLP FLOPs there
        # (VERDICT r3 missing #5); single-chip keeps the dense fallback
        # unless asked (options.routed)
        self.routed_moe = (
            cfg.is_moe and (self.ep > 1 if routed_moe is None else bool(routed_moe))
        )
        self.moe_capacity_factor = float(moe_capacity_factor)
        self.scratch_pos = max_seq - 1  # idle-slot write target; never generated into
        dtype = params["final_norm"].dtype  # always dense, even when quantized
        if self.paged:
            # page pool [L, P, KV, page_size, hd]: same two-leaf pytree
            # discipline as the dense arena, so scan/donation/sharding
            # machinery applies unchanged
            cache_shape = (
                cfg.n_layers,
                self._total_pages,
                cfg.n_kv_heads,
                self.page_size,
                cfg.head_dim,
            )
        else:
            cache_shape = (cfg.n_layers, max_batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        if self.tp * self.ep > 1:
            # serve-time model parallelism over the agent's ASSIGNED chips:
            # Megatron-style GSPMD shardings on a tp×ep mesh — heads/FFN
            # width split over tp, MoE expert weights split over ep (each
            # chip holds and computes E/ep experts; the top-k combine's
            # expert contraction becomes a psum — BASELINE config #5), KV
            # arena split on the kv-head axis; XLA inserts the ICI
            # collectives. (DP scale-out stays at the control plane via
            # `replicas: N`, matching the reference's fan-out.) Params
            # arrive host-side and are device_put directly with their
            # shardings, and the arena is allocated sharded, so nothing is
            # ever materialized whole on one chip.
            from jax.sharding import NamedSharding

            from ..parallel.mesh import make_mesh
            from ..parallel.sharding import cache_specs, param_shardings_for

            self.mesh = mesh if mesh is not None else make_mesh(
                self.tp, self.ep, devices=devices
            )
            # quant-aware: int8 QTensor leaves shard q on the dense spec and
            # replicate the scale across the contraction split
            params = jax.device_put(params, param_shardings_for(params, self.mesh, cfg.is_moe))
            if self.paged:
                # pool shards on the KV-head axis exactly like the dense
                # arena; the page axis stays whole (page ids are global —
                # the block-table gather must be shard-local, pinned by
                # tests/test_paged_hlo.py)
                from jax.sharding import PartitionSpec as _P

                cache_sh = NamedSharding(self.mesh, _P(None, None, "tp", None, None))
                self._alloc_cache = jax.jit(
                    lambda: PagedKVCache(
                        jnp.zeros(cache_shape, dtype), jnp.zeros(cache_shape, dtype)
                    ),
                    out_shardings=PagedKVCache(cache_sh, cache_sh),
                )
            else:
                cache_sh = NamedSharding(self.mesh, cache_specs())
                self._alloc_cache = jax.jit(
                    lambda: KVCache(
                        jnp.zeros(cache_shape, dtype), jnp.zeros(cache_shape, dtype)
                    ),
                    out_shardings=KVCache(cache_sh, cache_sh),
                )
            cache = self._alloc_cache()
        else:
            self.mesh = None
            # single-chip: place on the ASSIGNED chip, not the default
            # device — on a multi-chip host two agents with different
            # single-chip slices must not both land on device 0. Explicit
            # device_put COMMITS the arrays: serve-time cache/carries are
            # jit outputs (always committed), and a committed-vs-not
            # mismatch is a different executable-cache key — warmup must
            # see the same placement real traffic will.
            dev = devices[0] if devices else jax.devices()[0]
            params = jax.device_put(params, dev)  # checkpoint loads arrive host-side

            if self.paged:

                def _alloc_single():
                    with jax.default_device(dev):
                        c = PagedKVCache.create(
                            cfg, self._total_pages, self.page_size, dtype=dtype
                        )
                    return jax.device_put(c, dev)

            else:

                def _alloc_single():
                    with jax.default_device(dev):
                        # the model builds its cache; an engine's lanes of a
                        # recurrent state start closed
                        c = init_cache(
                            cfg, max_batch, max_seq, dtype=dtype, live=False,
                            **ring_plan(cfg, dtype, self.prefill_chunk),
                        )
                    return jax.device_put(c, dev)

            self._alloc_cache = _alloc_single
            cache = self._alloc_cache()
        self.params = params
        self.cache = cache
        self.slots = [Slot(i) for i in range(max_batch)]
        # session membership surface. Dense: name → owning slot index (the
        # slot holds the KV). Paged: name → bound lane index while a
        # request is in flight, -1 while resident-but-idle — membership
        # and iteration keep working for the serve layer (restore checks,
        # drain snapshots), but the KV lives in paged_sessions[name].pages.
        self.sessions: dict[str, int] = {}
        # -- paged-arena allocator (host side; _page_lock guards it) ------
        # physical ids [0, _data_pages) are allocatable; ids [_data_pages,
        # _total_pages) are per-lane scratch pages (lane i owns id
        # _data_pages + i), permanently pinned, never shared: parked-lane
        # and bucket-padding writes land there instead of in any session's
        # pages. The authoritative block table is HOST state (numpy) and
        # ships to the device per dispatch — ~1 KB, async, and never a
        # recompile since it is an argument, not a constant.
        self.paged_sessions: dict[str, PagedSession] = {}
        self._page_lock = threading.RLock()
        self._page_free: list[int] = list(range(self._data_pages - 1, -1, -1))
        self._page_refs = np.zeros(self._total_pages, dtype=np.int64)
        # pages freed while readbacks are in flight park here: a chunk
        # dispatched BEFORE the free captured the old block table and will
        # still write into these pages — they must not be reallocated until
        # that dispatch's readback has drained
        self._page_quarantine: list[int] = []
        self._bt = np.empty((max_batch, self._n_blocks), dtype=np.int32)
        for i in range(max_batch):
            self._bt[i, :] = self._scratch_page(i)
        self.page_exhausted_total = 0
        self.prefix_pages_shared = 0
        self._snap_paged_fns: dict[int, Any] = {}
        self._restore_paged_fns: dict[int, Any] = {}
        self._page_copy_fn_cached: Any = None

        # -- tiered KV hierarchy (device → pinned host RAM → store) -------
        # Idle sessions park their KV OFF the device: a host-tier entry
        # holds the position-trimmed prefix (exact dtype, or int8 with
        # per-page scales when tier_quantize is on), the device pages flow
        # back to the pool through the quarantine discipline, and the park
        # also yields an exact SNAP_VERSION 3 blob for the store (the cold
        # tier — survives the process). Promotion is the reverse and is
        # initiated from the admission path so the device swap-in overlaps
        # the queue-wait phase of TTFT. Works for BOTH arenas; the paged
        # pool additionally demotes under pressure before 429ing.
        self.kv_tiering = bool(kv_tiering)
        self.tier_quantize = int(tier_quantize)
        # _tier_lock guards _host_tier + byte/page gauges: API threads
        # insert (park) while the worker promotes/pressure-demotes. Never
        # held across device work or blocking readbacks.
        self._tier_lock = threading.Lock()
        self._host_tier: collections.OrderedDict[str, TieredEntry] = (
            collections.OrderedDict()
        )
        # host-RAM budget for parked KV: beyond it the LRU host entries are
        # dropped (their store blob remains — the cold tier serves the next
        # turn via the serve layer's restore-on-unknown path). Defaults to
        # one KV arena's worth of host RAM (stamped below, once the arena
        # byte count is known).
        self.tier_host_budget_bytes = 0
        self.tier_host_bytes = 0
        self.tier_quantized_pages = 0
        self.tier_demotions_total = 0
        self.tier_promotions_total = 0
        self.tier_pressure_demotions_total = 0
        self.tier_prewarm_hits_total = 0
        self.tier_demote_failures_total = 0
        self.tier_promote_failures_total = 0
        # promote-start instants by session, consumed when the promoted
        # session's next request dispatches its first prefill chunk — the
        # interval is restore latency HIDDEN behind the queue-wait phase
        self._tier_promote_started: dict[str, float] = {}
        self.tier_promote_overlap_ms_recent: collections.deque[float] = (
            collections.deque(maxlen=64)
        )

        # Device-side decode carry: the pipelined decode chains (token,
        # position, temperature) per slot lane ON DEVICE across chunks, so
        # steady-state decode never waits for a host round-trip (a readback
        # serialized per chunk would sit on every token's path).
        # Idle lanes park at scratch_pos exactly like the pre-pipeline
        # design; prefill injects a finished prompt's first token into its
        # lane with a jitted scatter instead of a host rebuild.
        def _mk_carry():
            return (
                jnp.zeros((max_batch,), jnp.int32),
                jnp.full((max_batch,), self.scratch_pos, jnp.int32),
                jnp.zeros((max_batch,), jnp.float32),
                jnp.zeros((max_batch,), jnp.int32),  # top_k (0 = disabled)
                jnp.ones((max_batch,), jnp.float32),  # top_p (1 = disabled)
                # in-loop spec history ring (right-aligned recent tokens)
                # + per-lane valid count; dead weight when the fused loop
                # or in-loop spec is off (W ints per lane — negligible),
                # kept in the carry unconditionally so every injection and
                # reallocation path has ONE shape.
                jnp.zeros((max_batch, FUSED_HIST_W), jnp.int32),
                jnp.zeros((max_batch,), jnp.int32),
            )

        if self.mesh is not None:
            from jax.sharding import NamedSharding as _NS, PartitionSpec as _P

            repl = _NS(self.mesh, _P())
            self._alloc_carry = jax.jit(
                _mk_carry, out_shardings=(repl,) * 7
            )
        else:
            # committed (see the cache comment above): first-use and
            # steady-state signatures must match
            self._alloc_carry = lambda: jax.device_put(_mk_carry(), dev)
        (
            self._dtok,
            self._dpos,
            self._dtemps,
            self._dtopk,
            self._dtopp,
            self._dhist,
            self._dhlen,
        ) = self._alloc_carry()
        # Double-buffered lane injection (ISSUE 17): a capacity-1 staging
        # slot a running fused loop absorbs at its next dispatch boundary.
        # The staged lane's (token, position, sampler params, spec history)
        # are scattered into these shadow arrays OUTSIDE the loop via the
        # same jitted _inject scatter the live carry uses; the next fused
        # dispatch ships a per-lane `armed` mask and the loop's entry merge
        # reads staged state for armed lanes — so a finished prefill starts
        # decoding WITHOUT the host waiting on the in-flight loop's
        # readback (exit-and-redispatch put that host RTT on the device's
        # idle path). _staged_lane tracks occupancy; an occupied slot falls
        # back to the direct-injection path (today's behavior).
        (
            self._stok,
            self._spos,
            self._stemps,
            self._stopk,
            self._stopp,
            self._shist,
            self._shlen,
        ) = self._alloc_carry()
        self._staged_lane: int | None = None
        # instance toggle (not a constructor option: injection is a
        # fused-dispatch internal, A/B'd by tests flipping this directly)
        self._fused_inject = self.fused_decode
        self.fused_injections_total = 0
        self.fused_inject_fallbacks_total = 0
        # FIFO of lagged readbacks: ("first", slot, req, first_dev, launch),
        # ("chunk", [(slot, req, start_pos)...], toks_dev, launch) and
        # ("fused", [...], packed_dev, chunk, launch), each with its launch's
        # ledger record last; staleness is detected by `slot.request is not
        # req` identity at processing time
        self._readbacks: collections.deque = collections.deque()

        self._queue: queue.Queue[GenRequest | None] = queue.Queue()
        # submitted-but-unadmitted items (burst drain / all slots busy);
        # worker-thread state, but an instance attribute so the dispatcher
        # can see contention and the shutdown path can fail what's left
        self._waiting: list = []
        self._sentinel = False  # shutdown marker observed by the worker
        self._completed: collections.OrderedDict[str, dict] = collections.OrderedDict()
        self._lock = threading.Lock()
        # committed like the carry: the first-token program returns the next
        # key, so its first call must see what every later call sees
        self._rng = jax.device_put(
            jax.random.PRNGKey(0), dev if self.mesh is None else repl
        )
        self._running = True

        # where the worker thread's time goes (utils/spans.py): phase
        # totals for metrics(), and the same spans on the profiler's clock
        # while a /profile capture runs
        self._spans = Spans()
        # every launch of a step program, counted where it is dispatched and
        # timed where its output is read back (utils/launches.py): the
        # launch counters of metrics() are sums over it
        self._launches = Launches()
        # the ledger at the two edges of the newest /profile capture
        # (``h_profile`` sets it): what pairs a trace's modules with the
        # launches that were really in it
        self.last_capture: dict | None = None

        # counters
        self.tokens_generated = 0
        self.prefills = 0
        self.requests_finished = 0
        self.ttft_ms_recent: collections.deque[float] = collections.deque(maxlen=256)
        self.itl_ms_recent: collections.deque[float] = collections.deque(maxlen=256)
        # TTFT phase decomposition: queue-wait (admission → first prefill
        # chunk dispatched), prefill (first chunk → first-token injection),
        # first-readback (injection → token on host). The phases regress
        # independently — admission is scheduler policy, the rest is device
        # work — so they are tracked independently (VERDICT r4 #10, r5 #3).
        self.admission_ms_recent: collections.deque[float] = collections.deque(maxlen=256)
        self.prefill_ms_recent: collections.deque[float] = collections.deque(maxlen=256)
        self.first_readback_ms_recent: collections.deque[float] = collections.deque(
            maxlen=256
        )
        # how often contention shrank a decode chunk below the configured one
        self.decode_chunks_shrunk = 0
        self.worker_errors = 0
        self.last_worker_error = ""
        self.cache_resets = 0
        # End-to-end deadline plumbing (deadlines=False is the A/B baseline:
        # no expiry checks, no overload shed — exactly the prior behavior;
        # explicit cancel() still works, it is an API, not policy).
        self.deadlines = bool(deadlines)
        # submit-time shed watermark on queue+waiting+active depth; 0 = off
        # (the historical unbounded queue). The serve layer maps the raised
        # EngineOverloaded to 429 + Retry-After.
        self.shed_watermark = max(0, int(shed_watermark))
        # request-id → cancel-record time (guarded by self._lock). TTL'd:
        # a cancel for an id the engine never ends up seeing (client died
        # before its dispatch arrived) must not poison a LATER legitimate
        # dispatch of the same id (operator requeue) nor accumulate forever.
        self._cancel_requested: dict[str, float] = {}
        self._cancel_ttl_s = 30.0
        self._draining = False
        self.cancelled_total = 0
        self.expired_total = 0
        self.shed_total = 0
        self._snap_fns: dict[int, Any] = {}
        # global limiter: one snapshot staging per gap — the readback rides
        # the same device stream decode lives on (a bucket-128 8B snapshot
        # is a ~17 MB blob), so unthrottled snapshots from many sessions at
        # once would tax every in-flight generation
        self.snapshot_min_gap_s = 2.0
        # busy engines defer snapshots to idle moments, but never longer
        # than this per session (durability floor under sustained load)
        self.snapshot_force_s = 30.0
        # minimum spacing between stagings while OTHER requests decode
        self.snapshot_busy_gap_s = 10.0
        # gap-free first snapshot, but the force timer starts fresh
        self._last_snapshot_at = time.monotonic() - self.snapshot_min_gap_s
        self._staged_leaf = None  # weakref to the last staged snapshot's first leaf
        # session → SnapshotCmd parked until the session's request settles
        self._snap_parked: dict[str, SnapshotCmd] = {}
        # per-session staging times for the durability floor (bounded: one
        # entry per session name ever snapshotted; evictions clean up)
        self._snap_last_by_session: dict[str, float] = {}
        self._snap_epoch0 = time.monotonic()
        self._prefilling_slot: Slot | None = None
        # HBM traffic model for MBU (decode is memory-bound; MFU alone
        # judges it against the wrong roofline — VERDICT r4 item 6): every
        # decode step streams the weights once plus each active lane's KV
        # prefix; prefill streams the weights once per chunk.
        self.hbm_bytes_read = 0.0
        if self._hybrid:
            # positional bytes a token adds (the latent rows, or k and v as
            # stored); the per-lane state is read and written whole each step
            # whatever the context
            per_pos = lambda leaves: sum(  # noqa: E731
                a.shape[0] * int(np.prod(a.shape[3:])) * a.dtype.itemsize for a in leaves
            )
            # of it, what the window layers add: read for the last ``window``
            # positions only (``_kv_read_bytes``)
            self._kv_bytes_per_pos_window = per_pos(cache.ring())
            self._kv_bytes_per_pos = per_pos(cache.rows()) + self._kv_bytes_per_pos_window
        else:
            self._kv_bytes_per_pos = (
                2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * cache.k.dtype.itemsize
            )
            self._kv_bytes_per_pos_window = self._kv_bytes_per_pos * cfg.n_window // cfg.n_layers
        # cache-manager counters of the per-lane state (``cache`` in /metrics)
        self.state_resets = 0
        self.state_snapshots = 0
        self.state_restores = 0
        self._restore_fns: dict[int, Any] = {}
        self._staged_bytes_by_bucket: dict[int, str] = {}
        self._last_decode_end: float | None = None
        self._started_at = time.monotonic()

        # FLOP/HBM accounting (VERDICT r2 items 1-2/10): achieved model
        # FLOPs accumulate per prefill chunk / decode token so the metrics
        # plane can report MFU against the spanned chips' spec-sheet peak;
        # weight/arena bytes let the scheduler's HBM claims be audited.
        from ..utils.hw import chip_spec

        self.flops_done = 0.0
        self.param_hbm_bytes = sum(
            x.nbytes for x in jax.tree.leaves(params)
        )
        self.kv_arena_bytes = sum(x.nbytes for x in jax.tree.leaves(cache))
        if not self.tier_host_budget_bytes:
            self.tier_host_budget_bytes = self.kv_arena_bytes
        # Cross-session prefix arena: bucket-length token prefixes → their
        # prefilled KV, populated the first time a prefix is prefilled and
        # forked into a fresh slot on admission (the second session with a
        # shared system prompt prefills only its uncached tail). Keyed by a
        # rolling hash of the token ids at bucket granularity, verified by
        # exact token equality, LRU-evicted under the bytes budget.
        # prefix_cache=False is the A/B baseline (mirrors adaptive_decode).
        self.prefix_cache = bool(prefix_cache)
        self._prefix_active = self.prefix_cache  # warmup serves with it off
        # bucket levels a prefix can be cached at: a hit must leave ≥1
        # prompt token to prefill (the first token is sampled from prefill
        # logits), so levels cap below the longest admissible prompt
        self._prefix_levels = [b for b in PREFILL_BUCKETS if b <= max_seq - 2]
        self._prefix_entries: collections.OrderedDict[tuple, PrefixEntry] = (
            collections.OrderedDict()
        )
        self._prefix_bytes = 0
        # arena budget defaults to the main KV arena's size: one extra
        # arena's worth of HBM buys ~every repeat prefill in the workload.
        # Paged engines pin prefix pages INSIDE the pool (no extra HBM), so
        # the default caps pinning at half the pool — the other half stays
        # for live sessions; pool pressure can still evict pinned entries.
        if prefix_cache_bytes:
            self._prefix_budget = int(prefix_cache_bytes)
        elif self.paged:
            self._prefix_budget = self.kv_arena_bytes // 2
        else:
            self._prefix_budget = self.kv_arena_bytes
        self._prefix_slice_fns: dict[int, Any] = {}
        self._prefix_fork_fns: dict[int, Any] = {}
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_saved = 0
        # eviction observability (session KV eviction used to be silent):
        # both the slot LRU and the prefix arena count through the same
        # path, so hit-rate regressions trace to churn in either pool
        self.session_evictions = 0
        self.prefix_evictions = 0
        self.session_eviction_idle_s_recent: collections.deque[float] = (
            collections.deque(maxlen=64)
        )
        self.prefix_eviction_idle_s_recent: collections.deque[float] = (
            collections.deque(maxlen=64)
        )
        # Self-speculative decoding (prompt-lookup drafting + batched
        # multi-token verification): a host-side drafter matches each
        # slot's trailing n-gram against its own token stream and proposes
        # up to gamma continuation tokens; one compiled verify forward per
        # round scores every lane's drafts in parallel and accepts the
        # longest agreeing prefix. speculative=False is the A/B baseline
        # (mirrors adaptive_decode / prefix_cache).
        self.speculative = bool(speculative)
        gamma_max = max(1, min(int(spec_gamma_max), SPEC_VERIFY_BUCKETS[-1]))
        self._spec_buckets = [
            b for b in SPEC_VERIFY_BUCKETS if b <= gamma_max
        ] or [SPEC_VERIFY_BUCKETS[0]]
        # snap DOWN to the largest compiled bucket: a gamma between buckets
        # (e.g. 5 with ladder {2,4}) would draft longer than any verify
        # program covers and the round's bucket pick would fail
        self.spec_gamma_max = self._spec_buckets[-1]
        self._verify_fns: dict[int, Any] = {}
        # In-loop device speculation: the fused loop drafts and verifies on
        # device, so speculating lanes stay loop-resident (the host-side
        # drafter forces a loop exit + synchronous verify round-trip every
        # round). Requires the fused loop and the speculative flag; meshed
        # engines keep the host drafter — the draft/verify lax.cond inside
        # the loop body trips the same XLA:CPU partitioner segfault the
        # sampler's greedy cond does over sharded operands.
        self.inloop_spec = (
            bool(inloop_spec)
            and self.fused_decode
            and bool(speculative)
            and self.mesh is None
        )
        self.inloop_spec_drafted = 0
        self.inloop_spec_accepted = 0
        self._spec_active = self.speculative  # warmup serves with it off
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        # fused-loop observability (ISSUE 10): loops dispatched, on-device
        # steps actually executed (early exits run fewer than the rung),
        # loops that exited before the rung bound, exit-reason histogram.
        # Host syncs are the launch ledger's readbacks (every host
        # materialization of device decode output is one ``ready``), so
        # syncs/token quantifies the one-readback-per-loop claim against
        # the per-chunk baseline.
        self._fused_fns: dict[int, Any] = {}
        # dynamic-rung cap: the single compiled loop's static sizing bound
        # (emitted buffer, key ladder); the runtime loop bound `nsteps` is
        # an operand, so dispatch picks any rung in [1, cap] at zero
        # compile cost and the uncontended steady state rides the top
        self._fused_cap = max(self.decode_chunk, FUSED_RUNG_MULT * self.decode_chunk)
        self.fused_loops_total = 0
        self.fused_steps_total = 0
        self.fused_early_exits_total = 0
        self.fused_exit_reason_hist: dict[str, int] = {}
        self._n_chips = self.tp * self.ep
        # the devices this engine computes on, as JAX reports them — what
        # /metrics names, so a number can always be traced to its device
        self._devices = (
            list(self.mesh.devices.flat) if self.mesh is not None else [dev]
        )
        # None for a device kind outside the peaks table (the CPU): such an
        # engine reports no mfu/mbu at all
        self._chip = chip_spec(self._devices[0].device_kind)

        self._build_compiled()
        self._worker = threading.Thread(target=self._loop, daemon=True, name="llm-engine")
        self._worker.start()

    # -- construction -----------------------------------------------------
    @classmethod
    def create(
        cls,
        config_name: str,
        checkpoint: str = "",
        agent_id: str = "",
        store=None,
        options: dict | None = None,
        boot: Spans | None = None,
    ) -> "LLMEngine":
        """``boot``: the recorder of the boot this load is part of (the
        serve app's ``utils/boot.BootTimeline``); its stages here are
        ``boot.backend``, ``boot.weights``, ``boot.engine_init`` and
        ``boot.warmup``."""
        options = options or {}
        boot = Spans() if boot is None else boot
        refusal = unserved_layout(options)
        if refusal:
            raise ValueError(refusal)
        # HF checkpoints carry their own config.json — derive the config
        # from the checkpoint itself so a mistyped/missing config name can't
        # cause an opaque shape error deep in the loader (ADVICE round-1)
        from .hf_convert import config_from_hf, is_hf_checkpoint

        if checkpoint and is_hf_checkpoint(checkpoint):
            try:
                cfg = config_from_hf(checkpoint)
            except (OSError, KeyError, ValueError) as e:
                # converted weights without a (llama-style) config.json: an
                # explicit config name remains authoritative
                if not config_name:
                    raise ValueError(
                        f"checkpoint {checkpoint!r} has no usable config.json "
                        f"({e!r}); pass model.config explicitly"
                    ) from e
                cfg = get_config(config_name)
        else:
            cfg = get_config(config_name or "tiny")
        tokenizer = load_tokenizer(cfg.vocab_size, checkpoint)
        with boot.span("boot.backend"):  # the runtime comes up here
            backend = jax.default_backend()
            all_devices = jax.devices()
        if backend == "cpu" and os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
            # an engine that finds no chip must say so, not serve float32
            # from the host as if nothing were wrong; tests and rehearsals
            # ask for the CPU by name (first in JAX_PLATFORMS)
            raise RuntimeError(
                "no accelerator: JAX came up on the cpu backend without being "
                "asked to (set JAX_PLATFORMS=cpu to serve from the host on purpose)"
            )
        dtype = jnp.bfloat16 if backend == "tpu" else jnp.float32
        quant = str(options.get("quant", "") or "").lower()
        if quant and quant != "int8":
            raise ValueError(f"unknown quant scheme {quant!r} (supported: int8)")

        # serve-time model parallelism: the control plane passes the agent's
        # assigned chip ids (llm_serve) and starts this process seeing ONLY
        # those chips (runtime/local.py), so device indices here are local:
        # the i-th assigned chip is jax.devices()[i], whatever its id on the
        # slice. Standalone default is single-chip. int8 quant keeps TP: the
        # QTensor pytree gets matching shardings
        # (parallel/sharding.param_shardings_for).
        from ..parallel.mesh import make_mesh, plan_layout

        chips = [int(c) for c in options.get("chips", []) or []]
        if len(chips) > len(all_devices):
            raise ValueError(
                f"assigned chips {chips} do not map to the {len(all_devices)} "
                f"visible {backend} device(s) of this process"
            )
        tp, ep = plan_layout(
            cfg,
            len(chips),
            len(all_devices),
            tp_asked=int(options.get("tp", 0) or 0),
            ep_asked=int(options.get("ep", 0) or 0),
        )
        devices = list(all_devices[: tp * ep])
        mesh = make_mesh(tp, ep, devices=devices) if tp * ep > 1 else None
        synthetic = bool(options.get("synthetic"))
        source = "checkpoint" if checkpoint else "synthetic" if synthetic and quant else "random"
        with boot.span("boot.weights", source=source):
            if checkpoint:
                from .checkpoint import load_params

                params = load_params(cfg, checkpoint, dtype=dtype)  # host-side
            elif synthetic and quant:
                # benchmark-grade int8 weights generated directly in HBM: no
                # minutes-long host init, no multi-GB host→device transfer.
                # Meshed engines generate each leaf WITH its sharding, so every
                # chip allocates only its slice (VERDICT r3 missing #3).
                from .quant import synthetic_quantized_params

                if mesh is not None:
                    params = synthetic_quantized_params(cfg, dtype, mesh=mesh)
                else:
                    params = synthetic_quantized_params(
                        cfg, dtype, device=devices[0] if devices else None
                    )
            elif quant:
                # random init on the HOST when quantizing: the dense bf16 model
                # may be exactly what doesn't fit the chip
                try:
                    cpu0 = jax.local_devices(backend="cpu")[0]
                except Exception:
                    cpu0 = None
                if cpu0 is not None:
                    with jax.default_device(cpu0):
                        params = init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
                else:
                    params = init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
            elif mesh is not None:
                # meshed random init allocates straight into shards — never the
                # whole model on the default device (VERDICT r3 missing #3)
                from ..parallel.sharding import param_specs as _ps

                params = _sharded_random_init(cfg, dtype, mesh, _ps(cfg.is_moe, cfg.qk_norm))
            else:
                params = init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
            if quant and not (synthetic and not checkpoint):
                from .quant import quantize_params

                # host-side: only the int8 model ever reaches HBM (synthetic
                # init already produced QTensors in device memory)
                params = quantize_params(params, dtype)
        max_batch = int(options.get("max_batch", 8))
        max_seq = int(options.get("max_seq", min(cfg.max_seq_len, 2048)))
        decode_chunk = int(options.get("decode_chunk", 8))
        prefill_chunk = int(options.get("prefill_chunk", 256))
        with boot.span("boot.engine_init"):
            engine = cls(
                cfg,
                params,
                tokenizer,
                max_batch=max_batch,
                max_seq=max_seq,
                decode_chunk=decode_chunk,
                prefill_chunk=prefill_chunk,
                tp=tp,
                ep=ep,
                devices=devices,
                mesh=mesh,
                routed_moe=options.get("routed"),
                moe_capacity_factor=float(options.get("moe_cf", 2.0)),
                adaptive_decode=bool(options.get("adaptive_decode", True)),
                # None: not asked (the family's default; ``cache_features``)
                prefix_cache=bool(options["prefix_cache"]) if "prefix_cache" in options else None,
                prefix_cache_bytes=int(options.get("prefix_cache_bytes", 0) or 0),
                deadlines=bool(options.get("deadlines", True)),
                shed_watermark=int(options.get("shed_watermark", 0) or 0),
                speculative=bool(options["speculative"]) if "speculative" in options else None,
                spec_gamma_max=int(options.get("spec_gamma_max", 8) or 8),
                paged_kv=bool(options.get("paged_kv", False)),
                page_size=int(options.get("page_size", PAGE_SIZE_DEFAULT) or PAGE_SIZE_DEFAULT),
                kv_pages=int(options.get("kv_pages", 0) or 0),
                fused_decode=bool(options.get("fused_decode", False)),
                inloop_spec=bool(options.get("inloop_spec", True)),
                approx_topk=bool(options.get("approx_topk", False)),
                kv_tiering=bool(options.get("kv_tiering", False)),
                tier_quantize=int(options.get("tier_quantize", 1) or 0),
                streaming=bool(options.get("streaming", False)),
            )
        # pay the decode/prefill compiles here (inside the loader thread, while
        # /health keeps answering) instead of on the first user request.
        # skip_warmup (set on engine RESPAWN when the persistent XLA cache is
        # already populated) trades a few cache-load hiccups on the first
        # requests for a much shorter crash-recovery time — the compiles are
        # disk loads, not recompiles.
        if not options.get("skip_warmup"):
            with boot.span("boot.warmup"):
                engine.warmup(boot)
        return engine

    def _build_compiled(self) -> None:
        cfg = self.cfg
        # One arena attention per engine, chosen HERE and nowhere deeper:
        # every compiled step traces ``attn.fn``, so what the log line and
        # /metrics say is what the programs contain. Unmeshed engines get
        # the Pallas flash kernels on a TPU backend (fused with the
        # block-table walk for the page pool). Meshed engines can't let
        # GSPMD partition a pallas_call, but attention is embarrassingly
        # parallel over heads/batch — so tp/ep engines run the SAME flash
        # kernels per device inside a shard_map body
        # (parallel/flash_mesh.py). A meshed page pool stays on the einsum
        # path (it needs the partitioning XLA derives).
        page_size = self.page_size if self.paged else 0
        if self._hybrid:
            # the hybrid block's plan: a kernel per mechanism and call shape
            # (models/hybrid.plan_hybrid), passed to ``forward`` in the same
            # seat as the arena attention
            from ..models.hybrid import plan_hybrid

            attn = plan_hybrid(cfg)
        elif self.mesh is None:
            attn = plan_cache_attention(
                cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, page_size=page_size
            )
        elif not self.paged:
            from ..parallel.flash_mesh import plan_meshed_cache_attention

            attn = plan_meshed_cache_attention(cfg, self.mesh, self.tp)
        else:
            attn = plan_cache_attention(
                cfg.n_heads,
                cfg.n_kv_heads,
                cfg.head_dim,
                page_size=page_size,
                use_pallas=False,
            )
        self.attention = attn.describe()
        # what ``flash_decode``'s index map lets through, counted on the host
        # at each decode launch (``_count_decode_blocks``), cumulative: blocks
        # of ``decode_block_positions`` positions the lanes' positions reach,
        # and blocks the arena rows hold; their ratio is the share of the
        # K/V arena a decode step fetches. 0 where no dense K/V arena serves
        from ..ops.pallas_attention import decode_kv_block

        k = None if self.paged else getattr(self.cache, "k", None)
        self._decode_bk = decode_kv_block(k.shape[3], k.shape[4], k.dtype, k.shape[2])[1] if k is not None else 0
        self.attention.update(
            decode_block_positions=self._decode_bk, decode_blocks_live=0, decode_blocks_stored=0
        )
        if k is not None and self.attention["prefill"] == "pallas:flash_prefill":
            # the plan a full chunk's call compiles to (a function of its shapes
            # alone): q tile rows, K/V block positions, the MXU's operand dtype
            from ..ops.pallas_attention import prefill_tile

            stored = k.shape[3]  # the hybrid block stores 32 heads for a model's 30
            self.attention["prefill_tile"] = prefill_tile(
                self.prefill_chunk, stored * (cfg.n_heads // cfg.n_kv_heads), stored, k.shape[4], k.shape[2], k.dtype, k.dtype
            )
        # the same count for a latent leaf (``mla_decode``'s index map: a
        # stepping lane at position p fetches ``p // bk + 1`` blocks of its
        # row, a layer counted once); absent where the cache has no such leaf
        latent = getattr(self.cache, "latent", None)
        self._latent_bk = 0
        if latent is not None:
            from ..ops.pallas_mla import decode_block_rows

            self._latent_bk = decode_block_rows(latent.shape[3], latent.dtype.itemsize, latent.shape[2])
            self.attention.update(
                latent_block_positions=self._latent_bk, latent_decode_blocks_live=0, latent_decode_blocks_stored=0
            )
        if cfg.rope_original_max:
            # rows (prefill and decode) at or past the position from which the
            # scaled frequencies and the query's scale differ from plain RoPE
            self.attention.update(
                rope_original_max=cfg.rope_original_max, rows_past_original_max=0, rows_positioned=0
            )
        if self._windowed:
            # the same count by kind of layer (a layer of each kind counted
            # once): ``global_*`` is what the first pair counts; ``window_*``
            # the ring blocks the lower and upper bounds let through against
            # the blocks an UNBOUNDED read of the same lanes would fetch (a
            # window layer that kept its whole context); lanes whose position
            # passed the ring's length (``window_wraps``, counted once a
            # request) and the rows a lane keeps of each kind
            wk = self.cache.wk
            self._ring_rows = wk.shape[2]
            self._ring_bk = decode_kv_block(wk.shape[3], wk.shape[4], wk.dtype, wk.shape[2])[1]
            self.attention.update(
                window=cfg.window, window_layers=cfg.n_window, global_layers=cfg.n_global,
                window_rows=self._ring_rows, global_rows=self.max_seq,
                window_block_positions=self._ring_bk,
                global_decode_blocks_live=0, global_decode_blocks_stored=0,
                window_decode_blocks_live=0, window_decode_blocks_unbounded=0,
                window_decode_blocks_stored=0, window_wraps=0,
                global_decode_rows=0, window_decode_rows=0,
            )
        if self._hybrid and k is not None:
            # the output gate's form ("none", "per_head" or "full": a sparse
            # layer's is as wide as its output) and the K/V heads a row is
            # STORED with (``hybrid.stored_kv_heads``: 32 for a model's 30)
            self.attention.update(gate="full" if cfg.n_sparse else cfg.gate_form, kv_heads_stored=int(k.shape[3]))
        if self._windowed and self._hybrid:
            # what differs by kind of layer: query heads, the gate, the rotary
            from ..models.hybrid import attention_by_kind

            self.attention.update(attention_by_kind(cfg))
        # block-sparse layers: what their selection reads, counted on the host
        # at each launch from the rows' positions (``ops/sparse_attention.
        # block_counts``), a layer counted once, cumulative: query rows under
        # and past ``dense_len``, the blocks those past it could see, chose
        # and were made to choose, the rows every query could see against the
        # rows read, and the pooled keys scored
        self._sparse_sizes = None
        if cfg.n_sparse:
            from ..ops.sparse_attention import SparseSizes, block_counts

            self._sparse_sizes = SparseSizes.of(cfg)
            self._block_counts = block_counts
            self.attention["sparse"] = {
                "layers": cfg.n_sparse, **self._sparse_sizes._asdict(), **block_counts([], self._sparse_sizes)
            }
        # the linear mixer's per-lane state: rows a chunked launch stepped it
        # by and one-token steps of a lane, a layer counted once, cumulative
        self.linear = None
        if self._recurrent:
            self.linear = {
                "kind": cfg.linear_kind, "layers": cfg.n_linear,
                # the state's shape a lane and layer, and whether β reaches 2
                # (the transition then has a negative eigenvalue)
                "heads": cfg.kda_heads, "head_dim": cfg.kda_head_dim,
                "neg_eigval": bool(cfg.delta_neg_eigval and cfg.linear_kind != "lightning"),
                "state_bytes_lane": int(self.cache.state.nbytes // self.max_batch),
                "conv": self.cache.conv is not None, "rows_chunked": 0, "steps": 0,
            }
        self.meshed_flash = (not self._hybrid) and "shard_map" in attn.decode
        if self._hybrid:
            print(
                "[llm-engine] attention: "
                + "; ".join(f"{k} prefill={p} decode={d}" for k, (p, d) in attn.kinds().items())
                + f" ({attn.reason})",
                flush=True,
            )
        else:
            print(
                f"[llm-engine] attention: prefill={attn.prefill} "
                f"decode={attn.decode} arena={attn.arena} ({attn.reason})",
                flush=True,
            )
        cache_attn_impl = attn if self._hybrid else attn.fn

        # the MoE FFN the steps trace. One chip, no option: ``forward`` itself
        # splits at the chip's ridge (ops/moe.sorted_from_rows) — calls under
        # it (decode, verify, short buckets) trace the all-experts einsum,
        # longer prefill chunks the sorted grouped FFN over the int8 stack.
        # A mesh pins one path: GSPMD cannot partition the grouped kernel, so
        # ``tp`` keeps the einsum and ``ep`` its shard_map'd dispatch.
        moe_impl = None
        if self.routed_moe:
            if self.mesh is not None and self.ep > 1:
                from ..parallel.expert import make_routed_moe

                moe_impl = make_routed_moe(
                    self.mesh, cfg, capacity_factor=self.moe_capacity_factor
                )
            else:
                moe_impl = functools.partial(
                    _moe_mlp_routed,
                    cfg=cfg,
                    capacity_factor=self.moe_capacity_factor,
                )
        elif cfg.is_moe and self.mesh is not None:
            moe_impl = functools.partial(_moe_mlp, cfg=cfg)
        impl = (
            "none" if not cfg.is_moe
            else "routed_dispatch" if self.routed_moe
            else "all_experts_einsum"
        )
        # rows a call needs to take the sorted FFN (no call under a pinned path)
        self._moe_sorted_from = (
            moe_sorted_from(cfg, self.params if self._hybrid else self.params["layers"])
            if moe_impl is None
            else None
        )
        self.moe = {
            # the path of the calls under ``routed_from_rows`` (every call
            # where that is null), and the model's routing shape
            "impl": impl,
            "experts": cfg.n_experts,
            # experts in this chip's stack (all of them unless it holds a
            # share), the shared experts every token also takes, the rule
            "experts_held": cfg.n_held if cfg.is_moe else 0,
            # the chip's share in the deployment's words: experts ``offset ..
            # offset + held`` of the ``published`` the router scores
            "held": cfg.n_held if cfg.is_moe else 0,
            "published": cfg.n_experts,
            "offset": cfg.expert_offset if cfg.is_moe else 0,
            "shared_experts": cfg.n_shared_experts,
            "router": cfg.moe_router if cfg.is_moe else None,
            "top_k": cfg.experts_per_token if cfg.is_moe else 0,
            "renormalize": bool(cfg.is_moe and cfg.moe_renormalize),
            # the path of the calls with that many rows (B·T) or more
            "prefill_impl": impl if self._moe_sorted_from is None else "sorted_grouped_ffn",
            "routed_from_rows": self._moe_sorted_from,
            # cumulative, counted on the host at each launch from its static
            # shapes (``_count_moe_rows``): Σ N·k; Σ N·E over launches under the
            # cut; Σ rows of the grouped FFN's planned grid, tile padding and
            # the tiles no routing needs included; Σ N over launches over the
            # cut whose rows reach their tiles and come back inside the
            # kernel (all of them on a TPU, none where the plain path serves)
            "assignments": 0,
            "rows_all_experts": 0,
            "rows_routed": 0,
            "rows_gathered_in_kernel": 0,
        }
        self._moe_in_kernel = kernel_by_default()
        if cfg.is_moe:
            served = (
                "every call" if self._moe_sorted_from is None
                else f"calls under {self._moe_sorted_from} rows; "
                f"prefill_impl={self.moe['prefill_impl']} from there"
            )
            print(
                f"[llm-engine] moe: impl={impl} ({served}) experts={cfg.n_experts} "
                f"top_k={cfg.experts_per_token} renormalize={cfg.moe_renormalize} "
                f"qk_norm={cfg.qk_norm}",
                flush=True,
            )

        def run_forward(params, toks, pos, cache, bt=None, slot=None, **kw):
            """``slot``: the batch's rows are arena rows ``slot..`` (a lane's
            prefill); ``forward`` addresses them in place."""
            return forward(
                params,
                cfg,
                toks,
                pos,
                cache,
                cache_attn_impl=cache_attn_impl,
                moe_impl=moe_impl,
                block_table=bt,
                slot=slot,
                **kw,
            )

        # the paged fns can't read the logical arena length off the cache
        # (its page axis is pool-wide); close over it statically
        scratch_static = self.max_seq - 1

        def real_rows(tokens, n_real):
            # a recurrent state must not see the bucket's padding rows: the
            # hybrid block is told which of a chunk's rows are real
            if not self._hybrid:
                return {}
            return {"valid": jnp.arange(tokens.shape[1])[None, :] < n_real}

        def prefill(params, cache, slot, tokens, positions, n_real):
            # the prompt runs against the slot's row where it lies in the
            # arena: no row sliced out, none written back
            kw = real_rows(tokens, n_real)
            logits, cache = run_forward(params, tokens, positions, cache, slot=slot, **kw)
            last = lax.dynamic_slice_in_dim(logits, n_real - 1, 1, axis=1)[0, 0]
            return last, cache

        def prefill_paged(params, cache, bt, tokens, positions, n_real):
            # no row slice/write-back: the lane's single-row block table IS
            # the view, and writes land in pool pages directly
            logits, cache = run_forward(params, tokens, positions, cache, bt)
            last = lax.dynamic_slice_in_dim(logits, n_real - 1, 1, axis=1)[0, 0]
            return last, cache

        def decode_n(params, cache, tokens, positions, temps, topk, topp, keys, bt=None):
            """Kernel-looped decode: ``chunk`` autoregressive steps inside one
            compiled call (lax.scan), so the host↔device round trip is paid
            once per chunk, not once per token. The (token, position) carry
            is returned so the NEXT chunk can chain on it device-side — the
            worker never has to wait for tokens to cross the host boundary
            between chunks. Tokens a request doesn't end up using are rolled
            back by the worker (their cache writes are overwritten before any
            later query can attend to them). One body serves both arenas:
            with ``bt`` the cache is the page pool (block table constant
            across the chunk — the dispatcher pre-allocates every step's
            pages) and the scratch clamp comes from the engine statics,
            since the pool's page axis says nothing about logical length."""

            scratch = scratch_static  # = the dense arena's last row

            def step(carry, key):
                tok, pos, cache = carry
                logits, cache = run_forward(params, tok[:, None], pos[:, None], cache, bt)
                nxt = sample_step(
                    logits[:, 0], key, temps, topk, topp,
                    greedy_cond=self.mesh is None,
                    approx_topk=self.approx_topk,
                )
                # clamp: parked (idle/finished) lanes decode forever at the
                # scratch position — real lanes never reach it (admission
                # budgets position + max_tokens below it)
                return (nxt, jnp.minimum(pos + 1, scratch), cache), nxt

            (tok, pos, cache), toks = lax.scan(step, (tokens, positions, cache), keys)
            return toks, tok, pos, cache  # toks [chunk, B]

        def prefill_with_decode(
            params, cache, slot, tokens, positions, n_real, lane_tok, lane_pos, temps, topk, topp, keys
        ):
            """A prefill chunk and the decode lanes' step beside it in ONE
            launch: ``prefill``'s chunk at arena row ``slot`` and one step of
            ``decode_n`` over the carry, their rows run together through
            every layer (``forward``'s ``lanes``), so the weights stream once
            for both. Returns what the two return: the chunk's last logits,
            ``toks [1, B]``, the advanced carry, the cache. ``keys [1]`` is
            the key a one-step ``decode_n`` would take."""
            logits, cache = run_forward(
                params, tokens, positions, cache, slot=slot,
                lanes=(lane_tok[:, None], lane_pos[:, None]), last=n_real - 1,
                **real_rows(tokens, n_real),
            )
            nxt = sample_step(
                logits[1:], keys[0], temps, topk, topp,
                greedy_cond=True, approx_topk=self.approx_topk,
            )
            return logits[0], nxt[None], nxt, jnp.minimum(lane_pos + 1, scratch_static), cache

        def decode_n_paged(params, cache, bt, tokens, positions, temps, topk, topp, keys):
            # positional-arg adapter for the call-site splat (bt sits
            # between cache and the token state); the body is decode_n
            return decode_n(params, cache, tokens, positions, temps, topk, topp, keys, bt)

        def inject(
            tok, pos, temps, topk, topp, hist, hlen,
            idx, first, position, temp, tk, tp_, hist_row, hist_n,
        ):
            """Point a slot's decode lane at its prefill result: lane `idx`
            continues from `first` (the sampled first token, still on
            device) at `position`. Idle/finished lanes are parked the same
            way with first=0, position=scratch. The in-loop spec history is
            seeded in the same scatter: ``hist_row`` carries the host-built
            prompt tail shifted left one slot, and ``first`` (still a
            device value) lands in the newest slot — so the drafter's first
            trailing gram already includes the first generated token."""
            row = jnp.concatenate([hist_row[1:], first[None].astype(jnp.int32)])
            return (
                tok.at[idx].set(first),
                pos.at[idx].set(position),
                temps.at[idx].set(temp),
                topk.at[idx].set(tk),
                topp.at[idx].set(tp_),
                hist.at[idx].set(row),
                hlen.at[idx].set(hist_n),
            )

        def first_token(logits, rng, temperature, top_k, top_p):
            """The token after a prefill: ``sample_step`` over the one row of
            last-position logits ``_prefill`` returns, compiled once per
            engine. It has to stay a jitted program: called eagerly,
            ``sample_step``'s all-greedy ``lax.cond`` carries fresh closures
            each time, so JAX traces, lowers and fetches an executable for
            it on every request (0.27 s of the worker's time on a v5e host).
            Takes the engine's key and returns the next one (one split per
            first token, the stream the eager call site drew from), the
            ``[1]`` token for the readback queue and the same token as the
            scalar the lane injection takes."""
            rng, key = jax.random.split(rng)
            first = sample_step(
                logits[None], key, temperature[None], top_k[None], top_p[None],
                greedy_cond=self.mesh is None,
                approx_topk=self.approx_topk,
            )
            return rng, first, first[0]

        if self.paged:
            self._prefill = _step_jit(JIT_PREFILL, prefill_paged, donate_argnums=(1,))
            self._decode_n = _step_jit(JIT_DECODE_N, decode_n_paged, donate_argnums=(1, 3, 4))
        else:
            self._prefill = _step_jit(JIT_PREFILL, prefill, donate_argnums=(1,))
            self._decode_n = _step_jit(JIT_DECODE_N, decode_n, donate_argnums=(1, 2, 3))
        # Does this engine have the mixed step? Where the cache is the dense
        # arena of one chip under the per-chunk decode driver and ``forward``
        # chooses the MoE path by row count: the K/V block, and the hybrid
        # block whatever its layers' kinds (``models/hybrid``: a linear mixer
        # steps the lanes' state and conv a group of rows at a time). The page
        # pool, the fused loop, a mesh and the ``routed`` dispatch keep two
        # launches, as does a ladder without the one-step rung. Its rungs are
        # the two largest buckets a chunk can take, a trade for the boot: each
        # rung is a program traced, lowered and read back at every start
        # (1.7-2 s of a hybrid engine's), and what the trade costs is a ridden
        # chunk of a quarter of the top bucket or less padded to half of it.
        self._prefill_with_decode, self._mixed_buckets = None, ()
        if self._decode_ladder[0] == 1 and not (
            self.paged or self.fused_decode or self.mesh is not None or moe_impl is not None
        ):
            self._prefill_with_decode = _step_jit(
                JIT_PREFILL_WITH_DECODE, prefill_with_decode, donate_argnums=(1, 6, 7)
            )
            top = self._bucket(min(self.prefill_chunk, max(1, self.max_seq - 2)))
            self._mixed_buckets = tuple(b for b in PREFILL_BUCKETS if b <= top)[-2:]
        self._inject = jax.jit(inject, donate_argnums=(0, 1, 2, 3, 4, 5, 6))
        self._first_token = _step_jit(JIT_FIRST_TOKEN, first_token)
        if self._hybrid:
            from ..models import hybrid

            # the cache manager's moves on a lane's state, as the model
            # defines them: open a lane for a request (zeroing a fresh
            # context's state), stage a lane's leaves, write them back
            self._admit_state = jax.jit(hybrid.admit_lane, donate_argnums=(0,))
            self._lane_leaves = functools.partial(hybrid.snapshot_lane, n_kv_heads=cfg.n_kv_heads)
            self._restore_lane = hybrid.restore_lane
            self._positional = hybrid.HybridCache.POSITIONAL
        elif self._windowed:
            from ..models import llama

            # a lane's leaves as the model ships them: the global layers'
            # rows up to the position's bucket, the ring whole
            self._lane_leaves = llama.snapshot_lane
            self._restore_lane = llama.restore_lane
            self._positional = ("k", "v")
        # the verify ladder reuses the same forward (one prefill-shaped call
        # with t = k+1 per round); fns are built per bucket on demand and
        # warmed alongside the decode ladder
        self._run_forward = run_forward

    def _fused_fn(self):
        """Compiled fused decode loop (ISSUE 10, reworked for ISSUE 17): a
        ``lax.while_loop`` running up to ``nsteps`` iterations entirely on
        device, with per-lane EOS masking, in-loop n-gram speculation, a
        double-buffered injection slot, and a whole-batch early-exit
        predicate — the only host↔device traffic per loop is the dispatch
        and ONE packed readback at loop exit.

        Dynamic rung: ``nsteps`` is a RUNTIME int32 operand; buffers are
        sized by the static cap ``self._fused_cap``, so ONE executable
        serves every rung of the adaptive ladder (recompile budget stays 0
        by construction) and long uncontended rungs amortize dispatch
        overhead without new compiles.

        Injection slot: ``armed`` flags lanes whose staged shadow state
        (stok/spos/... written by ``_stage_lane`` while the previous loop
        was in flight) replaces the carry at entry — a freshly prefilled
        request is absorbed by the already-pipelined next loop without an
        exit-and-redispatch bubble.

        In-loop speculation (greedy lanes only): each iteration drafts up
        to ``FUSED_SPEC_K`` tokens by matching the lane's trailing 3-gram
        (2-gram fallback) against its ``FUSED_HIST_W``-token history
        carry, then verifies the drafts as a batched [B, K+1] forward in a
        ``lax.cond`` branch of the SAME loop body. Acceptance is argmax
        agreement, so greedy lanes stay bit-exact with both
        ``speculative=False`` and the host-side drafter; sampled lanes
        never draft (dlen=0) and consume exactly ``keys[i]`` per
        iteration, so their streams are identical too.

        Budget handling: ``budgets`` is a per-loop emission cap
        (min(remaining, chunk+1) estimated by the host). The device NEVER
        declares a budget finish — a lane hitting its cap freezes
        (``full``: real tok/pos retained, reason stays 0, excluded from
        the active set) and the authoritative host rescan in
        ``_process_fused`` decides. Host dispatch counts iterations, not
        emissions, so the estimate only ever OVERSHOOTS remaining budget —
        the safe direction under pipelined dispatch (a device park the
        host disagrees with would let the in-flight next loop decode a
        host-live lane at scratch).

        Readback packing: one int32 [cap+6, B] array — rows [0, cap+1)
        emitted tokens (-1 past a lane's count), then per-lane counts,
        finish reasons (0 running / 1 EOS), executed iteration count
        (broadcast), accepted-draft and drafted counts."""
        fn = self._fused_fns.get(self._fused_cap)
        if fn is not None:
            return fn
        run_forward = self._run_forward
        scratch_static = self.max_seq - 1
        eos_id = int(self.tokenizer.eos_id)
        cap_rows = self._fused_cap + 1  # budgets clamp at chunk+1 emissions
        K = FUSED_SPEC_K
        W = FUSED_HIST_W
        inloop_spec = self.inloop_spec
        approx = self.approx_topk
        greedy_cond = self.mesh is None
        # Static index matrices for the n-gram drafter: row d-1 of idx3
        # addresses the 3-token window at distance d back from the trailing
        # 3-gram (d in 1..W-3); first match = smallest d via argmax.
        d3_vals = jnp.arange(1, W - 2, dtype=jnp.int32)
        idx3 = (W - 3 - d3_vals)[:, None] + jnp.arange(3)[None, :]
        d2_vals = jnp.arange(1, W - 1, dtype=jnp.int32)
        idx2 = (W - 2 - d2_vals)[:, None] + jnp.arange(2)[None, :]

        def fused_body(  # atp: hot
            params, cache, tok, pos, temps, topk, topp, hist, hlen,
            stok, spos, stemps, stopk, stopp, shist, shlen,
            armed, live, budgets, ign, keys, nsteps, bt=None,
        ):
            scratch = cache.k.shape[2] - 1 if bt is None else scratch_static
            B = tok.shape[0]
            # Absorb the staged lane (if armed) at loop entry — the shadow
            # state was written while the previous loop was in flight.
            tok = jnp.where(armed, stok, tok)
            pos = jnp.where(armed, spos, pos)
            temps = jnp.where(armed, stemps, temps)
            topk = jnp.where(armed, stopk, topk)
            topp = jnp.where(armed, stopp, topp)
            hist = jnp.where(armed[:, None], shist, hist)
            hlen = jnp.where(armed, shlen, hlen)
            lane = jnp.arange(B)

            def draft_from_hist(hist, hlen):
                tail3 = hist[:, W - 3:]
                win3 = hist[:, idx3]  # [B, D3, 3]
                m3 = jnp.all(win3 == tail3[:, None, :], -1) & (
                    hlen[:, None] >= d3_vals[None, :] + 3
                )
                any3 = jnp.any(m3, 1)
                dstar3 = d3_vals[jnp.argmax(m3, 1)]
                tail2 = hist[:, W - 2:]
                win2 = hist[:, idx2]
                m2 = jnp.all(win2 == tail2[:, None, :], -1) & (
                    hlen[:, None] >= d2_vals[None, :] + 2
                )
                any2 = jnp.any(m2, 1)
                dstar2 = d2_vals[jnp.argmax(m2, 1)]
                dstar = jnp.where(any3, dstar3, dstar2)
                exists = any3 | any2
                gidx = jnp.minimum(
                    (W - dstar)[:, None] + jnp.arange(K)[None, :], W - 1
                )
                drafts = jnp.take_along_axis(hist, gidx, axis=1)  # [B, K]
                return exists, dstar, drafts

            def cond(c):
                i, done, full = c[0], c[4], c[5]
                return (i < nsteps) & jnp.any(~(done | full))

            def body(c):
                (i, tok, pos, cache, done, full, emitted, nemit, reason,
                 hist, hlen, nacc, ndr) = c
                rec = ~(done | full)
                room = budgets - nemit
                zeros_b = jnp.zeros((B,), jnp.int32)

                def _plain(cache):
                    logits, cache = run_forward(
                        params, tok[:, None], pos[:, None], cache, bt
                    )
                    nxt = sample_step(
                        logits[:, 0], keys[i], temps, topk, topp,
                        greedy_cond=greedy_cond, approx_topk=approx,
                    )
                    cand = jnp.concatenate(
                        [nxt[:, None], jnp.zeros((B, K), jnp.int32)], 1
                    )
                    return cache, cand, rec.astype(jnp.int32), zeros_b, zeros_b

                if inloop_spec:
                    exists, dstar, drafts = draft_from_hist(hist, hlen)
                    # draft only greedy active lanes with budget headroom;
                    # continuation length is capped by the match distance
                    # (the tokens that followed the matched occurrence)
                    dlen = jnp.where(
                        exists & (temps <= 0.0) & rec,
                        jnp.minimum(
                            jnp.minimum(dstar, K), jnp.maximum(room - 1, 0)
                        ),
                        0,
                    )

                    def _with_spec(cache):
                        toks = jnp.concatenate([tok[:, None], drafts], 1)
                        posm = jnp.minimum(
                            pos[:, None] + jnp.arange(K + 1)[None, :], scratch
                        )
                        logits, cache = run_forward(params, toks, posm, cache, bt)
                        greedy = jnp.argmax(logits, -1).astype(jnp.int32)
                        valid = jnp.arange(K)[None, :] < dlen[:, None]
                        ok = (drafts == greedy[:, :K]) & valid
                        a = jnp.cumprod(ok.astype(jnp.int32), 1).sum(1)
                        nxt0 = sample_step(
                            logits[:, 0], keys[i], temps, topk, topp,
                            greedy_cond=greedy_cond, approx_topk=approx,
                        )
                        # position j>0 emits the verifier's argmax: token j
                        # is either an accepted draft (== argmax by the
                        # acceptance rule) or the correction token
                        cand = jnp.concatenate([nxt0[:, None], greedy[:, 1:]], 1)
                        navail = jnp.where(rec, a + 1, 0)
                        return (
                            cache, cand, navail,
                            jnp.where(rec, a, 0), jnp.where(rec, dlen, 0),
                        )

                    cache, cand, navail, acc, dln = lax.cond(
                        jnp.any(dlen > 0), _with_spec, _plain, cache
                    )
                else:
                    cache, cand, navail, acc, dln = _plain(cache)

                navail = jnp.minimum(navail, jnp.maximum(room, 0))
                is_emit = jnp.arange(K + 1)[None, :] < navail[:, None]
                is_eos = is_emit & (cand == eos_id) & (~ign[:, None])
                has_eos = jnp.any(is_eos, 1)
                cnt = jnp.where(has_eos, jnp.argmax(is_eos, 1) + 1, navail)
                nemit = nemit + cnt
                reason = jnp.where((reason == 0) & has_eos, 1, reason)
                done = done | has_eos
                # cap-hit lanes FREEZE at their real tok/pos with reason 0:
                # the host rescan (authoritative for budget) either finishes
                # them or lets the already-pipelined next loop continue them
                full = full | (rec & ~has_eos & (nemit >= budgets))
                last = jnp.take_along_axis(
                    cand, jnp.maximum(cnt - 1, 0)[:, None], 1
                )[:, 0]
                tok = jnp.where(cnt > 0, last, tok)
                # EOS lanes park at scratch (finishing token recorded, never
                # fed); frozen/live lanes keep real positions
                pos = jnp.where(
                    done,
                    jnp.full_like(pos, scratch),
                    jnp.minimum(pos + cnt, scratch),
                )
                for j in range(K + 1):
                    ridx = jnp.where(j < cnt, nemit - cnt + j, cap_rows)
                    emitted = emitted.at[ridx, lane].set(
                        cand[:, j], mode="drop"
                    )
                ext = jnp.concatenate([hist, cand], 1)
                hist = jnp.take_along_axis(
                    ext, jnp.arange(W)[None, :] + cnt[:, None], 1
                )
                hlen = jnp.minimum(hlen + cnt, W)
                return (
                    i + 1, tok, pos, cache, done, full, emitted, nemit,
                    reason, hist, hlen, nacc + acc, ndr + dln,
                )

            init = (
                jnp.int32(0),
                tok,
                pos,
                cache,
                ~live | (budgets <= 0),
                jnp.zeros((B,), bool),
                jnp.full((cap_rows, B), -1, jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                hist,
                hlen,
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
            )
            (i, tok, pos, cache, done, full, emitted, nemit, reason,
             hist, hlen, nacc, ndr) = lax.while_loop(cond, body, init)
            packed = jnp.concatenate(
                [
                    emitted,
                    nemit[None, :],
                    reason[None, :],
                    jnp.broadcast_to(i, (1, B)).astype(jnp.int32),
                    nacc[None, :],
                    ndr[None, :],
                ],
                axis=0,
            )
            return packed, tok, pos, temps, topk, topp, hist, hlen, cache

        if self.paged:

            def fused_paged(
                params, cache, bt, tok, pos, temps, topk, topp, hist, hlen,
                stok, spos, stemps, stopk, stopp, shist, shlen,
                armed, live, budgets, ign, keys, nsteps,
            ):
                return fused_body(
                    params, cache, tok, pos, temps, topk, topp, hist, hlen,
                    stok, spos, stemps, stopk, stopp, shist, shlen,
                    armed, live, budgets, ign, keys, nsteps, bt,
                )

            fn = self._fused_fns[self._fused_cap] = _step_jit(
                JIT_FUSED, fused_paged, donate_argnums=(1, 3, 4, 5, 6, 7, 8, 9)
            )
        else:
            fn = self._fused_fns[self._fused_cap] = _step_jit(
                JIT_FUSED, fused_body, donate_argnums=(1, 2, 3, 4, 5, 6, 7, 8)
            )
        return fn

    def warmup(self, boot: Spans | None = None) -> None:
        """Pre-compile every serve-path signature BY SERVING: one synthetic
        request per reachable prefill bucket runs through the real worker
        machinery (admission → chunked prefill → device-carry injection →
        pipelined decode → finish/park), so the executable cache is
        populated with exactly the signatures real traffic produces —
        shapes AND argument placement. Hand-rolled device calls kept
        missing signatures (a committed first-token scalar vs an
        uncommitted placeholder re-compiles the same shapes), so the first
        real request still paid a compile (VERDICT r3 weak #6). Chunked
        prefill feeds at most ``prefill_chunk`` tokens per tick, so the
        reachable buckets are those ≤ bucket(min(prefill_chunk,
        max_seq-2)). Runs behind the loading marker — /health answers 503
        throughout; telemetry from warmup traffic is dropped at the end.

        Five parts, each a span of ``boot`` (under ``create``'s
        ``boot.warmup``), entered where the engine has the part:
        ``boot.warmup_serve`` (the bucket passes and the decode ladder),
        ``_snapshot``, ``_prefix``, ``_verify`` and ``_mixed``."""
        boot = Spans() if boot is None else boot
        top_bucket = self._bucket(min(self.prefill_chunk, max(1, self.max_seq - 2)))
        filler = min(5, self.cfg.vocab_size - 1)

        async def _one(n_prompt: int, mt: int) -> None:
            loop = asyncio.get_running_loop()
            req = GenRequest(
                id="",
                session="",
                prompt_ids=[self.tokenizer.bos_id] + [filler] * (n_prompt - 1),
                max_tokens=mt,
                temperature=0.0,
                loop=loop,
                future=loop.create_future(),
            )
            self._queue.put(req)
            await req.future

        async def _serve_all() -> None:
            for b in PREFILL_BUCKETS:
                if b > top_bucket:
                    break
                # land exactly in bucket b: the longest admissible prompt
                # caps at max_seq-2 (budget with max_tokens=1), so undersized
                # arenas still reach their top bucket
                n = max(1, min(b, self.max_seq - 2))
                mt = max(1, min(self.decode_chunk, self.max_seq - 1 - n))
                await _one(n, mt)
            if self.decode_steps == 0:
                # pathological shapes can finish every bucket pass without a
                # decode chunk; force one so decode compiles here, not at
                # the first real request
                await _one(1, min(self.decode_chunk + 1, max(2, self.max_seq // 2)))
            # compile the adaptive chunk ladder: each bucket is its own
            # lax.scan length (its own executable). max_tokens = c + 1 makes
            # the remaining budget after the prefill-sampled first token
            # exactly c, so the dispatcher picks bucket c.
            for c in self._decode_ladder:
                if c >= self.decode_chunk:
                    break  # the full chunk compiled in the passes above
                await _one(1, min(c + 1, max(2, self.max_seq - 2)))

        # dedicated thread: asyncio.run must not land on a thread that is
        # already inside a running loop (LLMEngine.create is called from
        # async tests and from the serve app's loader thread alike)
        box: list[BaseException] = []

        def _runner() -> None:
            try:
                asyncio.run(_serve_all())
            except BaseException as e:  # surface warmup faults to create()
                box.append(e)

        # the arena stays OFF while warmup serves: the bucket passes share a
        # filler-token prefix, and a prefix hit would shrink a pass's tail
        # below its bucket — exactly the prefill signature warmup exists to
        # compile. The fork/slice fns are warmed explicitly below instead.
        # Speculation is OFF too: the filler prompts are maximally
        # repetitive, and a spec round replacing a decode chunk would leave
        # ladder buckets uncompiled. The verify ladder is warmed explicitly.
        with boot.span("boot.warmup_serve"):
            self._prefix_active = False
            self._spec_active = False
            try:
                t = threading.Thread(target=_runner, name="llm-warmup")
                t.start()
                t.join()
            finally:
                self._prefix_active = self.prefix_cache
                self._spec_active = self.speculative
        if box:
            raise box[0]
        # pre-compile the snapshot slicers too: their first jit used to
        # land on the serving worker thread mid-traffic, stalling every
        # in-flight decode for the compile's duration, long enough to 502
        # a request at the proxy
        with boot.span("boot.warmup_snapshot"):
            if self.paged:
                # paged snapshot stagers: exact-page-count gathers, warmed at
                # pow2 counts (odd counts compile on demand — a trivial gather)
                c = 1
                while True:
                    count = min(c, self._n_blocks)
                    ids = jnp.zeros((count,), jnp.int32)
                    jax.block_until_ready(self._snap_fn_paged(count)(self.cache, ids))
                    if c >= self._n_blocks:
                        break
                    c *= 2
            else:
                b = PREFILL_BUCKETS[0]
                snap_buckets = set()
                while True:
                    snap_buckets.add(min(b, self.max_seq))
                    if b >= self.max_seq:
                        break
                    b *= 2
                for bucket in sorted(snap_buckets):
                    jax.block_until_ready(self._snap_fn(bucket)(self.cache, jnp.int32(0)))
        # prefix-arena copy fns (same warm-up pattern as the snapshot
        # slicers): one slice + one fork executable per bucket level, so an
        # admission-time fork never pays a serve-time compile. The fork
        # round-trips slot 0's own rows — it writes back exactly what it
        # read, so warmed state is untouched. Paged engines fork by PAGE
        # MAPPING (no compiled copy at all); only the partial-tail CoW
        # single-page copy needs warming.
        if self.prefix_cache:
            with boot.span("boot.warmup_prefix"):
                if self.paged:
                    scr = jnp.int32(self._scratch_page(0))
                    self.cache = self._page_copy_fn()(self.cache, scr, scr)
                else:
                    for b in self._prefix_levels:
                        k, v = self._prefix_slice_fn(b)(self.cache, jnp.int32(0))
                        self.cache = self._prefix_fork_fn(b)(self.cache, jnp.int32(0), k, v)
                jax.block_until_ready(self.cache.k)
        # verify ladder (speculative decoding): one compiled k-token verify
        # program per bucket, exercised against the live carry/cache — all
        # lanes are parked at scratch here, so the round's writes land in
        # the scratch rows exactly like plain parked decode. A serving-time
        # spec round must never pay a compile.
        if self.speculative:
            with boot.span("boot.warmup_verify"):
                for b in self._spec_buckets:
                    self._rng, key = jax.random.split(self._rng)
                    _, _, self._dtok, self._dpos, self.cache = self._verify_fn(b)(
                        self.params,
                        self.cache,
                        *self._bt_arg(),
                        self._dtok,
                        self._dpos,
                        self._dtemps,
                        self._dtopk,
                        self._dtopp,
                        jnp.zeros((self.max_batch, b), jnp.int32),
                        jnp.zeros((self.max_batch,), jnp.int32),
                        key,
                    )
                jax.block_until_ready(self.cache.k)
        # the mixed step (a prefill chunk that carries the decode lanes'
        # step): warm-up serves one request at a time and so never has a
        # chunk pending beside a decoding lane. One program per rung a
        # ridden chunk can take (``_mixed_buckets``: the plain ladder's two
        # largest, the gate in ``_build_compiled`` says what that trades),
        # against the live carry and cache like the verify ladder:
        # every lane is parked, the chunk's rows land in slot 0, which
        # ``clear_sessions`` below leaves cold.
        if self._prefill_with_decode is not None:
            with boot.span("boot.warmup_mixed"):
                for b in self._mixed_buckets:
                    self._launch_with_decode(
                        0,
                        jnp.asarray(np.zeros((1, b), np.int32)),
                        jnp.asarray(np.arange(b, dtype=np.int32)[None]),
                        b,
                    )
                jax.block_until_ready(self.cache)
        # warmup traffic is not serving telemetry: TTFT samples here include
        # compile time and would pollute p50s until the deque rolls over
        self.clear_sessions()
        self.ttft_ms_recent.clear()
        self.itl_ms_recent.clear()
        self.admission_ms_recent.clear()
        self.prefill_ms_recent.clear()
        self.first_readback_ms_recent.clear()
        self._launches.reset()
        self.decode_chunks_shrunk = 0
        self.fused_loops_total = 0
        self.fused_steps_total = 0
        self.fused_early_exits_total = 0
        self.fused_exit_reason_hist = {}
        self.fused_injections_total = 0
        self.fused_inject_fallbacks_total = 0
        self.inloop_spec_drafted = 0
        self.inloop_spec_accepted = 0
        self._prefix_entries.clear()
        self._prefix_bytes = 0
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_saved = 0
        self.prefix_evictions = 0
        self.session_evictions = 0
        self.session_eviction_idle_s_recent.clear()
        self.prefix_eviction_idle_s_recent.clear()
        self.tokens_generated = 0
        self.prefills = 0
        self.requests_finished = 0
        self.flops_done = 0.0
        self.hbm_bytes_read = 0.0
        self._last_decode_end = None
        if self.paged:
            # warmup's anonymous sessions already freed their pages at
            # finish; reclaim anything still quarantined and zero the
            # pool-telemetry counters so serving starts from a clean gauge
            self._release_quarantine()
            self.page_exhausted_total = 0
            self.prefix_pages_shared = 0
        self._started_at = time.monotonic()

    # -- public API (called from the aiohttp loop) ------------------------
    def load_depth(self) -> int:
        """Submit-side load estimate: queued + drained-but-unadmitted +
        in-flight GENERATION requests. Snapshot/restore commands ride the
        same queue but are not admission load — counting them would shed
        serveable traffic whenever per-turn KV snapshots burst. Approximate
        by design: admission control needs a watermark comparison, not an
        exact census."""
        with self._queue.mutex:
            queued = sum(1 for it in self._queue.queue if isinstance(it, GenRequest))
        return (
            queued
            + sum(1 for it in self._waiting if isinstance(it, GenRequest))
            + sum(1 for s in self.slots if s.request is not None)
        )

    async def generate(
        self,
        prompt: str,
        max_tokens: int = 64,
        temperature: float = 0.0,
        request_id: str = "",
        session: str = "",
        deadline_at: float | None = None,
        ignore_eos: bool = False,
        top_k: int = 0,
        top_p: float = 1.0,
        emit=None,
    ) -> dict:
        if request_id:
            with self._lock:
                hit = self._completed.get(request_id)
            if hit is not None:
                return dict(hit, replayed=True)
        # failpoint: submit-side fault (chaos soak's "engine rejects work")
        # — surfaces to the serve layer exactly like any submit error
        await faults.fire_async("engine.submit")
        if self._draining:
            raise EngineDraining("engine draining for shutdown")
        if self.deadlines and self.shed_watermark:
            depth = self.load_depth()
            if depth >= self.shed_watermark:
                self.shed_total += 1
                raise EngineOverloaded(depth, self.shed_watermark)
        loop = asyncio.get_running_loop()
        prompt_ids = self.tokenizer.encode(prompt)
        req = GenRequest(
            id=request_id or f"gen-{time.monotonic_ns()}",
            session=session,
            prompt_ids=prompt_ids,
            max_tokens=max(1, max_tokens),
            temperature=temperature,
            loop=loop,
            future=loop.create_future(),
            deadline_at=deadline_at if self.deadlines else None,
            ignore_eos=ignore_eos,
            top_k=max(0, int(top_k)),
            top_p=min(1.0, max(0.0, float(top_p))) if top_p is not None else 1.0,
            emit=emit,
        )
        self._queue.put(req)
        result = await req.future
        if request_id:
            with self._lock:
                self._completed[request_id] = result
                while len(self._completed) > 512:
                    self._completed.popitem(last=False)
        return result

    async def chat(
        self,
        session: str,
        message: str,
        max_tokens: int = 64,
        request_id: str = "",
        deadline_at: float | None = None,
        ignore_eos: bool = False,
        emit=None,
    ) -> dict:
        return await self.generate(
            prompt=message,
            max_tokens=max_tokens,
            temperature=0.0,
            request_id=request_id,
            session=session or "default",
            deadline_at=deadline_at,
            ignore_eos=ignore_eos,
            emit=emit,
        )

    def cancel(self, request_id: str) -> bool:
        """Request-id cancel path (client disconnected / operator abort).
        Queued or waiting items are rejected before prefill; an in-flight
        lane is parked mid-decode on the next worker iteration and its slot
        freed for admission. Returns False for ids already completed (the
        memoized result stands — a replay may still claim it); True means
        the abort was recorded and the worker will act on it."""
        if not request_id:
            return False
        with self._lock:
            if request_id in self._completed:
                return False
            self._cancel_requested[request_id] = time.monotonic()
        return True

    async def snapshot_session(self, session: str) -> bytes | None:
        """Serialize a session's live KV prefix for the store.

        Two stages: the WORKER thread stages the slot's prefix into fresh
        cache-dtype device buffers (bounded bucket shapes — a handful of compiled
        slice programs, instead of one XLA program per distinct position),
        then the npz pack + blocking device→host readback runs in an
        executor thread so neither the worker nor the event loop stalls on
        the transfer.
        """
        loop = asyncio.get_running_loop()
        # failpoint: snapshot-serialize fault — surfaces through the serve
        # layer's kv_snapshot_errors counter, never into the decode path
        await faults.fire_async("engine.snapshot")
        staged = None
        for _ in range(5):  # global limiter may ask us to come back later
            cmd = SnapshotCmd(session=session, loop=loop, future=loop.create_future())
            self._queue.put(cmd)
            staged = await cmd.future
            if staged != "rate-limited":
                break
            await asyncio.sleep(self.snapshot_min_gap_s)
        if staged == "rate-limited":
            # distinguishable give-up: the caller decides whether to retry
            # later or surface it — silently returning None here would be
            # indistinguishable from "session has nothing to save"
            raise SnapshotDeferred(session)
        if staged is None:
            return None
        leaves, position, pending_token = staged
        from .checkpoint import pack_snapshot

        # read the staged buffers to the host and let go of them BEFORE the
        # slow half (the npz compression of an incompressible 0.8 GB takes
        # tens of seconds): a staged lane then stands on the device for a
        # transfer's time, and the next staging waits only for that
        # (``_staged_on_device``)
        leaves = await asyncio.to_thread(lambda staged: {n: np.asarray(a) for n, a in staged.items()}, leaves)
        del staged, cmd  # the future's result is the last holder of the device buffers
        meta = {"session": session, "pending_token": pending_token}
        if self.paged:
            # staged from live pages only (ceil(position/page_size) pages,
            # not a pow2 position bucket); payload layout is identical to
            # the dense staging so blobs restore across both arenas
            meta["page_size"] = self.page_size
        positional = self._positional if self._named_leaves else ("k", "v")
        return await asyncio.to_thread(pack_snapshot, leaves, position, meta, positional)

    def _do_snapshot(self, cmd: SnapshotCmd) -> None:
        """Worker-thread half of snapshot_session: dispatch the bucketed
        slice (async on the device queue) and hand the staged buffers to the
        caller. No blocking readback here — decode keeps flowing."""
        if self.paged:
            sess = self.paged_sessions.get(cmd.session)
            if sess is None:
                cmd.loop.call_soon_threadsafe(_resolve_value, cmd.future, None)
                return
            self._snap_last_by_session.setdefault(cmd.session, time.monotonic())
            if sess.lane is not None and self.slots[sess.lane].request is not None:
                if cmd.session in self._snap_parked:
                    cmd.loop.call_soon_threadsafe(
                        _resolve_value, cmd.future, "rate-limited"
                    )
                else:
                    self._snap_parked[cmd.session] = cmd
                return
            self._stage_snapshot_paged(cmd, sess)
            return
        idx = self.sessions.get(cmd.session)
        if idx is None:
            cmd.loop.call_soon_threadsafe(_resolve_value, cmd.future, None)
            return
        # the durability clock for a session starts at its FIRST snapshot
        # attempt (not engine boot): a fresh session under load stages
        # within snapshot_force_s of its first turn, no sooner
        self._snap_last_by_session.setdefault(cmd.session, time.monotonic())
        slot = self.slots[idx]
        if slot.request is not None:
            # mid-generation: PARK the command on the session and stage at
            # the request's finish — that instant is an idle-slot moment by
            # construction, so under back-to-back turns the snapshot can
            # never lose the race with the next admission (round-5 bench:
            # the "try now, give up if busy" policy produced kv_snapshots=0
            # under load). One parked command per session; extras bounce.
            if cmd.session in self._snap_parked:
                cmd.loop.call_soon_threadsafe(_resolve_value, cmd.future, "rate-limited")
            else:
                self._snap_parked[cmd.session] = cmd
            return
        self._stage_snapshot(cmd, slot)

    def _snap_gate(self, session: str) -> bool:
        """Shared staging limiter (dense slot and paged session alike):
        True = rate-limited this time. A snapshot's device→host readback
        serializes with decode on the device link (an 8B bucket-128 blob
        is ~17 MB), so stagings are spaced out;
        the per-session durability floor forces one through eventually."""
        now = time.monotonic()
        busy = any(s.decoding or s.pending_prompt for s in self.slots)
        # durability floor is PER SESSION: with a global timer, whichever
        # session staged first reset it for everyone and the other sessions
        # starved for N×30s under sustained multi-session load
        session_last = self._snap_last_by_session.get(session, self._snap_epoch0)
        overdue = now - session_last >= self.snapshot_force_s
        # busy stagings are spaced wider: each one costs ~a second of device
        # link the in-flight generations are using, so under sustained load
        # the per-session floor degrades gracefully to ~n_sessions×busy_gap
        gap = self.snapshot_busy_gap_s if busy else self.snapshot_min_gap_s
        gap_ok = now - self._last_snapshot_at >= gap
        return self._staged_on_device() or (not gap_ok) or (busy and not overdue)

    def _staged_on_device(self) -> bool:
        """Does the last staged snapshot still stand on the device? Its
        leaves are fresh device buffers until the caller has read them to
        the host (``snapshot_session``); a lane of a 16k arena is most of a
        GB (0.8 GB where window layers ship their ring whole), and staging
        the next one meanwhile stacks them on the arena's headroom."""
        held = self._staged_leaf
        return held is not None and held() is not None

    def _hold_staged(self, leaves: dict) -> None:
        self._staged_leaf = weakref.ref(next(iter(leaves.values())))

    def _staged_bytes(self, cmd: SnapshotCmd, slot: Slot) -> dict:
        """``bytes="leaf=n,..."`` of one lane staged at the slot's bucket: what
        each kind of leaf ships, for a cache of several kinds."""
        if not self._named_leaves or slot.position <= 0:
            return {}
        bucket = self._snap_bucket(slot.position)
        known = self._staged_bytes_by_bucket.get(bucket)
        if known is None:
            shapes = jax.eval_shape(lambda c: self._lane_leaves(c, 0, bucket), self.cache)
            known = self._staged_bytes_by_bucket[bucket] = ",".join(
                f"{name}={int(np.prod(a.shape)) * a.dtype.itemsize}" for name, a in shapes.items()
            )
        return {"bytes": known}

    @_phase("engine.snapshot", attrs=_staged_bytes)
    def _stage_snapshot(self, cmd: SnapshotCmd, slot: Slot) -> None:
        """Stage a settled slot's prefix (worker thread), limiter-gated."""
        staged = None
        if self._snap_gate(cmd.session):
            staged = "rate-limited"
        elif slot.position > 0:
            now = time.monotonic()
            self._last_snapshot_at = now
            self._snap_last_by_session[cmd.session] = now
            leaves = self._snap_fn(self._snap_bucket(slot.position))(
                self.cache, jnp.int32(slot.idx)
            )
            if not self._named_leaves:
                leaves = dict(zip(("k", "v"), leaves))
            else:
                self.state_snapshots += 1
            try:
                for leaf in leaves.values():
                    leaf.copy_to_host_async()
            except Exception:
                pass
            self._hold_staged(leaves)
            staged = (leaves, slot.position, slot.pending_token)
        cmd.loop.call_soon_threadsafe(_resolve_value, cmd.future, staged)

    @_phase("engine.snapshot")
    def _stage_snapshot_paged(self, cmd: SnapshotCmd, sess: PagedSession) -> None:
        """Paged staging: gather ONLY the session's live pages into a
        contiguous buffer — a 100-token session ships 2 pages, not a pow2
        position bucket — same limiter, same exact-dtype discipline."""
        staged = None
        if self._snap_gate(cmd.session):
            staged = "rate-limited"
        elif sess.position > 0 and sess.pages:
            now = time.monotonic()
            self._last_snapshot_at = now
            self._snap_last_by_session[cmd.session] = now
            count = min(
                len(sess.pages), (sess.position - 1) // self.page_size + 1
            )
            ids = jnp.asarray(np.asarray(sess.pages[:count], dtype=np.int32))
            k16, v16 = self._snap_fn_paged(count)(self.cache, ids)
            try:
                k16.copy_to_host_async()
                v16.copy_to_host_async()
            except Exception:
                pass
            staged = ({"k": k16, "v": v16}, sess.position, sess.pending_token)
            self._hold_staged(staged[0])
        cmd.loop.call_soon_threadsafe(_resolve_value, cmd.future, staged)

    def _service_parked_snapshot(self, slot: Slot) -> None:
        """Called at a request's finish: stage any snapshot parked on this
        session while the slot is provably idle."""
        cmd = self._snap_parked.pop(slot.session, None) if slot.session else None
        if cmd is not None:
            if self.paged and slot.psess is not None:
                self._stage_snapshot_paged(cmd, slot.psess)
            else:
                self._stage_snapshot(cmd, slot)

    def _flush_parked_snapshot(self, session: str) -> None:
        """Session going away (eviction/reset/clear): a parked snapshot
        command must resolve rather than hang its caller forever."""
        self._snap_last_by_session.pop(session, None)
        cmd = self._snap_parked.pop(session, None)
        if cmd is not None:
            cmd.loop.call_soon_threadsafe(_resolve_value, cmd.future, None)

    def _snap_bucket(self, position: int) -> int:
        """Next power of two ≥ position, capped at max_seq — a handful of
        compiled snapshot-slice shapes total (NOT one per position, and not
        capped at the prefill buckets' 1024: long-context sessions past
        1024 tokens must not have their tails silently truncated)."""
        b = PREFILL_BUCKETS[0]
        while b < position:
            b *= 2
        return min(b, self.max_seq)

    def _snap_fn(self, bucket: int):
        fn = self._snap_fns.get(bucket)
        if fn is None:

            def _snap(cache, i, _b=bucket):
                # EXACT dtype, no fp16 round-trip: the snapshot restores
                # into the same-dtype arena, and "resume token-identical"
                # is a bit-equality claim — an fp16 staging cast rounded
                # fp32/bf16 KV and flipped near-tie greedy argmaxes after
                # restore (found by the chaos soak's resume invariant).
                # bf16/fp16 caches ship 2 bytes/elem as before; fp32 CPU
                # caches pay 2x blob size for exactness.
                if self._named_leaves:
                    # named leaves: the positional rows up to the bucket,
                    # the per-lane state (or a window layer's ring) whole
                    return self._lane_leaves(cache, i, _b)
                k = lax.dynamic_slice_in_dim(cache.k, i, 1, axis=1)[:, 0, :_b]
                v = lax.dynamic_slice_in_dim(cache.v, i, 1, axis=1)[:, 0, :_b]
                return k, v

            fn = self._snap_fns[bucket] = jax.jit(_snap)
        return fn

    # -- tiered KV hierarchy: device → pinned host RAM → store ------------
    #
    # Parking reuses the snapshot plane's staging fns (exact dtype, bounded
    # shapes) and the pool's quarantine discipline for the freed pages;
    # promotion reuses the restore fns. Tier transfers are pure data
    # movement — no new compiled variants, ever (recompile budget 0).

    async def park_session(self, session: str) -> bytes | None:
        """Demote an idle session's KV off the device into the host RAM
        tier and return its exact SNAP_VERSION 3 blob for the store (the
        cold tier — survives the process and the host tier's LRU budget).
        None: tiering off, session unknown/busy, or the demote failpoint
        fired — in every case the session is left exactly as it was."""
        if not self.kv_tiering:
            return None
        loop = asyncio.get_running_loop()
        cmd = ParkCmd(session=session, loop=loop, future=loop.create_future())
        self._queue.put(cmd)
        staged = await cmd.future
        if staged is None:
            return None
        k, v, position, pending_token = staged
        from .checkpoint import pack_kv_snapshot

        meta = {"session": session, "pending_token": pending_token}
        if self.paged:
            meta["page_size"] = self.page_size
        return await asyncio.to_thread(pack_kv_snapshot, k, v, position, meta)

    async def prewarm_session(self, session: str) -> bool:
        """Promote a host-tier session back onto the device ahead of its
        next turn (the proxy's next-arrival hint). True when the session
        is device-resident afterwards (including already-resident)."""
        if not self.kv_tiering:
            return False
        loop = asyncio.get_running_loop()
        cmd = PrewarmCmd(session=session, loop=loop, future=loop.create_future())
        self._queue.put(cmd)
        return bool(await cmd.future)

    def has_session(self, session: str) -> bool:
        """Membership across tiers: device-resident OR parked in host RAM.
        The serve layer asks this instead of ``in sessions`` so a parked
        session is never mistaken for unknown (which would store-restore
        stale context and re-prepend the system prompt — duplicated
        context breaks resume parity)."""
        if session in self.sessions:
            return True
        with self._tier_lock:
            return session in self._host_tier

    def _do_park(self, cmd: ParkCmd) -> None:
        """Worker half of park_session: demote and hand the exact staged
        host arrays back for the caller's store blob."""
        staged = self._tier_demote(cmd.session) if self.kv_tiering else None
        cmd.loop.call_soon_threadsafe(_resolve_value, cmd.future, staged)

    def _do_prewarm(self, cmd: PrewarmCmd) -> None:
        ok = self._tier_promote(cmd.session, prewarm=True) if self.kv_tiering else False
        cmd.loop.call_soon_threadsafe(_resolve_value, cmd.future, ok)

    def _tier_needs_promote(self, item) -> bool:
        """Admission-path check: this request's session is parked in host
        RAM and must swap in before _try_admit can see it."""
        return (
            self.kv_tiering
            and isinstance(item, GenRequest)
            and bool(item.session)
            and item.session not in self.sessions
            and item.session in self._host_tier
        )

    @_phase("engine.tier_move")
    def _tier_demote(self, session: str, pressure: bool = False):
        """Worker thread: stage an idle session's exact KV prefix to host,
        free its device residency (pages via the quarantine discipline),
        and insert the host-tier entry (int8 per-page-scale quantized when
        tier_quantize is on). Returns the exact (k, v, position,
        pending_token) host arrays on success — the store blob is packed
        from THESE, before any quantization, so the cold tier keeps the
        bit-exact resume guarantee — or None with the session untouched."""
        try:
            # failpoint: a failed demote means the session simply STAYS
            # device-resident — parking is an optimization, never a
            # correctness step
            faults.fire("engine.kv_demote")
        except Exception:
            self.tier_demote_failures_total += 1
            return None
        if self.paged:
            sess = self.paged_sessions.get(session)
            if (
                sess is None
                or sess.lane is not None
                or not sess.pages
                or sess.position <= 0
            ):
                return None
            count = min(len(sess.pages), (sess.position - 1) // self.page_size + 1)
            ids = jnp.asarray(np.asarray(sess.pages[:count], dtype=np.int32))
            k16, v16 = self._snap_fn_paged(count)(self.cache, ids)
            # block on the gather BEFORE freeing the pages: the staged
            # buffers are fresh arrays, but materializing them proves the
            # read finished, so the freed pages can't be rewritten under it
            k = np.asarray(k16)[:, : sess.position]
            v = np.asarray(v16)[:, : sess.position]
            position, pending = sess.position, sess.pending_token
            spec = (list(sess.spec_hist), sess.spec_ema, sess.spec_miss)
            with self._page_lock:
                self._flush_parked_snapshot(session)
                self._free_session_pages(sess)
                self.paged_sessions.pop(session, None)
                self.sessions.pop(session, None)
        else:
            idx = self.sessions.get(session)
            if idx is None or idx < 0:
                return None
            slot = self.slots[idx]
            if slot.request is not None or slot.position <= 0:
                return None
            k16, v16 = self._snap_fn(self._snap_bucket(slot.position))(
                self.cache, jnp.int32(slot.idx)
            )
            k = np.asarray(k16)[:, : slot.position]
            v = np.asarray(v16)[:, : slot.position]
            position, pending = slot.position, slot.pending_token
            spec = (list(slot.spec_hist), slot.spec_ema, slot.spec_miss)
            self._flush_parked_snapshot(session)
            self.sessions.pop(session, None)
            slot.session = ""
            slot.position = 0
            slot.pending_token = None
            slot.prefix_ctx = None
            slot.spec_hist = []
            slot.spec_ema = 1.0
            slot.spec_miss = 0
            slot.epoch += 1
        if self.tier_quantize:
            from .quant import quantize_kv_pages

            qk, sk = quantize_kv_pages(k, self.page_size)
            qv, sv = quantize_kv_pages(v, self.page_size)
            entry = TieredEntry(
                k=qk,
                v=qv,
                k_scale=sk,
                v_scale=sv,
                quantized=True,
                pages=int(qk.shape[1]),
                position=position,
                pending_token=pending,
                nbytes=qk.nbytes + qv.nbytes + sk.nbytes + sv.nbytes,
                parked_at=time.monotonic(),
                spec_hist=spec[0],
                spec_ema=spec[1],
                spec_miss=spec[2],
            )
        else:
            entry = TieredEntry(
                k=k,
                v=v,
                position=position,
                pending_token=pending,
                nbytes=k.nbytes + v.nbytes,
                parked_at=time.monotonic(),
                spec_hist=spec[0],
                spec_ema=spec[1],
                spec_miss=spec[2],
            )
        self._tier_insert_host(session, entry, pressure=pressure)
        return k, v, position, pending

    def _tier_drop_locked(self, session: str):
        """Remove a host-tier entry + its gauge contribution. Caller holds
        _tier_lock. Returns the entry (or None)."""
        entry = self._host_tier.pop(session, None)
        if entry is not None:
            self.tier_host_bytes -= entry.nbytes
            if entry.quantized:
                self.tier_quantized_pages -= entry.pages
        return entry

    def _tier_insert_host(self, session: str, entry, pressure: bool = False) -> None:
        with self._tier_lock:
            self._tier_drop_locked(session)
            self._host_tier[session] = entry
            self._host_tier.move_to_end(session)
            self.tier_host_bytes += entry.nbytes
            if entry.quantized:
                self.tier_quantized_pages += entry.pages
            self.tier_demotions_total += 1
            if pressure:
                self.tier_pressure_demotions_total += 1
            # host budget: LRU entries fall through to the store-only cold
            # tier (their blob was written at park; the serve layer's
            # restore-on-unknown path serves their next turn)
            while (
                self.tier_host_bytes > self.tier_host_budget_bytes
                and len(self._host_tier) > 1
            ):
                oldest = next(iter(self._host_tier))
                self._tier_drop_locked(oldest)

    @_phase("engine.tier_move")
    def _tier_promote(self, session: str, prewarm: bool = False) -> bool:
        """Worker thread: swap a host-tier session back onto the device.
        The restore dispatch is ASYNC (no readback) — called from the
        admission path it overlaps the queue-wait phase of the returning
        turn's TTFT. On failure the entry stays parked and False returns
        (the admission path maps it to typed 429 backpressure)."""
        with self._tier_lock:
            entry = self._host_tier.get(session)
        if entry is None:
            return session in self.sessions
        t0 = time.monotonic()
        try:
            faults.fire("engine.kv_promote")
        except Exception:
            self.tier_promote_failures_total += 1
            return False
        if entry.quantized:
            from .quant import dequantize_kv_pages

            k = dequantize_kv_pages(entry.k, entry.k_scale, entry.position)
            v = dequantize_kv_pages(entry.v, entry.v_scale, entry.position)
        else:
            k, v = entry.k, entry.v
        if self.paged:
            ok = self._tier_promote_paged(session, entry, k, v)
        else:
            ok = self._tier_promote_dense(session, entry, k, v)
        if not ok:
            self.tier_promote_failures_total += 1
            return False
        with self._tier_lock:
            self._tier_drop_locked(session)
        self.tier_promotions_total += 1
        if prewarm:
            self.tier_prewarm_hits_total += 1
        if len(self._tier_promote_started) > 256:
            cutoff = t0 - 300.0
            for name in [
                n for n, t in self._tier_promote_started.items() if t < cutoff
            ]:
                self._tier_promote_started.pop(name, None)
        self._tier_promote_started[session] = t0
        return True

    def _tier_promote_paged(self, session: str, entry, k, v) -> bool:
        if entry.position <= 0 or entry.position >= self.max_seq - 1:
            return False
        if session in self.paged_sessions:
            return True  # already resident (stale host entry; caller drops it)
        count = (entry.position - 1) // self.page_size + 1
        try:
            ids = self._alloc_pages(count, serving=False)
        except EngineOverloaded:
            return False
        k = np.asarray(k)
        v = np.asarray(v)
        pad = count * self.page_size - k.shape[1]
        if pad:
            widths = [(0, 0), (0, pad)] + [(0, 0)] * (k.ndim - 2)
            k = np.pad(k, widths)
            v = np.pad(v, widths)
        dtype = self.cache.k.dtype
        self.cache = self._restore_fn_paged(count)(
            self.cache,
            jnp.asarray(np.asarray(ids, dtype=np.int32)),
            jnp.asarray(k, dtype),
            jnp.asarray(v, dtype),
        )
        sess = PagedSession(
            name=session,
            pages=ids,
            position=entry.position,
            pending_token=entry.pending_token,
            last_used=time.monotonic(),
            spec_hist=list(entry.spec_hist),
            spec_ema=entry.spec_ema,
            spec_miss=entry.spec_miss,
        )
        with self._page_lock:
            self.paged_sessions[session] = sess
            self.sessions[session] = -1
        return True

    def _tier_promote_dense(self, session: str, entry, k, v) -> bool:
        from .checkpoint import restore_kv_slot

        if entry.position <= 0 or entry.position >= self.max_seq - 1:
            return False
        slot = self._find_slot(session)
        if slot is None:
            return False
        self.cache = restore_kv_slot(self.cache, slot.idx, k, v)
        slot.position = entry.position
        slot.pending_token = entry.pending_token
        slot.last_used = time.monotonic()
        slot.spec_hist = list(entry.spec_hist)
        slot.spec_ema = entry.spec_ema
        slot.spec_miss = entry.spec_miss
        return True

    def _tier_pressure_demote(self, need: int) -> None:
        """Pool pressure (paged, worker thread, OUTSIDE _page_lock — the
        staging readback blocks): demote idle resident sessions LRU-first
        to the host tier until ``need`` pages are coverable. Where
        _reclaim_pages destroys the victim's context, demotion preserves
        it — a would-be 429 becomes a slower-but-served admission and the
        victim's next turn promotes instead of re-prefilling."""
        if not (self.kv_tiering and self.paged):
            return

        def short() -> bool:
            with self._page_lock:
                return len(self._page_free) + len(self._page_quarantine) < need

        while short():
            victim = None
            with self._page_lock:
                for sess in self.paged_sessions.values():
                    if sess.lane is not None or not sess.pages or sess.position <= 0:
                        continue
                    if victim is None or sess.last_used < victim.last_used:
                        victim = sess
            if victim is None:
                return
            if self._tier_demote(victim.name, pressure=True) is None:
                return  # demote failpoint or raced a new turn: stop, don't spin

    def _tier_metrics(self) -> dict:
        with self._tier_lock:
            host_sessions = len(self._host_tier)
            host_bytes = self.tier_host_bytes
            quantized_pages = self.tier_quantized_pages
        overlap = sorted(self.tier_promote_overlap_ms_recent)
        return {
            "kv_tiering": self.kv_tiering,
            "tier_quantize": self.tier_quantize,
            "tier_host_sessions": host_sessions,
            "tier_host_bytes": host_bytes,
            "tier_quantized_pages": quantized_pages,
            "tier_demotions_total": self.tier_demotions_total,
            "tier_promotions_total": self.tier_promotions_total,
            "tier_pressure_demotions_total": self.tier_pressure_demotions_total,
            "tier_prewarm_hits_total": self.tier_prewarm_hits_total,
            "tier_demote_failures_total": self.tier_demote_failures_total,
            "tier_promote_failures_total": self.tier_promote_failures_total,
            "tier_promote_overlap_ms_p50": (
                round(overlap[len(overlap) // 2], 2) if overlap else None
            ),
        }

    # -- paged arena: page allocator + block tables -----------------------
    #
    # Host-side bookkeeping for the device page pool. The free list /
    # refcounts / block table live in numpy under _page_lock (the worker
    # allocates; API threads clear sessions), and the table ships to the
    # device as a per-dispatch argument. Refcounting is what makes prefix
    # sharing zero-copy: a cached prefix PINS its pages, sessions map them
    # read-only (they never write below their fork point), and a page is
    # returned to the free list only when its last reference drops.

    def _scratch_page(self, lane: int) -> int:
        """Lane ``lane``'s dedicated scratch page: every block-table entry
        not covered by the bound session's pages points here, so parked
        decode steps and bucket-padding writes land in per-lane garbage
        that no live query's position mask ever exposes."""
        return self._data_pages + lane

    def _bt_arg(self) -> tuple:
        """The block-table positional argument the paged compiled fns take
        between ``cache`` and the token state — empty in dense mode, so
        shared call sites splat it instead of duplicating argument lists."""
        return (jnp.asarray(self._bt),) if self.paged else ()

    def _alloc_pages(
        self, n: int, serving: bool = True, reclaim: bool = True
    ) -> list[int]:
        """Take ``n`` pages off the free list, evicting idle resident
        sessions (then unpinning prefix entries) LRU-first when the list
        runs dry. Raises PagePoolExhausted — mapped to 429 backpressure by
        the serve layer — when reclaim cannot cover the need; the pool
        being full of in-flight work is overload, not a fault."""
        if n <= 0:
            return []
        if serving:
            # failpoint: deterministic pool-exhaustion injection (chaos
            # soak). Any injected error surfaces as the same backpressure
            # a genuinely full pool produces — never a crash.
            try:
                faults.fire("engine.page_alloc")
            except Exception as e:
                self.page_exhausted_total += 1
                with self._page_lock:
                    free = len(self._page_free)
                raise PagePoolExhausted(n, free) from e
        self._reap_quarantine_if_short(n)
        if reclaim and self.kv_tiering:
            with self._page_lock:
                tier_short = (
                    len(self._page_free) + len(self._page_quarantine) < n
                )
            if tier_short:
                # demote idle residents to the HOST TIER before destructive
                # reclaim: parked context survives for its next turn, and
                # the freed pages convert a would-be 429 into admission
                self._tier_pressure_demote(n)
                self._reap_quarantine_if_short(n)
        with self._page_lock:
            if len(self._page_free) < n and reclaim:
                self._reclaim_pages(n)
        # eviction frees land in quarantine while readbacks are in flight;
        # take them back before declaring exhaustion
        self._reap_quarantine_if_short(n)
        with self._page_lock:
            if len(self._page_free) < n:
                if serving:
                    # only SERVING allocations are backpressure events: a
                    # best-effort internal alloc (prefix tail pin) failing
                    # must not inflate the 429 evidence counter
                    self.page_exhausted_total += 1
                raise PagePoolExhausted(n, len(self._page_free))
            ids = [self._page_free.pop() for _ in range(n)]
            for pid in ids:
                self._page_refs[pid] = 1
            return ids

    @_phase("engine.evict")
    def _reclaim_pages(self, need: int) -> None:
        """Evict until ``need`` pages are free (or nothing evictable is
        left): idle resident sessions LRU-first — they can re-prefill (or
        restore from their store snapshot) — then prefix-arena pins, which
        only cost the next cold prefill. In-flight sessions are never
        touched. Caller holds _page_lock. Quarantined pages COUNT toward
        the goal (the caller reaps them right after): with readbacks in
        flight every eviction's pages land in quarantine, and a loop
        watching only the free list would keep evicting — one transient
        one-page shortfall wiping every idle resident and prefix pin."""

        def short() -> bool:
            return len(self._page_free) + len(self._page_quarantine) < need

        while short():
            victim = None
            for sess in self.paged_sessions.values():
                if sess.lane is not None or not sess.pages:
                    continue
                if victim is None or sess.last_used < victim.last_used:
                    victim = sess
            if victim is None:
                break
            self._count_eviction("session", time.monotonic() - victim.last_used)
            self._free_session_pages(victim)
            self.paged_sessions.pop(victim.name, None)
            self.sessions.pop(victim.name, None)
            self._flush_parked_snapshot(victim.name)
        now = time.monotonic()
        while short() and any(
            e.pages is not None for e in self._prefix_entries.values()
        ):
            self._prefix_evict_lru(now)

    def _free_page_ids(self, ids: list[int]) -> None:
        """Return zero-ref pages to the free list — via quarantine when
        readbacks are in flight: a chunk dispatched before the free holds
        the OLD device block table and will still write into these pages,
        so reallocating them before its readback drains would let parked
        garbage corrupt another session's KV."""
        if not ids:
            return
        with self._page_lock:
            if self._readbacks:
                self._page_quarantine.extend(ids)
            else:
                self._page_free.extend(ids)

    def _reap_quarantine_if_short(self, need: int) -> None:
        """Allocation-path quarantine release (worker thread): when the
        free list can't cover ``need``, WAIT for the in-flight device work
        to finish — NOT for the readback FIFO to process. Draining the
        FIFO here would run admissions and finishes in the middle of a
        dispatch whose lane snapshot the caller already captured, desyncing
        token delivery. Every cache-writing dispatch chains through the
        donated pool (self.cache is the newest link), so the current
        cache being ready proves every stale-block-table write has landed
        and the whole quarantine is reallocatable. Token readbacks still
        pending in the FIFO are independent device arrays — releasing the
        pages under them is safe."""
        with self._page_lock:
            if len(self._page_free) >= need or not self._page_quarantine:
                return
        try:
            with self._spans.span("engine.wait_device"):
                jax.block_until_ready(self.cache.k)
        except Exception:
            return  # can't prove the writes landed; quarantine stays parked
        with self._page_lock:
            self._page_free.extend(self._page_quarantine)
            self._page_quarantine = []

    def _release_quarantine(self) -> None:
        """Worker loop, once the readback FIFO is empty: every dispatch
        that could touch quarantined pages has drained."""
        if self._page_quarantine and not self._readbacks:
            with self._page_lock:
                if self._page_quarantine and not self._readbacks:
                    self._page_free.extend(self._page_quarantine)
                    self._page_quarantine = []

    def _decref_page(self, pid: int) -> None:
        with self._page_lock:
            self._page_refs[pid] -= 1
            if self._page_refs[pid] <= 0:
                self._page_refs[pid] = 0
                self._free_page_ids([pid])

    def _free_session_pages(self, sess: PagedSession) -> None:
        pages, sess.pages, sess.shared = sess.pages, [], 0
        for pid in pages:
            self._decref_page(pid)

    def _bind_lane_bt(self, slot: Slot, sess: PagedSession) -> None:
        """Point the lane's block-table row at the session's pages; every
        uncovered block falls back to the lane's scratch page."""
        self._bt[slot.idx, :] = self._scratch_page(slot.idx)
        if sess.pages:
            self._bt[slot.idx, : len(sess.pages)] = sess.pages

    def _ensure_lane_pages(self, slot: Slot, upto_pos: int, serving: bool) -> None:
        """Grow the bound session's page list (and the lane's table row) to
        cover writes through logical position ``upto_pos``. Called before
        every prefill/decode/verify dispatch so the compiled call never
        needs in-flight table growth; allocation failure surfaces as
        PagePoolExhausted for THIS request only."""
        sess = slot.psess
        if sess is None:
            return
        blocks = min(max(0, upto_pos), self.max_seq - 2) // self.page_size + 1
        have = len(sess.pages)
        if have >= blocks:
            return
        new = self._alloc_pages(blocks - have, serving=serving)
        sess.pages.extend(new)
        self._bt[slot.idx, have:blocks] = new

    def _truncate_session_pages(self, sess: PagedSession) -> None:
        """Page-tail truncation: free whole pages beyond the live context.
        This is what speculative rewind and chunk overshoot become in the
        paged arena — rejected-draft KV beyond ``position`` was already
        position-masked; here the PAGES holding only such garbage go back
        to the pool instead of staying pinned to the session."""
        with self._page_lock:
            keep = (
                0 if sess.position <= 0 else (sess.position - 1) // self.page_size + 1
            )
            keep = max(keep, sess.shared)  # never drop mapped prefix pages
            if len(sess.pages) <= keep:
                return
            tail = sess.pages[keep:]
            del sess.pages[keep:]
            if sess.lane is not None:
                # un-map the freed blocks from the live lane: a stale table
                # entry is read-masked but must never be WRITTEN through
                self._bt[sess.lane, keep:] = self._scratch_page(sess.lane)
            for pid in tail:
                self._decref_page(pid)

    def _rollback_lane_session(self, slot: Slot) -> None:
        """Paged lane reset for a POLICY failure (pool exhaustion → 429):
        unlike a fault, no dispatch died mid-write — the session's KV below
        its admission-time position is intact, and only this request's
        prefill/partial generation (which the recorded history will never
        contain) must go. Truncate back, restore the admission-time pending
        token, and keep the session RESIDENT: the client's Retry-After
        retry continues the conversation instead of finding it destroyed."""
        sess = slot.psess
        if sess is None or not sess.name or sess.admit_position <= 0:
            # fresh or anonymous context: nothing pre-request to preserve
            # (a fresh prefix-hit admission advanced position, but those
            # mapped tokens belong to the failed request — drop them too)
            self._drop_lane_session(slot)
            return
        with self._page_lock:
            slot.psess = None
            self._bt[slot.idx, :] = self._scratch_page(slot.idx)
            # roll position back too: the prefix map and speculative accept
            # syncs both advance it mid-request, and every such token
            # belongs to the request that just failed with 429
            sess.position = sess.admit_position
            sess.pending_token = sess.admit_pending
            # spec_hist was extended in place at admission (and by every
            # accepted token since); restore the saved copy so a retry of
            # the same prompt doesn't duplicate its region in the drafting
            # corpus and tank the lookup accept rate
            sess.spec_hist = list(sess.admit_spec_hist)
            sess.last_used = time.monotonic()
            sess.lane = None
            self.sessions[sess.name] = -1
            self._truncate_session_pages(sess)
        slot.session = ""
        # the session is provably idle right now: stage any snapshot that
        # parked while the failed request was in flight (mirrors the finish
        # path's _service_parked_snapshot — without this the parked cmd's
        # future never resolves and the serve layer awaits it forever)
        cmd = self._snap_parked.pop(sess.name, None)
        if cmd is not None:
            self._stage_snapshot_paged(cmd, sess)

    def _drop_lane_session(self, slot: Slot) -> None:
        """Paged half of a lane reset after a FAULT/abort: the bound
        session's KV is no longer trusted (the failed call may have died
        mid-write), so its pages go back to the pool and the session
        leaves residency entirely (the store snapshot still allows resume)."""
        with self._page_lock:
            sess, slot.psess = slot.psess, None
            self._bt[slot.idx, :] = self._scratch_page(slot.idx)
            if sess is None:
                return
            self._free_session_pages(sess)
            if sess.name:
                self.paged_sessions.pop(sess.name, None)
                self.sessions.pop(sess.name, None)
                self._flush_parked_snapshot(sess.name)
            slot.session = ""

    def _detach_lane(self, slot: Slot) -> None:
        """A finished request releases its COMPUTE lane while the session
        stays resident in pages — the decoupling that lets resident
        sessions outnumber max_batch. Lane spec/position state syncs back
        to the session; anonymous (sessionless) generations free their
        pages immediately."""
        with self._page_lock:
            sess, slot.psess = slot.psess, None
            self._bt[slot.idx, :] = self._scratch_page(slot.idx)
            if sess is None:
                return
            sess.spec_ema = slot.spec_ema
            sess.spec_miss = slot.spec_miss
            sess.last_used = time.monotonic()
            sess.lane = None
            if sess.name:
                self.sessions[sess.name] = -1
                self._truncate_session_pages(sess)
            else:
                self._free_session_pages(sess)
        slot.session = ""
        slot.position = 0
        slot.pending_token = None
        slot.spec_hist = []

    # paged compiled helpers: exact-page-count gather/scatter programs.
    # Counts are bounded by the block-table width (≤ max_seq/page_size
    # distinct shapes, each a trivial gather), warmed at pow2 counts.

    def _snap_fn_paged(self, count: int):
        fn = self._snap_paged_fns.get(count)
        if fn is None:

            def _snap(cache, ids):
                # EXACT dtype (see _snap_fn): gather ONLY the session's
                # live pages and lay them out contiguously — the blob
                # layout matches the dense staging, so snapshots restore
                # across paged and dense engines alike
                return pages_to_rows(cache.k[:, ids]), pages_to_rows(cache.v[:, ids])

            fn = self._snap_paged_fns[count] = jax.jit(_snap)
        return fn

    def _restore_fn_paged(self, count: int):
        fn = self._restore_paged_fns.get(count)
        if fn is None:

            def _restore(cache, ids, k, v):
                # k/v arrive as rows [L, count * page_size, KV, hd] (the
                # snapshot layout); scatter into the session's
                # freshly-allocated pages
                return type(cache)(
                    cache.k.at[:, ids].set(rows_to_pages(k, self.page_size)),
                    cache.v.at[:, ids].set(rows_to_pages(v, self.page_size)),
                )

            fn = self._restore_paged_fns[count] = jax.jit(
                _restore, donate_argnums=(0,)
            )
        return fn

    def _page_copy_fn(self):
        """One-page pool copy (src → dst): the partial-tail copy-on-write
        for non-page-aligned prefix levels. Full pages are never copied —
        that is the zero-copy claim."""
        fn = self._page_copy_fn_cached
        if fn is None:

            def _copy(cache, src, dst):
                k = lax.dynamic_slice_in_dim(cache.k, src, 1, axis=1)
                v = lax.dynamic_slice_in_dim(cache.v, src, 1, axis=1)
                return type(cache)(
                    lax.dynamic_update_slice_in_dim(cache.k, k, dst, axis=1),
                    lax.dynamic_update_slice_in_dim(cache.v, v, dst, axis=1),
                )

            fn = self._page_copy_fn_cached = jax.jit(_copy, donate_argnums=(0,))
        return fn

    # -- prefix arena (cross-session KV reuse; worker thread) -------------
    @staticmethod
    def _rolling_hashes(tokens: list[int]) -> dict[int, int]:
        """FNV-1a rolling hash of the token-id stream, sampled at every
        prefill-bucket boundary: hashes[b] keys the exact prefix tokens[:b].
        One O(len) pass per admission/registration — the same order of work
        as tokenizing the prompt."""
        h = 1469598103934665603
        out: dict[int, int] = {}
        bi = 0
        for i, t in enumerate(tokens):
            h = ((h ^ (int(t) + 1)) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
            if bi < len(PREFILL_BUCKETS) and i + 1 == PREFILL_BUCKETS[bi]:
                out[PREFILL_BUCKETS[bi]] = h
                bi += 1
        return out

    def _prefix_slice_fn(self, bucket: int):
        """Copy a slot's first ``bucket`` KV positions into FRESH device
        buffers (one compiled program per bucket, like _snap_fn). The
        outputs are independent arrays, so they survive every later
        donation of the main cache. No dtype cast: a forked prefix must be
        bit-exact with the prefill that produced it."""
        fn = self._prefix_slice_fns.get(bucket)
        if fn is None:

            def _slice(cache, i, _b=bucket):
                k = lax.dynamic_slice_in_dim(cache.k, i, 1, axis=1)[:, 0, :_b]
                v = lax.dynamic_slice_in_dim(cache.v, i, 1, axis=1)[:, 0, :_b]
                return k, v

            fn = self._prefix_slice_fns[bucket] = jax.jit(_slice)
        return fn

    def _prefix_fork_fn(self, bucket: int):
        """Write an arena entry into a slot's rows at position 0 (the
        admission-time fork). Donates the cache — in-place on device; the
        entry buffers are NOT donated, so the arena can fork the same
        prefix into any number of later sessions."""
        fn = self._prefix_fork_fns.get(bucket)
        if fn is None:

            def _fork(cache, i, k, v):
                newk = lax.dynamic_update_slice(cache.k, k[:, None], (0, i, 0, 0, 0))
                newv = lax.dynamic_update_slice(cache.v, v[:, None], (0, i, 0, 0, 0))
                return KVCache(newk, newv)

            fn = self._prefix_fork_fns[bucket] = jax.jit(_fork, donate_argnums=(0,))
        return fn

    def _prefix_lookup(self, prompt: list[int]):
        """Longest cached prefix at bucket granularity, or None. A hit must
        leave at least one prompt token to prefill (the first generated
        token is sampled from prefill logits). Hash match is verified by
        exact token equality — a collision degrades to a miss."""
        limit = len(prompt) - 1
        hashes = self._rolling_hashes(prompt)
        for b in reversed(self._prefix_levels):
            if b > limit:
                continue
            key = (b, hashes.get(b))
            entry = self._prefix_entries.get(key)
            if entry is not None and entry.tokens == tuple(prompt[:b]):
                return key, entry
        return None

    def _prefix_register(self, slot: Slot) -> None:
        """Final-prefill-chunk hook: store every bucket-level prefix of a
        fresh-context prompt that isn't cached yet. Each level is one
        async device copy; positions [0:b] hold real KV for exactly
        ctx[:b] by causality (later tokens cannot influence them).
        Best-effort — a failure here must never fail the generation."""
        ctx = slot.prefix_ctx
        slot.prefix_ctx = None
        if ctx is None or not self._prefix_active:
            return
        n = min(len(ctx), slot.position)
        try:
            hashes = self._rolling_hashes(ctx)
            now = time.monotonic()
            for b in self._prefix_levels:
                if b > n:
                    break
                key = (b, hashes[b])
                if key in self._prefix_entries:
                    continue
                if self.paged:
                    if not self._prefix_register_paged(slot, ctx, b, key, now):
                        break
                    continue
                k, v = self._prefix_slice_fn(b)(self.cache, jnp.int32(slot.idx))
                nbytes = int(k.nbytes + v.nbytes)
                if nbytes > self._prefix_budget:
                    break  # larger levels only grow — stop here
                while (
                    self._prefix_bytes + nbytes > self._prefix_budget
                    and self._prefix_entries
                ):
                    self._prefix_evict_lru(now)
                self._prefix_entries[key] = PrefixEntry(
                    k=k,
                    v=v,
                    tokens=tuple(ctx[:b]),
                    nbytes=nbytes,
                    created=now,
                    last_used=now,
                )
                self._prefix_bytes += nbytes
        except Exception as e:
            self._note_error(e)

    def _prefix_register_paged(
        self, slot: Slot, ctx: list[int], b: int, key: tuple, now: float
    ) -> bool:
        """Zero-copy paged registration: pin the owning session's full
        pages below ``b`` by refcount — no device copy at all for
        page-aligned levels. A non-aligned level (bucket 32 under the
        64-token default page) eagerly copies its partial tail page once,
        because the owner keeps writing the rest of that page. Returns
        False to stop the level walk (budget exhausted)."""
        sess = slot.psess
        if sess is None:
            return False
        full = b // self.page_size
        tail_len = b % self.page_size
        page_bytes = self._page_nbytes()
        nbytes = (full + (1 if tail_len else 0)) * page_bytes
        if nbytes > self._prefix_budget:
            return False
        if len(sess.pages) < full + (1 if tail_len else 0):
            return False  # context shorter than the level (can't happen)
        # budget charge is the DISTINCT pinned page count: levels of one
        # context share their full pages, so summing per-entry spans (the
        # dense formula, where every level is a real private copy) would
        # double-count and stop registration far short of the budget
        full_pages = sess.pages[:full]

        def projected() -> int:
            pinned = self._prefix_pinned_page_ids()
            extra = sum(1 for p in full_pages if p not in pinned)
            return (len(pinned) + extra + (1 if tail_len else 0)) * page_bytes

        while projected() > self._prefix_budget and self._prefix_entries:
            self._prefix_evict_lru(now)
        tail_page = None
        if tail_len:
            # best-effort, no reclaim: pinning a prefix must never evict a
            # live resident session, and a full pool just stops the level
            # walk — registration is an optimization, not backpressure
            try:
                tail_page = self._alloc_pages(1, serving=False, reclaim=False)[0]
            except EngineOverloaded:
                return False
            self.cache = self._page_copy_fn()(
                self.cache, jnp.int32(sess.pages[full]), jnp.int32(tail_page)
            )
        pages = list(sess.pages[:full])
        with self._page_lock:
            for pid in pages:
                self._page_refs[pid] += 1
        self._prefix_entries[key] = PrefixEntry(
            k=None,
            v=None,
            tokens=tuple(ctx[:b]),
            nbytes=nbytes,
            created=now,
            last_used=now,
            pages=pages,
            tail_page=tail_page,
            tail_len=tail_len,
        )
        self._recount_prefix_pinned()
        return True

    def _page_nbytes(self) -> int:
        return int((self.cache.k.nbytes + self.cache.v.nbytes) / self._total_pages)

    def _prefix_pinned_page_ids(self) -> set[int]:
        """Distinct physical pages pinned by the paged prefix arena —
        levels of one context share pages, so per-entry spans overlap."""
        pinned: set[int] = set()
        for e in self._prefix_entries.values():
            if e.pages is not None:
                pinned.update(e.pages)
                if e.tail_page is not None:
                    pinned.add(e.tail_page)
        return pinned

    def _recount_prefix_pinned(self) -> None:
        self._prefix_bytes = len(self._prefix_pinned_page_ids()) * self._page_nbytes()

    def _prefix_evict_lru(self, now: float | None = None) -> None:
        key, entry = self._prefix_entries.popitem(last=False)
        self._prefix_bytes -= entry.nbytes
        if entry.pages is not None:
            # unpin: sessions still mapping these pages keep their own
            # references — only the arena's pin drops
            for pid in entry.pages:
                self._decref_page(pid)
            if entry.tail_page is not None:
                self._decref_page(entry.tail_page)
            # distinct-page accounting: surviving entries may still pin
            # pages this entry shared, so recount instead of subtracting
            self._recount_prefix_pinned()
        self._count_eviction(
            "prefix", (now or time.monotonic()) - entry.last_used
        )

    def _count_eviction(self, kind: str, idle_s: float) -> None:
        """Shared eviction counter path (session slots AND prefix arena):
        a prefix hit-rate regression is diagnosed by which pool churns."""
        if kind == "session":
            self.session_evictions += 1
            self.session_eviction_idle_s_recent.append(idle_s)
        else:
            self.prefix_evictions += 1
            self.prefix_eviction_idle_s_recent.append(idle_s)

    async def restore_session(self, session: str, blob: bytes) -> bool:
        """Load a snapshot into a fresh slot (worker-thread mediated)."""
        from .checkpoint import deserialize_snapshot

        leaves, header = deserialize_snapshot(blob)
        loop = asyncio.get_running_loop()
        cmd = RestoreCmd(
            session=session,
            k=leaves.get("k"),
            v=leaves.get("v"),
            position=int(header["position"]),
            pending_token=header.get("pending_token"),
            loop=loop,
            future=loop.create_future(),
            leaves=leaves,
        )
        self._queue.put(cmd)
        return await cmd.future

    def clear_sessions(self, prefix: str = "") -> None:
        """Drop idle sessions (all, or only those whose name starts with
        ``prefix`` — a multi-tenant host clears one tenant's namespace
        without touching its co-tenants' KV)."""
        if self.kv_tiering:
            # host-tier entries are sessions too: clearing must not leave
            # a parked copy that the next same-named session promotes
            with self._tier_lock:
                for name in [s for s in self._host_tier if s.startswith(prefix)]:
                    self._tier_drop_locked(name)
        if self.paged:
            with self._page_lock:
                for name in [s for s in self.paged_sessions if s.startswith(prefix)]:
                    sess = self.paged_sessions[name]
                    if sess.lane is not None:
                        continue  # request in flight; same skip as dense
                    self._flush_parked_snapshot(name)
                    self._free_session_pages(sess)
                    self.paged_sessions.pop(name, None)
                    self.sessions.pop(name, None)
            return
        with self._lock:
            for name in [s for s in self.sessions if s.startswith(prefix)]:
                idx = self.sessions.pop(name)
                self._flush_parked_snapshot(name)
                slot = self.slots[idx]
                if slot.request is None:
                    slot.session = ""
                    slot.position = 0
                    slot.epoch += 1

    # -- the launch counters: sums over the ledger, under their old names ----
    @property
    def prefill_launches(self) -> int:
        """Launches that fed a prompt's chunk, a mixed launch among them."""
        return self._launches.total("n", *_PREFILL_PROGRAMS)

    @property
    def prefill_tokens(self) -> int:
        """The real tokens those launches carried (bucket padding excluded)."""
        return self._launches.total("rows", *_PREFILL_PROGRAMS) - self._launches.total("lanes", *_PREFILL_PROGRAMS)

    @property
    def decode_steps(self) -> int:
        """Launches that stepped the decode lanes: a rung, a fused loop, a
        verify round, a prefill chunk that carried the step."""
        return self._launches.total("n", *_DECODE_PROGRAMS)

    @property
    def decode_chunk_hist(self) -> dict[int, int]:
        """The decode driver's launches by rung (``jit_decode_n``'s, or the
        fused loop's by its bound): never a mixed launch."""
        return {int(k): n for k, n in self._launches.by_key(JIT_DECODE_N, JIT_FUSED).items()}

    @property
    def spec_verify_hist(self) -> dict[int, int]:
        return {int(k): n for k, n in self._launches.by_key(JIT_VERIFY).items()}

    @property
    def mixed_launches(self) -> int:
        """Prefill launches that carried the decode lanes' step with them
        (``jit_prefill_with_decode``)."""
        return self._launches.total("n", JIT_PREFILL_WITH_DECODE)

    @property
    def mixed_decode_lanes(self) -> int:
        """The live lanes that rode those launches."""
        return self._launches.total("lanes", JIT_PREFILL_WITH_DECODE)

    @property
    def forward_passes(self) -> int:
        """Passes through the model's layers launched so far, every step
        program counted (a decode launch of n steps is n): what a device
        trace's size follows (``h_profile`` bounds a capture by it)."""
        return self._launches.total("steps")

    def launches(self) -> dict:
        """The launch ledger as ``/metrics`` carries it (utils/launches.py)."""
        return self._launches.snapshot()

    def metrics(self) -> dict:
        elapsed = max(1e-6, time.monotonic() - self._started_at)
        decode_steps = self.decode_steps
        recent = sorted(self.ttft_ms_recent)
        itl = sorted(self.itl_ms_recent)
        adm = sorted(self.admission_ms_recent)
        pre = sorted(self.prefill_ms_recent)
        frb = sorted(self.first_readback_ms_recent)
        return {
            # where the worker's time went, cumulative since it started
            # (read as differences): per phase n / self_s / total_s, and
            # loop_s, the wall time its spans tile (utils/spans.py)
            **self._spans.snapshot(),
            # every launch of a step program by XLA module name and key
            # (rung, bucket, K): how many, what they carried, and the seconds
            # in service of those read back alone (utils/launches.py;
            # cumulative, read as differences); ``last_capture`` is the same
            # document at the two edges of the newest /profile capture
            "launches": self.launches(),
            "last_capture": self.last_capture,
            "tokens_generated": self.tokens_generated,
            "tokens_per_s": round(self.tokens_generated / elapsed, 2),
            "prefills": self.prefills,
            "prefill_launches": self.prefill_launches,
            "prefill_tokens": self.prefill_tokens,
            "requests_finished": self.requests_finished,
            "decode_steps": decode_steps,
            # mean share of the lanes that stepped, over those launches
            "batch_occupancy": round(
                self._launches.total("lanes", *_DECODE_PROGRAMS) / self.max_batch / max(1, decode_steps), 3
            ),
            "ttft_ms_p50": round(recent[len(recent) // 2], 2) if recent else None,
            "itl_ms_p50": round(itl[len(itl) // 2], 2) if itl else None,
            # TTFT phase decomposition: queue-wait (admission_ms, submit →
            # first prefill chunk dispatched) + prefill (first chunk →
            # first-token injection) + first-readback (injection → token on
            # host) ≈ ttft_ms per request
            "admission_ms_p50": round(adm[len(adm) // 2], 2) if adm else None,
            "admission_samples": [round(x, 2) for x in self.admission_ms_recent],
            "ttft_prefill_ms_p50": round(pre[len(pre) // 2], 2) if pre else None,
            "ttft_first_readback_ms_p50": round(frb[len(frb) // 2], 2) if frb else None,
            "ttft_prefill_samples": [round(x, 2) for x in self.prefill_ms_recent],
            "ttft_first_readback_samples": [
                round(x, 2) for x in self.first_readback_ms_recent
            ],
            # adaptive decode-chunk policy: configured chunk, dispatched
            # chunk-size histogram, and how often contention shrank it
            "decode_chunk": self.decode_chunk,
            "adaptive_decode": self.adaptive_decode,
            "decode_chunk_hist": {str(k): v for k, v in sorted(self.decode_chunk_hist.items())},
            "decode_chunks_shrunk": self.decode_chunks_shrunk,
            # prefill launches that carried the decode lanes' step (they
            # count in prefill_launches AND decode_steps, never in
            # decode_chunk_hist, which is ``jit_decode_n``'s), and the live
            # lanes that rode: mixed_decode_lanes ÷ (batch_occupancy ×
            # decode_steps × max_batch) is the share of lane-steps that
            # cost no weight stream of their own
            "mixed_launches": self.mixed_launches,
            "mixed_decode_lanes": self.mixed_decode_lanes,
            # self-speculative decoding: drafted/accepted token
            # counters, verify-bucket histogram, and each slot's live
            # acceptance EMA — a collapsed gamma shows up as EMAs pinned
            # under the floor while spec_rounds stops advancing
            "speculative": self.speculative,
            "spec_rounds": self.spec_rounds,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_verify_hist": {str(k): v for k, v in sorted(self.spec_verify_hist.items())},
            "spec_slot_acceptance": [round(s.spec_ema, 3) for s in self.slots],
            # fused on-device decode loop: loops dispatched, device steps
            # executed (early exits run fewer than the rung), early-exit
            # count, exit-reason histogram, and the host-sync economics —
            # host_syncs_per_token is THE fused-vs-unfused readback claim
            # as a gauge (one sync per loop exit vs one per chunk, plus
            # the shared first-token and spec-round syncs in both modes)
            "fused_decode": self.fused_decode,
            "fused_loops_total": self.fused_loops_total,
            "fused_steps_total": self.fused_steps_total,
            "fused_early_exits_total": self.fused_early_exits_total,
            "fused_exit_reason_hist": dict(
                sorted(self.fused_exit_reason_hist.copy().items())
            ),
            # ISSUE 17: double-buffered lane injection (staged absorbs vs
            # exit-and-redispatch fallbacks) and in-loop n-gram speculation
            # (device-counted drafted/accepted, read back in the packed
            # loop transfer — no extra syncs)
            "fused_injections_total": self.fused_injections_total,
            "fused_inject_fallbacks_total": self.fused_inject_fallbacks_total,
            "inloop_spec": self.inloop_spec,
            "inloop_spec_drafted": self.inloop_spec_drafted,
            "inloop_spec_accepted": self.inloop_spec_accepted,
            "approx_topk": self.approx_topk,
            "host_syncs_per_token": (
                round(self._launches.reads() / self.tokens_generated, 4)
                if self.tokens_generated
                else None
            ),
            "worker_errors": self.worker_errors,
            "last_worker_error": self.last_worker_error or None,
            "cache_resets": self.cache_resets,
            # request-lifecycle policy plane: deadlines/cancel/shed state.
            # queue_depth/waiting_depth/active_requests are the admission
            # picture the control plane's shedding watermark reads.
            "deadlines": self.deadlines,
            "queue_depth": self._queue.qsize(),
            "waiting_depth": len(self._waiting),
            "active_requests": sum(1 for s in self.slots if s.request is not None),
            "cancelled_total": self.cancelled_total,
            "expired_total": self.expired_total,
            "shed_total": self.shed_total,
            "shed_watermark": self.shed_watermark or None,
            "draining": self._draining,
            # prefix arena (cross-session KV reuse): hit/miss/saved counters
            # plus occupancy — tokens_saved is prefill work the fork skipped
            "prefix_cache": self.prefix_cache,
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_tokens_saved": self.prefix_tokens_saved,
            "prefix_arena_entries": len(self._prefix_entries),
            "prefix_arena_bytes": self._prefix_bytes,
            "prefix_arena_capacity_bytes": self._prefix_budget,
            "prefix_evictions_total": self.prefix_evictions,
            # session-slot LRU eviction (was silent): count + idle age of
            # the evictees, so "why did my session re-prefill" is answerable
            "session_evictions_total": self.session_evictions,
            "session_eviction_idle_s_p50": (
                round(sev[len(sev) // 2], 2)
                if (sev := sorted(self.session_eviction_idle_s_recent))
                else None
            ),
            "prefix_eviction_idle_s_p50": (
                round(pev[len(pev) // 2], 2)
                if (pev := sorted(self.prefix_eviction_idle_s_recent))
                else None
            ),
            # paged KV arena (block tables): pool occupancy gauges replace
            # the dense-only slot accounting as the HBM audit — resident
            # sessions are bounded by pages, not max_batch, so capacity
            # questions are answered here
            **self._paged_metrics(),
            # tiered KV hierarchy: per-tier session counts, host-tier
            # bytes/quantized pages, demote/promote/prewarm totals, and the
            # promote-overlap hidden-ms — the capacity claim's gauges
            **self._tier_metrics(),
            # raw append-ordered samples (bounded deques): lets a caller
            # window percentiles over ITS measurement interval instead of
            # whatever warmup/compile history the deque still holds
            "ttft_samples": [round(x, 2) for x in self.ttft_ms_recent],
            "itl_samples": [round(x, 2) for x in self.itl_ms_recent],
            "max_batch": self.max_batch,
            "max_seq": self.max_seq,
            "tp": self.tp,
            "ep": self.ep,
            "meshed_flash": self.meshed_flash,
            "moe_routed": self.routed_moe,
            # FLOP model + HBM telemetry: lifetime MFU here is a floor
            # (includes idle time); bench_llm.py samples flops_done twice
            # and computes windowed MFU over the loaded interval
            "flops_done": self.flops_done,
            "hbm_bytes_read": self.hbm_bytes_read,
            **self._utilization_metrics(elapsed),
            # the device as JAX reports it (count = every device this
            # process sees), the devices the engine computes on, and which
            # attention implementation its compiled steps trace and why
            "device": {
                "platform": self._devices[0].platform,
                "kind": self._devices[0].device_kind,
                "count": jax.device_count(),
            },
            "engine_devices": [self._device_doc(d) for d in self._devices],
            "attention": dict(self.attention),
            # which MoE path the compiled steps trace, and the block's shape
            "moe": dict(self.moe),
            **({"linear": dict(self.linear)} if self.linear is not None else {}),
            "model_arch": {
                "layers": self.cfg.n_layers,
                "dim": self.cfg.dim,
                "heads": self.cfg.n_heads,
                "kv_heads": self.cfg.n_kv_heads,
                "head_dim": self.cfg.head_dim,
                "qk_norm": self.cfg.qk_norm,
                # the mixers by kind (every layer "gqa" where none is named)
                "layer_kinds": (
                    {k: self.cfg.layer_kinds.count(k) for k in sorted(set(self.cfg.layer_kinds))}
                    or {"gqa": self.cfg.n_layers}
                ),
                "dense_layers": (
                    self.cfg.n_dense_layers if self.cfg.is_hybrid
                    else 0 if self.cfg.is_moe else self.cfg.n_layers
                ),
            },
            # what the cache holds by kind of leaf, the features its kind
            # turned off (``cache_features``), and the per-lane state's moves
            "cache": self._cache_metrics(),
            "n_chips": self._n_chips,
            "param_hbm_bytes": self.param_hbm_bytes,
            "kv_arena_bytes": self.kv_arena_bytes,
            "hbm_bytes_per_chip_est": int(
                (self.param_hbm_bytes + self.kv_arena_bytes) / self._n_chips
            ),
        }

    def _cache_metrics(self) -> dict:
        if self._windowed and not self._hybrid:
            sizes = {
                "kv_bytes": self.cache.k.nbytes + self.cache.v.nbytes,
                "kv_ring_bytes": self.cache.wk.nbytes + self.cache.wv.nbytes,
            }
            return {
                "kinds": ["kv", "kv_ring"],
                **sizes,
                "bytes_per_lane": sum(sizes.values()) // self.max_batch,
                "state_snapshots": self.state_snapshots,
                "state_restores": self.state_restores,
                "off": dict(self._cache_off),
            }
        if not self._hybrid:
            return {
                "kinds": ["kv"],
                "kv_bytes": self.kv_arena_bytes,
                "bytes_per_lane": self.kv_arena_bytes // self.max_batch if not self.paged else None,
                "off": {},
            }
        sizes = {f"{name}_bytes": a.nbytes for name, a in self.cache.leaves().items()}
        return {
            "kinds": list(self.cache.leaves()),
            **sizes,
            "bytes_per_lane": sum(sizes.values()) // self.max_batch,
            "state_resets": self.state_resets,
            "state_snapshots": self.state_snapshots,
            "state_restores": self.state_restores,
            "off": dict(self._cache_off),
        }

    def _utilization_metrics(self, elapsed: float) -> dict:
        """MFU/MBU against the spec-sheet peaks of every chip spanned — only
        for a device kind in the peaks table (utils/hw.py)."""
        if self._chip is None:
            return {}
        peak_flops = self._chip.bf16_flops * self._n_chips
        peak_hbm_bps = self._chip.hbm_gbps * self._n_chips
        return {
            "mfu_lifetime": round(self.flops_done / elapsed / peak_flops, 5),
            "mbu_lifetime": round(self.hbm_bytes_read / elapsed / peak_hbm_bps, 5),
            "hbm_gbps_peak": round(peak_hbm_bps / 1e9, 1),
            "peak_tflops": round(peak_flops / 1e12, 1),
            "chip_kind": self._chip.kind,
        }

    @staticmethod
    def _device_doc(d) -> dict:
        doc = {"id": d.id, "coords": list(getattr(d, "coords", None) or []) or None}
        stats = d.memory_stats()  # None where the backend keeps no stats (cpu)
        if stats:
            doc.update(
                {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
            )
        return doc

    def _paged_metrics(self) -> dict:
        if not self.paged:
            return {"paged_kv": False}
        with self._page_lock:
            free = len(self._page_free)
            quarantined = len(self._page_quarantine)
            allocated = sum(len(s.pages) for s in self.paged_sessions.values())
            live_tokens = sum(s.position for s in self.paged_sessions.values())
            pinned = len(self._prefix_pinned_page_ids())
        # internal fragmentation: allocated page capacity the resident
        # sessions' live tokens don't fill (the cost of page granularity —
        # dense slots score (1 - position/max_seq) on the same formula)
        frag = (
            round(100.0 * (1.0 - live_tokens / (allocated * self.page_size)), 2)
            if allocated
            else 0.0
        )
        return {
            "paged_kv": True,
            "page_size": self.page_size,
            "kv_pages_total": self._data_pages,
            "kv_pages_free": free,
            "kv_pages_used": self._data_pages - free - quarantined,
            "kv_pages_prefix_pinned": pinned,
            "resident_sessions": len(self.paged_sessions),
            "kv_fragmentation_pct": frag,
            "page_exhausted_total": self.page_exhausted_total,
            "prefix_pages_shared_total": self.prefix_pages_shared,
        }

    def begin_drain(self) -> None:
        """Stop admitting (generate() raises EngineDraining); in-flight and
        already-queued work keeps running. First half of graceful SIGTERM."""
        self._draining = True

    def drain(self, budget_s: float = 10.0) -> bool:
        """Block until every queued/waiting/in-flight request settles, up to
        ``budget_s``; returns True on a clean drain. Called off the worker
        thread (serve-layer cleanup). Work still live when the budget runs
        out is failed by the caller's subsequent shutdown()."""
        self.begin_drain()

        def busy() -> bool:
            return bool(
                any(s.request is not None for s in self.slots)
                or self._waiting
                or not self._queue.empty()
                or self._readbacks
            )

        deadline = time.monotonic() + max(0.0, budget_s)
        while time.monotonic() < deadline:
            if not busy():
                return True
            time.sleep(0.05)
        # same predicate at the budget's edge: queued/waiting leftovers the
        # subsequent shutdown() will fail must not report drained_clean
        return not busy()

    def shutdown(self) -> None:
        self._running = False
        self._queue.put(None)
        self._worker.join(timeout=10)
        # one more drain after the join: items enqueued after the worker's
        # own exit drain (or left behind by a crashed worker) must fail,
        # not hang their callers forever (ADVICE r5)
        self._fail_pending(EngineShutdown("engine shut down"))
        for session in list(self._snap_parked):
            self._flush_parked_snapshot(session)

    # -- worker thread ----------------------------------------------------
    #
    # Pipelined decode (round-3 perf work): the device carry chains decode
    # chunks with no host round-trip between them; token readbacks are
    # initiated asynchronously at dispatch and PROCESSED one pipeline slot
    # later, so the device→host readback rides under the next chunk's
    # compute instead of serializing with it. Consequences the logic below
    # accounts for: EOS/finish detection lags by up to one chunk (the extra
    # lane-steps are parked garbage, overwritten before any query can attend
    # to them), and a finished lane keeps decoding until its park-injection
    # lands (clamped at the scratch position).
    _PIPELINE_DEPTH = 1  # readback RTT < chunk compute, so depth 1 hides it

    def _loop(self) -> None:
        with self._spans.loop():
            self._serve()
        # worker exit: nothing may hang on a dead worker — fail queued work,
        # drained-but-unadmitted work, and in-flight requests (ADVICE r5:
        # the None sentinel used to abandon SnapshotCmd/RestoreCmd/
        # GenRequest futures forever)
        self._fail_pending(EngineShutdown("engine shut down"))

    def _serve(self) -> None:
        """The worker's iterations. Everything that takes time in here runs
        under a top-level ``engine.*`` span, so the spans tile the loop:
        ``metrics()["loop_s"]`` less the phases' self times is glue."""
        while self._running and not self._sentinel:
            busy = any(s.request is not None for s in self.slots) or bool(self._readbacks)
            self._pump_queue(0.0 if (busy or self._waiting) else 0.2)
            if self._sentinel:
                break
            if self.paged:
                # freed pages parked behind in-flight dispatches become
                # allocatable once the readback FIFO has drained
                self._release_quarantine()
            self._admit_waiting()
            # cancelled/expired in-flight lanes are reaped BEFORE dispatching
            # more device work for them; their freed slots are admissible on
            # the next iteration's _admit_waiting pass
            self._reap_aborted()
            # ONE prefill chunk, then a decode chunk: a long prompt is fed
            # through chunk-by-chunk between decode chunks, so admitting it
            # never stalls active generations for more than one chunk's
            # latency. Where the decode chunk would be the one-step rung
            # (someone still waits on the worker after this chunk), the
            # chunk's launch CARRIES that step (``_riders``): one launch,
            # one stream of the weights, for both. When NOTHING is decoding,
            # prefill multi-ticks back to
            # back instead — a cold 1024-token prompt must not pay a full
            # worker iteration of decode-dispatch bookkeeping per 256-token
            # chunk. Prefill faults are PER-REQUEST: the culprit request
            # fails, everyone else keeps decoding (VERDICT r4 item 1b — a
            # single poisoned prompt used to fail every in-flight request).
            rode = False
            try:
                rode = self._prefill_tick(self._riders())
                while self.adaptive_decode and not any(
                    s.decoding for s in self.slots
                ) and any(
                    s.request is not None and s.pending_prompt for s in self.slots
                ):
                    # keep admitting between chunks: a newcomer's first
                    # chunk outranks an in-progress prompt's next chunk
                    # (admission-first ordering in _prefill_tick)
                    self._pump_queue(0.0)
                    if self._sentinel:
                        break
                    self._admit_waiting()
                    self._prefill_tick()
            except RidersFault as e:
                self._fail_batch(e.__cause__)
            except Exception as e:
                self._note_error(e)
                slot = self._prefilling_slot
                if slot is not None and slot.request is not None:
                    self._fail_item(slot.request, _as_prefill_failure(e))
                    self._reset_slot(slot)
                self._ensure_device_state()
            finally:
                self._prefilling_slot = None
            try:
                if rode:
                    pass  # the lanes' step went with the chunk
                elif any(s.decoding for s in self.slots):
                    # speculative verify round when lanes have drafts;
                    # otherwise (or under contention) the plain pipelined
                    # decode-chunk path — gamma collapse makes low-match
                    # traffic live here permanently. With in-loop spec the
                    # drafter/verifier run INSIDE the fused loop body, so
                    # the host-side round-trip is skipped entirely.
                    if self.inloop_spec or not self._try_speculate():
                        if self.fused_decode:
                            self._fused_dispatch()
                        else:
                            self._decode_dispatch()
                else:
                    self._last_decode_end = None  # idle gap isn't ITL
                # drain landed readbacks; block on the oldest when the
                # pipeline is full (that wait IS the backpressure bounding
                # how far dispatch runs ahead of the device) or when there
                # is nothing else worth dispatching (lanes whose whole token
                # budget is already in flight don't count — dispatching more
                # would burn a garbage chunk just to have something to do)
                self._drain_readbacks(
                    block=len(self._readbacks) > self._PIPELINE_DEPTH
                    or not self._has_dispatchable()
                )
            except Exception as e:
                self._fail_batch(e)
            if not any(s.request is not None for s in self.slots) and self._waiting:
                with self._spans.span("engine.wait_request"):
                    time.sleep(0.002)  # all slots busy-by-session; brief backoff

    def _fail_batch(self, e: Exception) -> None:
        """A decode/readback fault is batch-wide by construction (one
        compiled call covers every lane): fail the in-flight requests, then
        verify the donated device state survived — if not, reallocate so the
        engine serves on, sessions cold."""
        self._note_error(e)
        for slot in self.slots:
            if slot.request is not None:
                self._fail_item(slot.request, e)
                self._reset_slot(slot)
        self._readbacks.clear()
        self._ensure_device_state()

    def _pump_queue(self, block_s: float) -> None:
        """Drain the submit queue into the waiting list (a burst admits
        together). The shutdown sentinel sets ``_sentinel`` instead of
        returning mid-drain so every caller unwinds to the exit drain."""
        try:
            if block_s > 0:
                with self._spans.span("engine.wait_request"):  # nothing to do
                    item = self._queue.get(timeout=block_s)
            else:
                item = self._queue.get_nowait()
            while True:
                if item is None:
                    self._sentinel = True
                    return
                self._waiting.append(item)
                item = self._queue.get_nowait()
        except queue.Empty:
            pass

    def _admit_waiting(self) -> None:
        if self._waiting:
            self._admit_items()

    @_phase("engine.admit")
    def _admit_items(self) -> None:
        still = []
        for item in self._waiting:
            try:
                if isinstance(item, RestoreCmd):
                    self._do_restore(item)
                elif isinstance(item, SnapshotCmd):
                    self._do_snapshot(item)
                elif isinstance(item, ParkCmd):
                    self._do_park(item)
                elif isinstance(item, PrewarmCmd):
                    self._do_prewarm(item)
                elif self._pre_reject(item):
                    pass  # expired/cancelled before prefill — already failed
                elif self._tier_needs_promote(item) and not self._tier_promote(
                    item.session
                ):
                    # host-parked session whose device swap-in failed
                    # (injected kv_promote fault or pool pressure): typed
                    # backpressure — the entry stays parked, a retry finds
                    # the session still promotable
                    raise TierPromoteFailed(item.session)
                elif not self._try_admit(item):
                    still.append(item)
            except EngineOverloaded as e:
                # pool backpressure at admission (the prefix tail-CoW
                # alloc): a policy 429, not a worker fault — fail typed
                # without polluting the worker-error channel, matching the
                # prefill/decode exhaustion handlers
                self._fail_item(item, e)
            except Exception as e:
                # a poisoned request/snapshot must not kill the worker
                self._note_error(e)
                self._fail_item(item, e)
        self._waiting = still

    def _take_cancel(self, request_id: str) -> bool:
        with self._lock:
            return self._cancel_requested.pop(request_id, None) is not None

    def _purge_stale_cancels(self) -> None:
        """Drop cancel markers whose request never showed up (TTL): the
        client-disconnect path can record a cancel for a dispatch that died
        on the wire before the engine saw it."""
        if not self._cancel_requested:
            return
        cutoff = time.monotonic() - self._cancel_ttl_s
        with self._lock:
            for rid in [r for r, t in self._cancel_requested.items() if t < cutoff]:
                del self._cancel_requested[rid]

    def _pre_reject(self, req: GenRequest) -> bool:
        """Fail a not-yet-admitted request whose caller is gone: cancelled
        ids and past-deadline arrivals never reach prefill — the whole point
        of the admission-side check is that a deadline miss costs ZERO
        device work."""
        if self._take_cancel(req.id):
            self.cancelled_total += 1
            self._fail_item(req, RequestCancelled(f"request {req.id} cancelled"))
            return True
        if self.deadlines and req.deadline_at is not None and time.time() > req.deadline_at:
            self.expired_total += 1
            self._fail_item(
                req, RequestExpired(f"request {req.id} deadline exceeded before prefill")
            )
            return True
        return False

    def _reap_aborted(self) -> None:
        """Per-iteration sweep of in-flight lanes: a cancelled request (or
        one whose deadline passed mid-generation) is parked mid-decode and
        its slot freed for admission — decoding on for a caller that is gone
        is pure waste under overload. In-flight readback entries for the
        reaped request are skipped at processing (request-identity check),
        the same staleness discipline finished lanes already use."""
        self._purge_stale_cancels()
        if not self._cancel_requested and not (
            self.deadlines
            and any(
                s.request is not None and s.request.deadline_at is not None
                for s in self.slots
            )
        ):
            return
        now = time.time()
        for slot in self.slots:
            req = slot.request
            if req is None:
                continue
            if self._take_cancel(req.id):
                self.cancelled_total += 1
                err: Exception = RequestCancelled(f"request {req.id} cancelled mid-flight")
            elif (
                self.deadlines
                and req.deadline_at is not None
                and now > req.deadline_at
            ):
                self.expired_total += 1
                err = RequestExpired(f"request {req.id} deadline exceeded mid-flight")
            else:
                continue
            self._fail_item(req, err)
            self._abandon_slot(slot)

    @_phase("engine.inject_lane")
    def _inject_lane(
        self, idx: int, first, position: int, temp: float, top_k: int, top_p: float,
        hist_row=None, hist_n: int = 0,
    ) -> None:
        """Jitted single-lane scatter into the 7-array decode carry (token,
        position, temperature, top_k, top_p, spec history, history length).
        ``hist_row`` seeds the in-loop drafter with the prompt tail (host
        int32 [FUSED_HIST_W], left-shifted in the scatter so ``first``
        lands in the newest slot); None parks the history empty."""
        if hist_row is None:
            hist_row = jnp.zeros((FUSED_HIST_W,), jnp.int32)
        (
            self._dtok,
            self._dpos,
            self._dtemps,
            self._dtopk,
            self._dtopp,
            self._dhist,
            self._dhlen,
        ) = self._inject(
            self._dtok,
            self._dpos,
            self._dtemps,
            self._dtopk,
            self._dtopp,
            self._dhist,
            self._dhlen,
            jnp.int32(idx),
            first,
            jnp.int32(position),
            jnp.float32(temp),
            jnp.int32(top_k),
            jnp.float32(top_p),
            hist_row,
            jnp.int32(hist_n),
        )

    @_phase("engine.inject_lane")
    def _stage_lane(
        self, idx: int, first, position: int, temp: float, top_k: int, top_p: float,
        hist_row=None, hist_n: int = 0,
    ) -> None:
        """Write a freshly prefilled lane into the STAGING shadow carry
        instead of the live one: the already-dispatched fused loop absorbs
        it at entry via the ``armed`` flag (double-buffered injection) —
        continuous batching without exiting the running loop. Same jitted
        scatter as ``_inject_lane``, pointed at the shadow arrays."""
        if hist_row is None:
            hist_row = jnp.zeros((FUSED_HIST_W,), jnp.int32)
        (
            self._stok,
            self._spos,
            self._stemps,
            self._stopk,
            self._stopp,
            self._shist,
            self._shlen,
        ) = self._inject(
            self._stok,
            self._spos,
            self._stemps,
            self._stopk,
            self._stopp,
            self._shist,
            self._shlen,
            jnp.int32(idx),
            first,
            jnp.int32(position),
            jnp.float32(temp),
            jnp.int32(top_k),
            jnp.float32(top_p),
            hist_row,
            jnp.int32(hist_n),
        )

    def _park_lane(self, idx: int) -> None:
        """Point a lane at the scratch position with neutral sampling state
        (idle/finished/aborted lanes all park identically)."""
        self._inject_lane(idx, jnp.int32(0), self.scratch_pos, 0.0, 0, 1.0)
        if self._staged_lane == idx:
            # a staged-but-not-yet-absorbed lane that gets parked (abort
            # between staging and dispatch) must not arm into the next loop
            self._staged_lane = None

    def _abandon_slot(self, slot: Slot, rollback: bool = False) -> None:
        """Free a slot whose request was aborted mid-flight: park its decode
        lane (chunks already dispatched keep stepping it until the park
        injection lands, their tokens skipped at processing), then return
        the slot to cold idle — the KV prefix holds a partial generation the
        session's recorded history will never contain, so continuing from it
        would desync context."""
        if slot.decoding:
            slot.decoding = False
            slot.dev_position = self.scratch_pos
            self._park_lane(slot.idx)
        self._reset_slot(slot, rollback=rollback)

    def _has_dispatchable(self) -> bool:
        """Is there device work left to dispatch? Pending prompt chunks, or
        a decoding lane with token budget not yet in flight."""
        for s in self.slots:
            if s.request is None:
                continue
            if s.pending_prompt:
                return True
            if s.decoding and s.request.dispatched < s.request.max_tokens:
                return True
        return False

    def _fail_pending(self, error: Exception) -> None:
        """Fail everything still owed a result: waiting items, queued items,
        and in-flight slot requests. Called from the worker's exit path and
        again from shutdown() after the join (late enqueues)."""
        for item in self._waiting:
            self._fail_item(item, error)
        self._waiting = []
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                self._fail_item(item, error)
        for slot in self.slots:
            if slot.request is not None:
                self._fail_item(slot.request, error)
                slot.request = None
                slot.pending_prompt = []
                slot.decoding = False

    def _note_error(self, e: Exception) -> None:
        self.worker_errors += 1
        self.last_worker_error = f"{type(e).__name__}: {e}"
        print(f"[llm-engine] worker error: {self.last_worker_error}", flush=True)
        # what was in flight may never be read back, and a reallocated arena
        # starts the device's queue anew: the ledger's next launch stands alone
        self._launches.cut()

    def _reset_slot(self, slot: Slot, rollback: bool = False) -> None:
        """Return a slot to cold idle after its request failed: KV prefix is
        no longer trusted (the fault may have landed mid-write). With
        ``rollback`` (policy failures: pool exhaustion — the alloc fails
        BEFORE any dispatch) the paged session's pre-request KV is trusted
        and preserved instead."""
        if self.paged:
            if rollback:
                self._rollback_lane_session(slot)
            else:
                self._drop_lane_session(slot)
        if self._staged_lane == slot.idx:
            # staged-but-unabsorbed lane dying on a fault path must not arm
            # its stale shadow state into the next fused loop
            self._staged_lane = None
        slot.request = None
        slot.pending_prompt = []
        slot.decoding = False
        slot.position = 0
        slot.pending_token = None
        slot.prefix_ctx = None
        slot.spec_hist = []
        slot.spec_ema = 1.0
        slot.spec_miss = 0
        slot.epoch += 1
        if slot.session:
            # only drop the mapping if it still points HERE — clear_sessions
            # may have already remapped this session name to another slot
            if self.sessions.get(slot.session) == slot.idx:
                self.sessions.pop(slot.session, None)
                self._flush_parked_snapshot(slot.session)
            slot.session = ""

    def _ensure_device_state(self) -> None:
        """After a worker fault: the failed call may have CONSUMED its
        donated inputs (cache, decode carry) without producing outputs —
        every later dispatch would then raise 'array deleted' forever.
        Reallocate anything lost so the engine keeps serving (sessions
        restart cold; the store-side KV snapshots still allow resume)."""
        lost = False
        for arr in jax.tree.leaves(self.cache):
            try:
                if arr.is_deleted():
                    lost = True
            except Exception:
                lost = True
        if lost:
            self.cache = self._alloc_cache()
            self.cache_resets += 1
            for slot in self.slots:
                if slot.request is not None:
                    self._fail_item(slot.request, RuntimeError("KV arena reset"))
                self._reset_slot(slot)
            self.sessions.clear()
            if self.paged:
                # the pool's contents are gone: every session, prefix pin,
                # and quarantined id referenced the lost arrays
                with self._page_lock:
                    self.paged_sessions.clear()
                    self._prefix_entries.clear()
                    self._prefix_bytes = 0
                    self._page_free = list(range(self._data_pages - 1, -1, -1))
                    self._page_refs[:] = 0
                    self._page_quarantine = []
                    for i in range(self.max_batch):
                        self._bt[i, :] = self._scratch_page(i)
        carry_lost = False
        for arr in (
            self._dtok, self._dpos, self._dtemps, self._dtopk, self._dtopp,
            self._dhist, self._dhlen,
        ):
            try:
                if arr.is_deleted():
                    carry_lost = True
            except Exception:
                carry_lost = True
        if carry_lost:
            (
                self._dtok,
                self._dpos,
                self._dtemps,
                self._dtopk,
                self._dtopp,
                self._dhist,
                self._dhlen,
            ) = self._alloc_carry()
            # fresh carry parks every lane at scratch: decoding requests
            # lost their device position and cannot continue
            for slot in self.slots:
                if slot.decoding and slot.request is not None:
                    self._fail_item(slot.request, RuntimeError("decode carry reset"))
                    self._reset_slot(slot)
                slot.decoding = False
        stage_lost = False
        for arr in (
            self._stok, self._spos, self._stemps, self._stopk, self._stopp,
            self._shist, self._shlen,
        ):
            try:
                if arr.is_deleted():
                    stage_lost = True
            except Exception:
                stage_lost = True
        if stage_lost:
            (
                self._stok,
                self._spos,
                self._stemps,
                self._stopk,
                self._stopp,
                self._shist,
                self._shlen,
            ) = self._alloc_carry()
            self._staged_lane = None

    def _restored_bytes(self, cmd: RestoreCmd) -> dict:
        if not self._named_leaves or not cmd.leaves:
            return {}
        return {"bytes": ",".join(f"{n}={np.asarray(a).nbytes}" for n, a in cmd.leaves.items())}

    @_phase("engine.restore", attrs=_restored_bytes)
    def _do_restore(self, cmd: RestoreCmd) -> None:
        from .checkpoint import restore_kv_slot

        ok = False
        try:
            if self._named_leaves:
                ok = self._do_restore_state(cmd)
                return
            if cmd.k is None or cmd.v is None or set(cmd.leaves or ("k", "v")) != {"k", "v"}:
                # another family's snapshot (a latent, or k and v BESIDE a
                # state), refused by its leaves' names: the caller re-prefills
                return
            if self.paged:
                ok = self._do_restore_paged(cmd)
                return
            slot = self._find_slot(cmd.session)
            if slot is not None and cmd.position < self.max_seq - 1:
                self.cache = restore_kv_slot(self.cache, slot.idx, cmd.k, cmd.v)
                slot.position = cmd.position
                slot.pending_token = cmd.pending_token
                # a restored slot is LIVE now: without this, its last_used
                # is whatever its previous occupant left (often 0), so the
                # very next admission/restore picks it as the LRU victim
                # and silently evicts the session that was just restored
                # (the paged restore path already stamps last_used)
                slot.last_used = time.monotonic()
                ok = True
        finally:
            # resolve even on exception (shape-mismatched snapshots from a
            # redeployed model config must not hang the caller)
            cmd.loop.call_soon_threadsafe(_resolve_value, cmd.future, ok)

    def _do_restore_state(self, cmd: RestoreCmd) -> bool:
        """Restore into a lane whose cache is positional rows + per-lane
        state: every named leaf has to be there and fit (a snapshot of
        another family, or of other widths, is refused: the caller
        re-prefills). The positional rows are padded to their snapshot
        bucket, so the write is one of a handful of compiled programs, on
        the donated cache: nothing the size of a state stack is copied."""
        leaves = cmd.leaves or {}
        if not 0 < cmd.position < self.max_seq - 1:
            return False
        # at the snapshot's bucket: a leaf with a row every few positions (a
        # sparse layer's pooled keys) ships that many rows of it
        bucket = self._snap_bucket(cmd.position)
        want = jax.eval_shape(lambda c: self._lane_leaves(c, 0, bucket), self.cache)
        if set(leaves) != set(want):
            return False
        staged = {}
        for name, spec in want.items():
            a = np.asarray(leaves[name])
            if name in self._positional:
                if a.shape[0] != spec.shape[0] or a.shape[2:] != spec.shape[2:] or a.shape[1] > bucket:
                    return False
                a = np.pad(a, [(0, 0), (0, bucket - a.shape[1])] + [(0, 0)] * (a.ndim - 2))
            elif a.shape != spec.shape:
                return False
            staged[name] = jnp.asarray(a, spec.dtype)
        slot = self._find_slot(cmd.session)
        if slot is None:
            return False
        fn = self._restore_fns.get(bucket)
        if fn is None:
            fn = self._restore_fns[bucket] = jax.jit(self._restore_lane, donate_argnums=(0,))
        self.cache = fn(self.cache, jnp.int32(slot.idx), staged)
        slot.position = cmd.position
        slot.pending_token = cmd.pending_token
        slot.last_used = time.monotonic()
        self.state_restores += 1
        return True

    def _do_restore_paged(self, cmd: RestoreCmd) -> bool:
        """Restore into PAGES, not a lane: the session enters residency
        without occupying a compute lane at all (a restored session that
        never speaks again costs only its pages). Exhaustion surfaces as
        False — the caller re-prefills instead."""
        if not cmd.session or cmd.position >= self.max_seq - 1 or cmd.position <= 0:
            return False
        # under _page_lock against API-thread clear_sessions: the
        # existing-session teardown and the new binding must be atomic
        with self._page_lock:
            existing = self.paged_sessions.get(cmd.session)
            if existing is not None:
                if existing.lane is not None:
                    return False  # mid-generation: never clobber live KV
                self._free_session_pages(existing)
                self.paged_sessions.pop(cmd.session, None)
                self.sessions.pop(cmd.session, None)
        count = (cmd.position - 1) // self.page_size + 1
        try:
            ids = self._alloc_pages(count, serving=False)
        except EngineOverloaded:
            return False
        k = np.asarray(cmd.k)
        v = np.asarray(cmd.v)
        pad = count * self.page_size - k.shape[1]
        if pad:
            widths = [(0, 0), (0, pad)] + [(0, 0)] * (k.ndim - 2)
            k = np.pad(k, widths)
            v = np.pad(v, widths)
        dtype = self.cache.k.dtype
        self.cache = self._restore_fn_paged(count)(
            self.cache,
            jnp.asarray(np.asarray(ids, dtype=np.int32)),
            jnp.asarray(k, dtype),
            jnp.asarray(v, dtype),
        )
        sess = PagedSession(
            name=cmd.session,
            pages=ids,
            position=cmd.position,
            pending_token=cmd.pending_token,
            last_used=time.monotonic(),
        )
        with self._page_lock:
            self.paged_sessions[cmd.session] = sess
            self.sessions[cmd.session] = -1
        return True

    def _fail_item(self, item, error: Exception) -> None:
        fut = getattr(item, "future", None)
        loop = getattr(item, "loop", None)
        if fut is not None and loop is not None:
            try:
                loop.call_soon_threadsafe(_reject, fut, error)
            except RuntimeError:
                pass  # caller's loop already closed; nobody left to notify

    def _admit_prologue(
        self, position: int, pending_token: int | None, req: GenRequest
    ) -> tuple[list[int], int | None, bool]:
        """Shared admission prologue — ONE implementation for both arenas,
        because greedy A/B parity between them hinges on these semantics
        matching exactly. Splices the held-out pending token into the
        prompt, decides whether the continuation fits the budget (reset
        otherwise — and the pending token belongs to the context being
        DISCARDED: keeping it would prefill one stale token that an engine
        without a held-out pending never sees, breaking parity at exactly
        the reset boundary), and trims an over-long prompt to its tail.
        Returns (prompt, original_pending, reset)."""
        prompt = list(req.prompt_ids)
        pend = pending_token
        if pend is not None:
            prompt = [pend] + prompt
        budget = self.max_seq - 1 - req.max_tokens
        reset = position + len(prompt) > budget
        if reset and pend is not None:
            prompt = prompt[1:]
        if len(prompt) > budget:
            prompt = prompt[-budget:]  # keep the tail
        return prompt, pend, reset

    def _try_admit(self, req: GenRequest) -> bool:
        if self.paged:
            return self._try_admit_paged(req)
        slot = self._find_slot(req.session)
        if slot is None:
            return False
        prompt, _, reset = self._admit_prologue(slot.position, slot.pending_token, req)
        slot.pending_token = None
        if reset:
            # continuation didn't fit: reset the session's KV
            slot.position = 0
            slot.epoch += 1
        # Fresh context (position 0): fork the longest cached prefix into
        # this slot instead of re-prefilling it — a second session with a
        # shared system prompt skips ~all of its prefill. Continuing
        # sessions already hold their context in KV; nothing to fork.
        forked = 0
        fresh = slot.position == 0
        # drafting corpus mirrors the slot's fed token stream exactly: a
        # fresh context replaces it, a continuing turn appends (the pending
        # token rides in via the prompt, having been held out at finish)
        if fresh:
            slot.spec_hist = list(prompt)
        else:
            slot.spec_hist.extend(prompt)
            del slot.spec_hist[: -self.max_seq]
        if self._prefix_active and fresh:
            if self._prefix_levels and len(prompt) > self._prefix_levels[0]:
                with self._spans.span("engine.prefix_fork", request_id=req.id):
                    forked = self._prefix_fork(slot, prompt)
            # track the fresh context so the final prefill chunk registers
            # its bucket-prefixes (including levels above a partial hit)
            slot.prefix_ctx = list(prompt)
        else:
            slot.prefix_ctx = None
        if self._hybrid:
            # open the lane's per-lane state for this request, zeroed for a
            # fresh context and carried on for a continuing one. Decode steps
            # it while it feeds tokens the reply keeps: positions up to the
            # prompt's end + max_tokens - 1 (the last token generated is never
            # fed), closing early on an EOS the request heeds
            with self._spans.span("engine.state_reset", request_id=req.id, fresh=fresh):
                self.cache = self._admit_state(
                    self.cache,
                    jnp.int32(slot.idx),
                    jnp.bool_(fresh),
                    jnp.int32(slot.position + len(prompt) + req.max_tokens - 1),
                    jnp.int32(-1 if req.ignore_eos else self.tokenizer.eos_id),
                )
            self.state_resets += int(fresh and self._recurrent)
        # admit: the slot is busy from here; the worker's prefill tick feeds
        # the prompt through chunk-by-chunk, interleaved with decode steps
        slot.request = req
        slot.pending_prompt = prompt[forked:]
        slot.last_used = time.monotonic()
        return True

    def _prefix_fork(self, slot: Slot, prompt: list[int]) -> int:
        """Copy the longest cached prefix of ``prompt`` into the slot's rows;
        the number of tokens the slot now holds (0: a miss)."""
        hit = self._prefix_lookup(prompt)
        if hit is None:
            self.prefix_misses += 1
            return 0
        key, entry = hit
        b = key[0]
        try:
            self.cache = self._prefix_fork_fn(b)(
                self.cache, jnp.int32(slot.idx), entry.k, entry.v
            )
        except Exception:
            # the fork may have consumed its donated cache without
            # producing one — repair device state, then let
            # _admit_waiting fail this request
            self._ensure_device_state()
            raise
        slot.position = b
        entry.hits += 1
        entry.last_used = time.monotonic()
        self._prefix_entries.move_to_end(key)
        self.prefix_hits += 1
        self.prefix_tokens_saved += b
        # the fork streams the entry's KV once (copy, no FLOPs — that's the
        # point); keeps the MBU model honest
        self.hbm_bytes_read += b * self._kv_bytes_per_pos
        return b

    def _try_admit_paged(self, req: GenRequest) -> bool:
        """Paged admission: bind the session (resident or new) to ANY free
        compute lane — lanes carry no KV affinity, the pages do — and map
        the longest cached prefix as refcounted pages instead of forking a
        copy. Mirrors the dense _try_admit flow step for step so greedy
        scheduling (and therefore token streams) stay identical. Runs
        under _page_lock: an API-thread clear_sessions checks ``lane is
        None`` and frees pages, so it must never interleave with a bind —
        a session cleared between the lookup and ``sess.lane = idx`` would
        have its just-mapped pages returned to the pool and handed to
        another session while this lane writes through them."""
        # Pre-drain the quarantine OUTSIDE the lock when the pool looks
        # short for this request: _alloc_pages' quarantine reap waits on
        # in-flight device work (jax.block_until_ready), and paying that
        # wait while holding _page_lock would stall every API-thread lock
        # consumer (stats/clear_sessions) for the duration. Out here only
        # the worker waits; inside, the reap then finds the quarantine
        # already empty. (Worst-case page need for this admission; a race
        # refilling the quarantine in between just falls back to the
        # locked wait, which is correct, merely slower.)
        need = (len(req.prompt_ids) + req.max_tokens) // self.page_size + 2
        self._reap_quarantine_if_short(min(need, self._n_blocks))
        with self._page_lock:
            return self._try_admit_paged_locked(req)

    def _try_admit_paged_locked(self, req: GenRequest) -> bool:
        name = req.session
        sess = self.paged_sessions.get(name) if name else None
        if sess is not None and sess.lane is not None:
            return False  # session busy: one request per session at a time
        lane = next((s for s in self.slots if s.request is None), None)
        if lane is None:
            return False
        fresh_session = sess is None
        if fresh_session:
            sess = PagedSession(name=name)
        prompt, pend, reset = self._admit_prologue(sess.position, sess.pending_token, req)
        sess.pending_token = None
        if reset:
            # continuation didn't fit: reset the session's KV (pages too)
            self._free_session_pages(sess)
            sess.position = 0
        # pre-request state for pool-exhaustion rollback: the pending token
        # was just consumed into the prompt and must return with a rollback,
        # the position is about to advance (prefix map below; spec accepts
        # mid-request), and spec_hist is about to be extended in place.
        # After a context reset there is no pre-request state worth keeping
        # (admit_position 0 → drop).
        sess.admit_pending = pend if sess.position > 0 else None
        sess.admit_position = sess.position
        sess.admit_spec_hist = list(sess.spec_hist) if sess.position > 0 else []
        forked = 0
        fresh = sess.position == 0
        if fresh:
            sess.spec_hist = list(prompt)
        else:
            sess.spec_hist.extend(prompt)
            del sess.spec_hist[: -self.max_seq]
        try:
            if self._prefix_active and fresh:
                if self._prefix_levels and len(prompt) > self._prefix_levels[0]:
                    with self._spans.span("engine.prefix_fork", request_id=req.id):
                        hit = self._prefix_lookup(prompt)
                        if hit is not None and hit[1].pages is not None:
                            key, entry = hit
                            forked = self._map_prefix_pages(sess, key, entry)
                        else:
                            self.prefix_misses += 1
                lane.prefix_ctx = list(prompt)
            else:
                lane.prefix_ctx = None
        except Exception:
            # partial mappings must not leak a half-built session into
            # residency: free what was mapped, then surface the error
            # (_admit_waiting fails the request — 429 for pool exhaustion)
            self._free_session_pages(sess)
            if not fresh_session and name:
                self.paged_sessions.pop(name, None)
                self.sessions.pop(name, None)
            raise
        # bind: the lane mirrors the session while the request is in flight
        if fresh_session and name:
            self.paged_sessions[name] = sess
        sess.lane = lane.idx
        sess.last_used = time.monotonic()
        if name:
            self.sessions[name] = lane.idx
        lane.psess = sess
        lane.session = name
        lane.position = sess.position
        lane.pending_token = None
        lane.spec_hist = sess.spec_hist
        lane.spec_ema = sess.spec_ema
        lane.spec_miss = sess.spec_miss
        lane.epoch += 1
        lane.request = req
        lane.pending_prompt = prompt[forked:]
        lane.last_used = time.monotonic()
        self._bind_lane_bt(lane, sess)
        return True

    def _map_prefix_pages(self, sess: PagedSession, key: tuple, entry) -> int:
        """Zero-copy prefix fork: the session's block table maps the
        entry's full pages read-only (one refcount bump per page, no
        device traffic); only a partial tail page is copied — and only
        when the level isn't page-aligned. Returns the forked token count."""
        b = key[0]
        # take this session's page references FIRST: the tail-copy
        # allocation below may reclaim, and reclaim may evict THIS entry
        # (it is not re-LRU'd until the hit is recorded) — with the refs
        # already held, an eviction only drops the arena's pin while the
        # pages (and the tail-copy source) stay live for the mapping
        pages = list(entry.pages)
        tail_src = entry.tail_page
        with self._page_lock:
            for pid in pages:
                self._page_refs[pid] += 1
            if tail_src is not None:
                self._page_refs[tail_src] += 1
        tail_copy = None
        try:
            if tail_src is not None:
                # copy-on-write at the partial last page: this session will
                # write positions [b, page boundary) into that same page
                tail_copy = self._alloc_pages(1, serving=True)[0]
                self.cache = self._page_copy_fn()(
                    self.cache, jnp.int32(tail_src), jnp.int32(tail_copy)
                )
        except BaseException:
            with self._page_lock:
                for pid in pages:
                    self._decref_page(pid)
                if tail_copy is not None:
                    self._decref_page(tail_copy)
            raise
        finally:
            if tail_src is not None:
                self._decref_page(tail_src)
        sess.pages = pages
        sess.shared = len(pages)
        if tail_copy is not None:
            sess.pages.append(tail_copy)
        sess.position = b
        entry.hits += 1
        entry.last_used = time.monotonic()
        if key in self._prefix_entries:  # the alloc may have evicted it
            self._prefix_entries.move_to_end(key)
        self.prefix_hits += 1
        self.prefix_tokens_saved += b
        self.prefix_pages_shared += len(pages)
        # HBM traffic: ONLY the tail copy streams bytes — the whole point
        # of page mapping vs the dense fork's full-prefix copy
        if tail_copy is not None:
            self.hbm_bytes_read += self.page_size * self._kv_bytes_per_pos
        return b

    def _find_slot(self, session: str) -> Slot | None:
        if session and session in self.sessions:
            slot = self.slots[self.sessions[session]]
            if slot.request is None:
                return slot
            return None  # session busy: one request per session at a time
        # fresh slot: prefer never-used, else LRU idle session
        idle = [s for s in self.slots if s.request is None]
        if not idle:
            return None
        fresh = [s for s in idle if not s.session]
        slot = fresh[0] if fresh else min(idle, key=lambda s: s.last_used)
        if slot.session and self.sessions.get(slot.session) == slot.idx:
            with self._spans.span("engine.evict"):
                self.sessions.pop(slot.session, None)  # evict LRU session's KV
                self._count_eviction("session", time.monotonic() - slot.last_used)
                self._flush_parked_snapshot(slot.session)
        slot.session = session
        slot.position = 0
        slot.pending_token = None  # stale state from the previous occupant
        slot.pending_prompt = []
        slot.spec_hist = []
        slot.spec_ema = 1.0  # new occupant: optimistic until measured
        slot.spec_miss = 0
        slot.epoch += 1
        if session:
            self.sessions[session] = slot.idx
        return slot

    def _launch(self, program: str, key: int, **facts):
        """Count a launch of a step program when its jitted call has
        returned, i.e. the host has handed it to the device: the one place a
        launch is counted (utils/launches.py). ``facts`` are the ledger's
        (``steps`` passes through the layers, ``rows`` real rows a pass,
        ``lanes`` stepping); the dispatch span open around the call carries
        them on its trace event. Returns the record whoever reads the output
        back hands to ``self._launches.ready``."""
        opened = self._launches.dispatched(program, key, **facts)
        self._spans.note(**opened.attrs())
        return opened

    def _count_moe_rows(self, rows: int, passes: int = 1) -> None:
        """A launch of ``passes`` passes through the layers, ``rows = B·T``
        rows each, counted from static shapes only: an MoE model's
        ``moe`` block counters — what the algorithm asked for and what the
        path that served it executed (the ``routed`` option's buffers are not
        counted)."""
        if not self.cfg.is_moe:
            return
        e, k = self.cfg.n_experts, self.cfg.experts_per_token
        self.moe["assignments"] += passes * rows * k
        if self._moe_sorted_from is not None and rows >= self._moe_sorted_from:
            # a chip's share: the stack's experts, the tile of the whole router
            self.moe["rows_routed"] += passes * sorted_rows(
                rows, self.cfg.n_held, k, row_tile(rows, e, k)
            )
            if self._moe_in_kernel:
                self.moe["rows_gathered_in_kernel"] += passes * rows
        elif not self.routed_moe:
            self.moe["rows_all_experts"] += passes * rows * self.cfg.n_held

    def _kv_read_bytes(self, position: float) -> float:
        """Bytes of cache a row at ``position`` reads (the host's MBU model):
        every layer's rows up to it, a window layer's for its window only."""
        past = max(0.0, position - self.cfg.window) if self._windowed else 0.0
        if self._sparse_sizes is not None and position > self._sparse_sizes.dense_len:
            # the chosen blocks' rows, and a pooled key (half a K and V pair) every ``stride`` rows
            sp = self._sparse_sizes
            position = min(position, sp.topk * sp.block) + position / sp.stride / 2
        return position * self._kv_bytes_per_pos - past * self._kv_bytes_per_pos_window

    def _count_decode_blocks(self, positions: list[int], steps: int) -> None:
        """A decode launch of ``steps`` steps whose stepping lanes start at
        ``positions``: the K/V blocks ``flash_decode`` fetches (a lane at
        position p, below the arena's last row, reads ``p // bk + 1``; every
        other lane is parked and reads one) against the blocks the arena
        rows hold, a head block and a layer counted once. From what the
        worker knows at dispatch, never from the device."""
        bk = self._decode_bk
        if not (bk or self._latent_bk or self.cfg.rope_original_max or self.linear is not None):
            return
        pos = np.asarray(positions, np.int64).reshape(-1, 1) + np.arange(steps)
        self._count_positioned(pos)
        pos = np.where(pos >= self.max_seq - 1, 0, pos)
        if self._sparse_sizes is not None:
            # a lane past ``dense_len`` hands the dense kernel one row and gathers its blocks
            pos = np.where(pos >= self._sparse_sizes.dense_len, 0, pos)
        parked = steps * (self.max_batch - len(positions))
        if self._latent_bk:
            lb = self._latent_bk
            self.attention["latent_decode_blocks_live"] += int((pos // lb + 1).sum()) + parked
            self.attention["latent_decode_blocks_stored"] += steps * self.max_batch * -(-self.max_seq // lb)
        if not bk:
            return
        live = int((pos // bk + 1).sum()) + parked
        stored = steps * self.max_batch * -(-self.max_seq // bk)
        self.attention["decode_blocks_live"] += live
        self.attention["decode_blocks_stored"] += stored
        if self._windowed:
            a, wb = self.attention, self._ring_bk
            first = np.maximum(pos - (self.cfg.window - 1), 0)
            ring = np.minimum(pos // wb - first // wb + 1, -(-self._ring_rows // wb))
            a["global_decode_blocks_live"] += live
            a["global_decode_blocks_stored"] += stored
            a["window_decode_blocks_live"] += int(ring.sum()) + parked
            a["window_decode_blocks_unbounded"] += int((pos // wb + 1).sum()) + parked
            a["window_decode_blocks_stored"] += steps * self.max_batch * -(-self._ring_rows // wb)
            # the rows the stepping lanes' queries see, as the equations name
            # them (a parked lane's query sees nothing anyone needs)
            a["global_decode_rows"] += int((pos + 1).sum())
            a["window_decode_rows"] += int(np.minimum(pos + 1, self.cfg.window).sum())

    def _count_positioned(self, positions: np.ndarray, chunked: bool = False) -> None:
        """Rows a launch gives these positions (``chunked``: a chunk's real
        tokens; else the stepping lanes' steps): those at or past
        ``rope_original_max`` where the model has such a boundary, what a
        sparse layer's selection reads for them, and the rows that step a
        linear mixer's state."""
        if self.cfg.rope_original_max:
            self.attention["rows_positioned"] += int(positions.size)
            self.attention["rows_past_original_max"] += int((positions >= self.cfg.rope_original_max).sum())
        if self._sparse_sizes is not None:
            sparse = self.attention["sparse"]
            for k, v in self._block_counts(positions, self._sparse_sizes).items():
                sparse[k] += v
        if self.linear is not None:
            self.linear["rows_chunked" if chunked else "steps"] += int(np.size(positions))

    def _bucket(self, n: int) -> int:
        for b in PREFILL_BUCKETS:
            if n <= b:
                return b
        return PREFILL_BUCKETS[-1]

    def _riders(self) -> list | None:
        """The decode lanes this tick's prefill chunk carries with it (one
        launch of ``jit_prefill_with_decode`` in place of ``jit_prefill``
        and the one-step ``jit_decode_n``), as ``_decode_dispatch`` snapshots
        them; ``None`` where the tick keeps its two launches: an engine
        without the program, nothing pending, nothing decoding or no budget
        left, or a tick whose decode would not be the plain one-step rung —
        nobody waits on the worker once this chunk is in (the only prompt's
        last chunk, no ``_admissible_waiter``), so the lanes keep their
        verify round or their longer rung."""
        if self._prefill_with_decode is None:
            return None
        pending = [s for s in self.slots if s.request is not None and s.pending_prompt]
        if not pending:
            return None
        snapshot = [
            (s, s.request, s.dev_position)
            for s in self.slots
            if s.decoding and s.request is not None
        ]
        if not snapshot or max(r.max_tokens - r.dispatched for _, r, _ in snapshot) <= 0:
            return None
        last_chunk = len(pending) == 1 and len(pending[0].pending_prompt) <= self.prefill_chunk
        if last_chunk and not self._admissible_waiter():
            return None
        return snapshot

    def _prefill_tick(self, riders: list | None = None) -> bool:
        """Feed ONE chunk of one pending prompt through the model (FIFO by
        submission time). Non-final chunks only populate the slot's KV; the
        final chunk samples the first token. Interleaving these ticks with
        decode steps bounds how long one long prompt can stall every active
        generation: one chunk's latency, not the whole prompt's. With
        ``riders`` (``_riders``) the chunk's launch carries the decode
        lanes' step; True when it did."""
        slots = [s for s in self.slots if s.request is not None and s.pending_prompt]
        if not slots:
            return False
        # admission-first: a prompt that has not started prefilling yet beats
        # an in-progress prompt's next chunk, so one long prompt cannot
        # monopolize the tick and push new arrivals' admission latency to
        # its full prefill time; ties (and steady state) stay FIFO
        slot = min(
            slots,
            key=lambda s: (s.request.prefill_started_at is not None, s.request.submitted_at),
        )
        self._prefilling_slot = slot  # fault attribution (worker loop)
        with self._spans.span(
            "engine.prefill_tick",
            request_id=slot.request.id,
            tokens=min(len(slot.pending_prompt), self.prefill_chunk),
        ):
            return self._prefill_chunk(slot, riders)

    def _prefill_chunk(self, slot: Slot, riders: list | None = None) -> bool:
        span = self._spans.span
        req = slot.request
        # failpoint: a poisoned prefill fails THIS request only — the worker
        # loop's per-request isolation (VERDICT r4 item 1b) is what the
        # chaos soak exercises through this seam. Warmup's synthetic
        # requests (empty id) are exempt: fault injection targets serving
        # traffic, and an env-armed failpoint must not brick engine boot.
        if req.id:
            faults.fire("engine.prefill")
        if riders and any(r.id for _, r, _ in riders):
            # the decode seam of a launch that carries the lanes' step: after
            # the prompt's own, so a poisoned prompt still fails alone
            try:
                faults.fire("engine.decode_step")
            except Exception as e:
                raise RidersFault() from e
        if req.prefill_started_at is None:
            req.prefill_started_at = time.monotonic()
            self.admission_ms_recent.append(
                1000 * (req.prefill_started_at - req.submitted_at)
            )
            # promote-overlap accounting: the interval from the tier
            # promotion's start to this first prefill dispatch is restore
            # latency HIDDEN behind the queue-wait phase of TTFT
            t0 = (
                self._tier_promote_started.pop(req.session, None)
                if req.session
                else None
            )
            if t0 is not None:
                hidden = 1000 * (req.prefill_started_at - t0)
                self.tier_promote_overlap_ms_recent.append(hidden)
        chunk = slot.pending_prompt[: self.prefill_chunk]
        slot.pending_prompt = slot.pending_prompt[self.prefill_chunk :]
        final = not slot.pending_prompt
        n = len(chunk)
        with span("engine.prefill_dispatch"):  # host prep + the call returning
            bucket = next(b for b in self._mixed_buckets if b >= n) if riders else self._bucket(n)
            padded = chunk + [0] * (bucket - n)
            # padding positions continue past the real tokens; every such
            # slot is rewritten by a later real token (next chunk or decode)
            # before any query can attend to it, and the position mask hides
            # the rest
            positions = np.arange(slot.position, slot.position + bucket, dtype=np.int32)
            tokens = jnp.asarray(np.array(padded, dtype=np.int32)[None])
            pos = jnp.asarray(positions[None])
            if self.paged:
                # pages cover the REAL tokens only; bucket-padding writes
                # past them fall into the lane's scratch page via the table
                # default (and clamp in-kernel past the logical arena) —
                # exactly as invisible as the dense path's dropped
                # out-of-range scatter
                try:
                    self._ensure_lane_pages(
                        slot, slot.position + n - 1, serving=bool(req.id)
                    )
                except EngineOverloaded as e:
                    # policy backpressure, not a fault: fail THIS request
                    # with the typed 429 and roll the session back — the
                    # worker loop's generic prefill handler would count a
                    # worker error and destroy the resident session
                    self._fail_item(req, e)
                    self._abandon_slot(slot, rollback=True)
                    return False
                last_logits, self.cache = self._prefill(
                    self.params,
                    self.cache,
                    jnp.asarray(self._bt[slot.idx : slot.idx + 1]),
                    tokens,
                    pos,
                    jnp.int32(n),
                )
                # nobody reads a plain chunk back (a last chunk's logits feed
                # ``jit_first_token`` on the device, and THAT launch is read)
                self._launch(JIT_PREFILL, bucket, rows=n)
            elif riders:
                with span("engine.mixed_dispatch"):
                    last_logits, toks = self._launch_with_decode(slot.idx, tokens, pos, n)
                    opened = self._launch(
                        JIT_PREFILL_WITH_DECODE, bucket, rows=n + len(riders), lanes=len(riders)
                    )
            else:
                last_logits, self.cache = self._prefill(
                    self.params, self.cache, jnp.int32(slot.idx), tokens, pos, jnp.int32(n)
                )
                self._launch(JIT_PREFILL, bucket, rows=n)
        self._count_positioned(positions[:n], chunked=True)
        self._count_moe_rows(bucket + self.max_batch if riders else bucket)
        # n real tokens, each attending ~its own position of context
        self.flops_done += n * self.cfg.flops_per_token(slot.position + n // 2)
        self.hbm_bytes_read += self.param_hbm_bytes + (
            self._kv_read_bytes(slot.position + n // 2)
        )
        slot.position += n
        slot.last_used = time.monotonic()
        if riders:
            self._count_decode_step(riders, toks, opened)
        if not final:
            return bool(riders)
        # whole fresh context now in KV: register its bucket-prefixes in
        # the arena (async device copies; positions [0:b] are real tokens —
        # the final chunk's padding lands strictly above slot.position)
        with span("engine.prefix_register"):
            self._prefix_register(slot)
        with span("engine.first_token_sample"):
            # one signature whatever the request asks for: typed host
            # scalars, never Python ones (weak types)
            self._rng, first, first_tok = self._first_token(
                last_logits,
                self._rng,
                np.float32(req.temperature),
                np.int32(req.top_k),
                np.float32(req.top_p),
            )
            sampled = self._launch(JIT_FIRST_TOKEN, 1, steps=0, rows=1)
        hist_row = None
        hist_n = 0
        if self.inloop_spec:
            # seed the in-loop drafter with the prompt tail, right-aligned;
            # the inject scatter shifts it left one slot so the sampled
            # first token occupies the newest position
            ctx = req.prompt_ids[-(FUSED_HIST_W - 1):]
            row = np.zeros((FUSED_HIST_W,), np.int32)
            if ctx:
                row[FUSED_HIST_W - len(ctx):] = ctx
            hist_row = jnp.asarray(row)
            hist_n = min(len(ctx) + 1, FUSED_HIST_W)
        # point the slot's decode lane at this prompt's continuation WITHOUT
        # waiting for the sampled token to reach the host — decode chunks
        # chain from it on device; the value lands via the readback queue.
        # If a fused loop is already in flight and the staging slot is free,
        # write the SHADOW carry instead: the pipelined next loop absorbs
        # the lane at its entry (double-buffered injection) rather than
        # waiting out an exit-and-redispatch.
        use_stage = (
            self.fused_decode
            and self._fused_inject
            and self._staged_lane is None
            # host-side speculation reads the LIVE carry for verify rounds;
            # a staged lane is invisible there until absorbed, so staging is
            # only safe when spec runs in-loop (or not at all)
            and (self.inloop_spec or not self._spec_active)
            and any(e[0] == "fused" for e in self._readbacks)
        )
        if use_stage:
            self._stage_lane(
                slot.idx,
                first_tok,
                slot.position,
                req.temperature,
                req.top_k,
                req.top_p,
                hist_row,
                hist_n,
            )
            self._staged_lane = slot.idx
        else:
            if (
                self.fused_decode
                and self._fused_inject
                and any(e[0] == "fused" for e in self._readbacks)
            ):
                # staging slot occupied with a loop in flight: fall back to
                # the direct-injection path (exit-and-redispatch semantics)
                self.fused_inject_fallbacks_total += 1
            self._inject_lane(
                slot.idx,
                first_tok,
                slot.position,
                req.temperature,
                req.top_k,
                req.top_p,
                hist_row,
                hist_n,
            )
        slot.dev_position = slot.position
        slot.decoding = True
        req.prefill_done_at = time.monotonic()
        req.dispatched = 1  # the prefill-sampled first token
        self.prefills += 1
        try:
            first.copy_to_host_async()
        except Exception:
            pass
        self._readbacks.append(("first", slot, req, first, sampled))
        return bool(riders)

    def _launch_with_decode(self, idx: int, tokens, pos, n: int):
        """One launch of ``jit_prefill_with_decode``: the chunk ``tokens
        [1, bucket]`` (``n`` real) at arena row ``idx`` and a step of the
        decode carry. Returns the chunk's last logits and ``toks [1, B]``."""
        self._rng, key = jax.random.split(self._rng)
        last_logits, toks, self._dtok, self._dpos, self.cache = self._prefill_with_decode(
            self.params, self.cache, jnp.int32(idx), tokens, pos, jnp.int32(n),
            self._dtok, self._dpos, self._dtemps, self._dtopk, self._dtopp,
            jax.random.split(key, 1),
        )
        return last_logits, toks

    def _finish(self, slot: Slot, pending_last: bool) -> None:
        """``pending_last``: the final generated token was sampled but not yet
        fed through the model (it is absent from the slot's KV); carry it
        into the session's next prompt. When a chunked decode already fed it
        (mid-chunk finish), the caller passes False."""
        req = slot.request
        slot.request = None
        slot.last_used = time.monotonic()
        slot.pending_token = (req.generated[-1] if req.generated else None) if pending_last else None
        if self._windowed and slot.position > self._ring_rows:
            self.attention["window_wraps"] += 1  # this context lapped the ring
        # fold the reply into the drafting corpus; a held-out pending token
        # re-arrives via the next turn's prompt, so it is excluded here
        slot.spec_hist.extend(
            req.generated[:-1] if slot.pending_token is not None else req.generated
        )
        del slot.spec_hist[: -self.max_seq]
        if slot.decoding:
            # park the lane: in-flight chunks keep decoding it (their tokens
            # are skipped at processing — request identity mismatch) until
            # this injection lands in dispatch order
            slot.decoding = False
            slot.dev_position = self.scratch_pos
            self._park_lane(slot.idx)
        breakdown = None
        if req.ttft_ms and req.prefill_started_at and req.prefill_done_at:
            breakdown = {
                "queue_ms": round(1000 * (req.prefill_started_at - req.submitted_at), 2),
                "prefill_ms": round(
                    1000 * (req.prefill_done_at - req.prefill_started_at), 2
                ),
                "first_readback_ms": round(
                    req.ttft_ms - 1000 * (req.prefill_done_at - req.submitted_at), 2
                ),
            }
        result = {
            "text": self.tokenizer.decode(req.generated),
            "tokens": req.generated,
            "prompt_tokens": len(req.prompt_ids),
            "completion_tokens": len(req.generated),
            "ttft_ms": round(req.ttft_ms, 2) if req.ttft_ms else None,
            # per-request TTFT phase decomposition: queue-wait / prefill /
            # first-readback (sums to ttft_ms up to rounding)
            "ttft_breakdown": breakdown,
        }
        # Paged: settle the SESSION before resolving the caller — sync the
        # lane's final state back, stage any parked snapshot (the staging
        # reads the synced session), then release the compute lane (the
        # session stays resident in pages, holding zero lanes between
        # turns; overshoot page tails go back to the pool). Resolving last
        # means "await chat() returned" implies the session is settled —
        # callers and tests can inspect residency without racing the worker.
        if self.paged:
            if slot.psess is not None:
                slot.psess.position = slot.position
                slot.psess.pending_token = slot.pending_token
            self._service_parked_snapshot(slot)
            self._detach_lane(slot)
        self.requests_finished += 1  # before the caller can see its reply and scrape
        req.loop.call_soon_threadsafe(_resolve, req.future, result)
        # a cancel that raced a natural finish loses: drop its stale marker
        with self._lock:
            self._cancel_requested.pop(req.id, None)
        if not self.paged:
            # settle point: the slot is idle RIGHT NOW — stage any snapshot
            # that parked while this request was generating
            self._service_parked_snapshot(slot)

    def _decode_dispatch(self) -> None:
        """Dispatch one decode chunk chained on the device carry and queue
        its token readback; processing happens a pipeline slot later. Chunk
        size is policy (_pick_chunk): full at steady state, the smallest
        compiled bucket while anyone waits for admission/prefill."""
        snapshot = [
            (s, s.request, s.dev_position)
            for s in self.slots
            if s.decoding and s.request is not None
        ]
        if not snapshot:
            return
        needed = max(r.max_tokens - r.dispatched for _, r, _ in snapshot)
        if needed <= 0:
            # every live lane's whole budget is already in flight: another
            # chunk would be pure garbage steps while the readbacks land
            return
        # failpoint: a decode fault is batch-wide by construction (one
        # compiled call covers every lane) — the worker fails the in-flight
        # batch and reallocates device state, then keeps serving. Warmup's
        # synthetic requests (empty id) are exempt, same as the prefill seam.
        if any(r.id for _, r, _ in snapshot):
            faults.fire("engine.decode_step")
        chunk = self._pick_chunk(needed)
        with self._spans.span("engine.decode_dispatch"):
            self._dispatch_chunk(snapshot, chunk)

    def _dispatch_chunk(self, snapshot: list, chunk: int) -> None:
        if self.paged:
            # pre-allocate pages covering every step of the chunk so the
            # block table is constant across the compiled scan; a lane the
            # pool can't cover fails with 429 backpressure — the others
            # keep decoding
            kept = []
            for s, r, p in snapshot:
                try:
                    self._ensure_lane_pages(
                        s, min(p + chunk - 1, self.max_seq - 2), serving=bool(r.id)
                    )
                    kept.append((s, r, p))
                except EngineOverloaded as e:
                    self._fail_item(r, e)
                    self._abandon_slot(s, rollback=True)
            snapshot = kept
            if not snapshot:
                return
        self._rng, key = jax.random.split(self._rng)
        keys = jax.random.split(key, chunk)
        toks, self._dtok, self._dpos, self.cache = self._decode_n(
            self.params,
            self.cache,
            *self._bt_arg(),
            self._dtok,
            self._dpos,
            self._dtemps,
            self._dtopk,
            self._dtopp,
            keys,
        )
        opened = self._launch(JIT_DECODE_N, chunk, steps=chunk, rows=len(snapshot), lanes=len(snapshot))
        self._count_moe_rows(self.max_batch, chunk)
        self._count_decode_step(snapshot, toks, opened)

    def _count_decode_step(self, snapshot: list, toks, opened) -> None:
        """The bookkeeping of a dispatched decode chunk ``toks [chunk, B]``
        over ``snapshot``'s lanes, and its readback entry with the launch's
        ledger record ``opened``. Where the step went with a prefill chunk's
        launch (``jit_prefill_with_decode``), that launch streamed the
        weights and counted its forward pass."""
        chunk = toks.shape[0]
        rode = opened.program == JIT_PREFILL_WITH_DECODE
        for s, r, _ in snapshot:
            s.dev_position += chunk
            r.dispatched += chunk
        self._count_decode_blocks([p for _, _, p in snapshot], chunk)
        # weights stream once per scan step; each live lane streams its KV
        # prefix (parked lanes re-read the scratch row — not useful traffic)
        self.hbm_bytes_read += (0 if rode else chunk * self.param_hbm_bytes) + sum(
            chunk * self._kv_read_bytes(p + chunk // 2) for _, _, p in snapshot
        )
        try:
            toks.copy_to_host_async()
        except Exception:
            pass
        self._readbacks.append(("chunk", snapshot, toks, opened))

    def _fused_dispatch(self) -> None:  # atp: hot
        """Dispatch one fused on-device decode loop (fused_decode=True's
        replacement for _decode_dispatch): same snapshot/paged
        pre-allocation discipline, but the compiled call is the dynamic-
        rung while_loop (_fused_fn) that masks finished lanes, runs the
        in-loop drafter/verifier, absorbs the staged injection lane, and
        early-exits on device — the readback queued here is the loop's
        single packed (tokens, lengths, reasons, steps, spec counters)
        transfer. The loop bound ``nsteps`` is a runtime operand of ONE
        compiled executable (_pick_fused_chunk), so the admission
        contention story carries over — contention shrinks the loop,
        newcomers' prefill still preempts at rung boundaries — without a
        per-rung executable ladder. Host-side speculation composes between
        fused loops when in-loop spec is off; with it on, drafting happens
        inside the loop body and _try_speculate is bypassed."""
        base = [
            (s, s.request, s.dev_position)
            for s in self.slots
            if s.decoding and s.request is not None
        ]
        if not base:
            return
        needed = max(r.max_tokens - r.dispatched for _, r, _ in base)
        if needed <= 0:
            return
        # failpoint: same batch-wide seam as engine.decode_step, but its
        # own catalog name — chaos schedules can cut (or delay, for the
        # SIGKILL-mid-loop soak phase) exactly the fused path
        if any(r.id for _, r, _ in base):
            faults.fire("engine.fused_decode")
        chunk = self._pick_fused_chunk()
        with self._spans.span("engine.decode_dispatch"):
            self._dispatch_fused(base, chunk)

    def _dispatch_fused(self, base: list, chunk: int) -> None:  # atp: hot
        if self.paged:
            kept = []
            for s, r, p in base:
                try:
                    # +FUSED_SPEC_K: the in-loop verifier forwards up to K
                    # draft positions past the last real token; those writes
                    # must land in owned pages even when rejected
                    self._ensure_lane_pages(
                        s,
                        min(p + chunk + FUSED_SPEC_K, self.max_seq - 2),
                        serving=bool(r.id),
                    )
                    kept.append((s, r, p))
                except EngineOverloaded as e:
                    self._fail_item(r, e)
                    self._abandon_slot(s, rollback=True)
            base = kept
            if not base:
                return
        self._rng, key = jax.random.split(self._rng)
        keys = jax.random.split(key, self._fused_cap)
        live = np.zeros((self.max_batch,), dtype=bool)
        budgets = np.zeros((self.max_batch,), dtype=np.int32)
        ign = np.zeros((self.max_batch,), dtype=bool)
        armed = np.zeros((self.max_batch,), dtype=bool)
        for s, r, _ in base:
            live[s.idx] = True
            # chunk+1 emission cap: the most one loop can emit (spec can
            # beat one-per-iteration). The device NEVER finishes on budget
            # — cap-hit lanes freeze and the host rescan decides, so this
            # estimate being ≥ true remaining (dispatched counts
            # iterations, not emissions) is the safe direction
            budgets[s.idx] = min(r.max_tokens - r.dispatched, chunk + 1)
            ign[s.idx] = bool(r.ignore_eos)
        if self._staged_lane is not None:
            armed[self._staged_lane] = True
        # per-lane upper bound on this loop's device-position advance —
        # used for dev_position bookkeeping (paging must only ever
        # over-ensure, never under)
        snapshot = [(s, r, p, int(budgets[s.idx])) for s, r, p in base]
        (
            packed,
            self._dtok,
            self._dpos,
            self._dtemps,
            self._dtopk,
            self._dtopp,
            self._dhist,
            self._dhlen,
            self.cache,
        ) = self._fused_fn()(
            self.params,
            self.cache,
            *self._bt_arg(),
            self._dtok,
            self._dpos,
            self._dtemps,
            self._dtopk,
            self._dtopp,
            self._dhist,
            self._dhlen,
            self._stok,
            self._spos,
            self._stemps,
            self._stopk,
            self._stopp,
            self._shist,
            self._shlen,
            jnp.asarray(armed),
            jnp.asarray(live),
            jnp.asarray(budgets),
            jnp.asarray(ign),
            keys,
            jnp.int32(chunk),
        )
        # ``steps`` is the loop's cap: it may stop early, and an in-loop verify is wider
        opened = self._launch(JIT_FUSED, chunk, steps=chunk, rows=len(base), lanes=len(base))
        if self._staged_lane is not None:
            # the loop just dispatched absorbs the staged lane at entry
            self._staged_lane = None
            self.fused_injections_total += 1
        for s, r, _, adv in snapshot:
            # upper bound for unfinished lanes; finished lanes park at
            # scratch on device and their host state is settled (and
            # dev_position corrected) at processing (_process_fused)
            s.dev_position += adv
            r.dispatched += chunk
        self.fused_loops_total += 1
        self._count_moe_rows(self.max_batch, chunk)
        self._count_decode_blocks([p for _, _, p, _ in snapshot], chunk)
        try:
            packed.copy_to_host_async()
        except Exception:
            pass
        self._readbacks.append(("fused", snapshot, packed, chunk, opened))

    def _pick_fused_chunk(self) -> int:  # atp: hot
        """Loop-bound policy for the fused dispatcher. ``nsteps`` is a
        runtime operand (no per-rung executables), so the only tradeoff is
        responsiveness: a longer loop amortizes dispatch/readback overhead
        per token, a shorter one returns to admission/prefill work sooner.
        Steady state rides the static cap (FUSED_RUNG_MULT × decode_chunk);
        contention — a mid-prefill prompt or an admissible waiter — drops
        to the smallest ladder rung, exactly like _pick_chunk. Budget tails
        need no shrinking: per-lane caps freeze finished lanes and the
        whole-batch early exit ends the loop the iteration everyone is
        inactive."""
        if not self.adaptive_decode:
            return self.decode_chunk
        if self._contended() and self._decode_ladder[0] < self._fused_cap:
            self.decode_chunks_shrunk += 1
            return self._decode_ladder[0]
        return self._fused_cap

    def _admissible_waiter(self) -> bool:
        """A queued request that a free slot could take. A waiter only
        benefits from a shrunk chunk if it can actually be admitted: when
        every slot is mid-generation it is gated on a FINISH, not on the
        worker loop's cadence — keep the full chunk or a saturated engine's
        throughput would collapse to chunk-1 dispatch overhead."""
        return bool(self._waiting or not self._queue.empty()) and any(
            s.request is None for s in self.slots
        )

    def _contended(self) -> bool:
        """Does anyone wait on the worker's cadence: a mid-prefill prompt,
        or a queued request that a free slot could take."""
        return (
            any(s.request is not None and s.pending_prompt for s in self.slots)
            or self._admissible_waiter()
        )

    def _pick_chunk(self, needed: int, tail_shrink: bool = True) -> int:
        """Adaptive decode-chunk policy (the admission-aware half of the
        scheduler). Contention — a queued/waiting request or a mid-prefill
        prompt — shrinks to the smallest compiled bucket, so the worker gets
        back to admission/prefill work after ~one ITL instead of a full
        chunk wall (the wall WAS the ~180 ms admission half of single-chip
        TTFT). Otherwise: the smallest bucket covering the remaining token
        budget, so sequence tails don't dispatch overshoot garbage. Steady
        state with budget to burn returns the full chunk — ITL and HBM
        efficiency are untouched when nobody is waiting.

        ``tail_shrink=False`` is the fused dispatcher's mode: its in-loop
        budget masks park finishing lanes on device and the whole-batch
        early exit ends the loop the step everyone is done, so a budget
        tail costs nothing extra on the top rung — and riding the top rung
        pays ONE readback where the shrinking ladder pays one per rung.
        The contention downshift still applies (a loop over live lanes
        can't early-exit on a waiter's behalf)."""
        if not self.adaptive_decode:
            return self.decode_chunk
        if self._contended() and self._decode_ladder[0] < self.decode_chunk:
            self.decode_chunks_shrunk += 1
            return self._decode_ladder[0]
        if not tail_shrink:
            return self.decode_chunk
        target = max(1, min(needed, self.decode_chunk))
        for c in self._decode_ladder:
            if c >= target:
                return c
        return self.decode_chunk

    # -- self-speculative decoding (worker thread) ------------------------
    #
    # Prompt-lookup drafting: agentic traffic (tool-call JSON, flattened
    # histories, retrieval-grounded answers) constantly re-emits spans that
    # already exist in the context, so the slot's OWN token stream is the
    # draft model — zero extra weights. Per round, a host-side drafter
    # proposes up to gamma continuation tokens per lane; one compiled
    # verify forward (t = k+1, the prefill path at per-lane positions)
    # scores every lane's drafts in parallel; the longest agreeing prefix
    # is accepted and the slot's KV position is rewound past rejected
    # tokens (their cache writes sit beyond the live length, where the
    # position mask hides them until the stream overwrites them — the same
    # invariant chunked-decode overshoot already relies on). Greedy lanes
    # are bit-exact with plain decode (acceptance = argmax agreement, the
    # correction token IS the argmax the plain path would have sampled);
    # temperature lanes use standard speculative rejection sampling with a
    # point-mass proposal, which leaves the output distribution unchanged.

    def _verify_fn(self, K: int):
        """Compiled k-token verify step for draft bucket ``K``: feed each
        lane [carry_token, draft_0..draft_{K-1}] at positions [p..p+K],
        accept the longest agreeing draft prefix, and emit accepted drafts
        plus the model's own token at the first unverified row. Returns
        (emitted [B,K+1], count [B], new_tok [B], new_pos [B], cache)."""
        fn = self._verify_fns.get(K)
        if fn is None:
            run_forward = self._run_forward

            def verify_body(
                params, cache, tok, pos, temps, topk, topp, drafts, dlen, key, bt=None
            ):
                # the paged pool's page axis says nothing about the logical
                # arena length — scratch comes from the engine statics there
                scratch = cache.k.shape[2] - 1 if bt is None else self.max_seq - 1
                toks = jnp.concatenate([tok[:, None], drafts], axis=1)  # [B,K+1]
                offs = jnp.arange(K + 1, dtype=jnp.int32)[None, :]
                # parked lanes (and padding rows past a lane's draft_len)
                # clamp at the scratch position, exactly like plain decode
                positions = jnp.minimum(pos[:, None] + offs, scratch)
                logits, cache = run_forward(params, toks, positions, cache, bt)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                k_acc, k_bonus = jax.random.split(key)
                # draft_j (= toks[:, j+1]) is scored by logits row j. Greedy
                # lanes accept on exact argmax agreement; sampled lanes
                # accept with prob p_j(draft_j) — rejection sampling with a
                # point-mass proposal keeps the output distribution intact.
                u = jax.random.uniform(k_acc, drafts.shape)
                probs = jax.nn.softmax(
                    logits[:, :K, :].astype(jnp.float32)
                    / jnp.maximum(temps, 1e-6)[:, None, None],
                    axis=-1,
                )
                p_draft = jnp.take_along_axis(
                    probs, drafts[:, :, None], axis=2
                )[:, :, 0]
                ok = jnp.where(
                    temps[:, None] <= 0.0, drafts == greedy[:, :K], u < p_draft
                )
                ok = ok & (jnp.arange(K, dtype=jnp.int32)[None, :] < dlen[:, None])
                a = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)  # [B]
                # correction/bonus from the first unverified row: on a
                # rejection the rejected draft is masked out of the residual
                # (max(p - q, 0) for a point-mass q is p minus that token);
                # when every draft accepted, row a is the bonus distribution
                row_a = jnp.take_along_axis(logits, a[:, None, None], axis=1)[:, 0]
                draft_a = jnp.take_along_axis(
                    toks, jnp.minimum(a + 1, K)[:, None], axis=1
                )[:, 0]
                rejected = a < dlen
                vocab = jnp.arange(row_a.shape[-1], dtype=jnp.int32)[None, :]
                row_a = jnp.where(
                    (vocab == draft_a[:, None]) & rejected[:, None], NEG_INF, row_a
                )
                # the bonus/correction token goes through the same per-lane
                # filtered sampler as plain decode (lanes with active
                # filters never draft — _spec_gamma gates them to 0 — so
                # the rejection-sampling acceptance above stays valid
                # against the unfiltered target)
                bonus = sample_step(
                    row_a, k_bonus, temps, topk, topp,
                    greedy_cond=self.mesh is None,
                    approx_topk=self.approx_topk,
                ).astype(jnp.int32)
                m = jnp.arange(K + 1, dtype=jnp.int32)[None, :]
                shifted = jnp.concatenate(
                    [toks[:, 1:], jnp.zeros_like(tok)[:, None]], axis=1
                )
                emitted = jnp.where(m < a[:, None], shifted, 0) + jnp.where(
                    m == a[:, None], bonus[:, None], 0
                )
                count = a + 1
                new_pos = jnp.minimum(pos + count, scratch)
                return emitted, count, bonus, new_pos, cache

            if self.paged:

                def verify_paged(
                    params, cache, bt, tok, pos, temps, topk, topp, drafts, dlen, key
                ):
                    return verify_body(
                        params, cache, tok, pos, temps, topk, topp, drafts, dlen, key, bt
                    )

                fn = self._verify_fns[K] = _step_jit(
                    JIT_VERIFY, verify_paged, donate_argnums=(1, 3, 4)
                )
            else:

                def verify(
                    params, cache, tok, pos, temps, topk, topp, drafts, dlen, key
                ):
                    return verify_body(
                        params, cache, tok, pos, temps, topk, topp, drafts, dlen, key
                    )

                fn = self._verify_fns[K] = _step_jit(JIT_VERIFY, verify, donate_argnums=(1, 2, 3))
        return fn

    def _spec_gamma(self, slot: Slot) -> int:
        """Draft-length policy for one lane: EMA-scaled up to gamma_max,
        capped by the request's remaining token budget and the arena
        headroom (drafted positions must stay below scratch). Collapsed
        (low-EMA) and lookup-missing lanes return 0 except for a short
        probe draft every SPEC_PROBE_EVERY decode steps, so a workload
        shift re-opens speculation without taxing the steady state."""
        req = slot.request
        if req is None or not req.generated:
            return 0
        if req.temperature > 0.0 and (req.top_k > 0 or req.top_p < 1.0):
            # point-mass rejection sampling verifies against the UNFILTERED
            # target distribution; a filtered temperature lane would accept
            # drafts the filtered sampler could never emit. Such lanes ride
            # verify rounds draft-free (dlen=0 — the bonus token still goes
            # through their filters). Greedy lanes draft regardless: argmax
            # is invariant under top-k/top-p masking.
            return 0
        cap = min(
            self.spec_gamma_max,
            req.max_tokens - len(req.generated) - 1,
            self.max_seq - 2 - slot.position,
        )
        if cap <= 0:
            return 0
        if slot.spec_ema < SPEC_EMA_FLOOR or slot.spec_miss >= SPEC_MISS_BACKOFF:
            probe_due = self.decode_steps - slot.spec_probe_at >= SPEC_PROBE_EVERY
            return min(2, cap) if probe_due else 0
        return min(max(1, int(round(slot.spec_ema * self.spec_gamma_max))), cap)

    def _spec_draft(self, slot: Slot, gamma: int) -> list[int]:
        """Prompt-lookup draft: the tokens that followed the most recent
        earlier occurrence of the stream's trailing n-gram (longest of
        3-gram / 2-gram). The lookup iterates on the extended stream when
        a match runs out of continuation before ``gamma`` tokens — a
        looping stream (tool-call JSON, repeated structure) drafts the
        whole bucket, not just one cycle's tail. Reverse scans over the
        slot's fed stream — bounded by max_seq, microseconds next to a
        model forward."""
        seq = slot.spec_hist + slot.request.generated
        base = len(seq)
        while len(seq) - base < gamma:
            got = self._spec_lookup(seq, gamma - (len(seq) - base))
            if not got:
                break
            seq.extend(got)
        return [int(t) for t in seq[base:]]

    @staticmethod
    def _spec_lookup(seq: list, want: int) -> list:
        L = len(seq)
        for n in (3, 2):
            if L < n + 1:
                continue
            pat = seq[L - n :]
            floor = max(0, L - n - 1 - SPEC_LOOKUP_WINDOW)
            for i in range(L - n - 1, floor - 1, -1):
                if seq[i : i + n] == pat:
                    return seq[i + n : i + n + want]
        return []

    def _try_speculate(self) -> bool:
        """Run one speculative verify round if the batch has draftable
        lanes. Returns True when a round was dispatched-and-processed (the
        caller skips the plain decode dispatch for this iteration).

        Speculation is a STEADY-STATE optimization: under admission/prefill
        contention the plain ladder (which shrinks) keeps newcomers fast —
        a synchronous verify round would block exactly the queue polling
        that admits them — so contended iterations fall through to the
        plain path unconditionally."""
        if not self._spec_active:
            return False
        if self._waiting or not self._queue.empty():
            return False
        if any(s.request is not None and s.pending_prompt for s in self.slots):
            return False
        if not any(
            s.decoding and s.request is not None and self._spec_gamma(s) > 0
            for s in self.slots
        ):
            return False
        # drafting needs the host's view of every lane's stream to be
        # current: drain the readback pipeline (the drain keeps admitting —
        # _wait_admitting — so this costs sync, not admission latency)
        while self._readbacks:
            self._drain_readbacks(block=True)
            if self._sentinel:
                return True  # unwind; the worker loop re-checks the sentinel
        # the drain may have admitted new work: re-check contention
        if self._waiting or any(
            s.request is not None and s.pending_prompt for s in self.slots
        ):
            return False
        lanes = sum(s.decoding and s.request is not None for s in self.slots)
        with self._spans.span("engine.spec_round", lanes=lanes):
            plan = []
            any_draft = False
            with self._spans.span("engine.spec_draft"):  # host n-gram lookup
                for s in self.slots:
                    if not s.decoding or s.request is None:
                        continue
                    g = self._spec_gamma(s)
                    d = self._spec_draft(s, g) if g > 0 else []
                    if g > 0:
                        s.spec_probe_at = self.decode_steps
                        s.spec_miss = 0 if d else s.spec_miss + 1
                    any_draft = any_draft or bool(d)
                    plan.append((s, s.request, s.position, d))
            if not any_draft:
                return False
            self._spec_round(plan)
        return True

    def _spec_round(self, plan: list) -> None:
        """Dispatch one verify forward for the whole batch and process it
        SYNCHRONOUSLY (the next round's drafts depend on these tokens).
        Every live lane advances at least one token — lanes with no draft
        this round ride along as a plain decode step (draft_len 0)."""
        gmax = max(len(d) for _, _, _, d in plan)
        K = next(b for b in self._spec_buckets if b >= gmax)
        with self._spans.span("engine.verify_dispatch"):
            if self.paged:
                # pages must cover the whole verify write span [p, p+K]; a
                # lane the pool can't cover fails with backpressure, the
                # rest verify
                kept = []
                for s, r, p, d in plan:
                    try:
                        self._ensure_lane_pages(
                            s, min(p + K, self.max_seq - 2), serving=bool(r.id)
                        )
                        kept.append((s, r, p, d))
                    except EngineOverloaded as e:
                        self._fail_item(r, e)
                        self._abandon_slot(s, rollback=True)
                plan = kept
                if not plan:
                    return
            drafts = np.zeros((self.max_batch, K), dtype=np.int32)
            dlen = np.zeros((self.max_batch,), dtype=np.int32)
            for s, _, _, d in plan:
                if d:
                    drafts[s.idx, : len(d)] = d
                    dlen[s.idx] = len(d)
            self._rng, key = jax.random.split(self._rng)
            emitted_dev, count_dev, self._dtok, self._dpos, self.cache = (
                self._verify_fn(K)(
                    self.params,
                    self.cache,
                    *self._bt_arg(),
                    self._dtok,
                    self._dpos,
                    self._dtemps,
                    self._dtopk,
                    self._dtopp,
                    jnp.asarray(drafts),
                    jnp.asarray(dlen),
                    key,
                )
            )
            opened = self._launch(JIT_VERIFY, K, rows=int(dlen.sum()) + len(plan), lanes=len(plan))
        self._count_moe_rows(self.max_batch * (K + 1))
        with self._spans.span("engine.verify_readback"):
            self._verify_readback(plan, K, dlen, emitted_dev, count_dev, opened)

    def _verify_readback(self, plan: list, K: int, dlen, emitted_dev, count_dev, opened) -> None:
        with self._spans.span("engine.wait_device"):
            emitted = np.asarray(emitted_dev)  # sync readback: spec rounds don't pipeline
            count = np.asarray(count_dev)
        self._launches.ready(opened)
        end = time.monotonic()
        self.spec_rounds += 1
        # the whole k+1-token verify streams the weights ONCE (that is the
        # point of batching the verification) plus each live lane's prefix
        self.hbm_bytes_read += self.param_hbm_bytes + sum(
            (p + K // 2) * self._kv_bytes_per_pos for _, _, p, _ in plan
        )
        eos = self.tokenizer.eos_id
        total_used = 0
        for slot, req, p, d in plan:
            if slot.request is not req:
                continue
            c = int(count[slot.idx])
            l = int(dlen[slot.idx])
            self.spec_drafted += l
            self.spec_accepted += c - 1
            if l:
                slot.spec_ema = (
                    1 - SPEC_EMA_ALPHA
                ) * slot.spec_ema + SPEC_EMA_ALPHA * ((c - 1) / l)
            outs = emitted[slot.idx]
            remaining = req.max_tokens - len(req.generated)
            used = 0
            hit_eos = False
            for j in range(min(c, remaining)):
                used += 1
                if not req.ignore_eos and int(outs[j]) == eos:
                    hit_eos = True
                    break
            req.generated.extend(int(t) for t in outs[:used])
            req.emit_appended(used)
            req.dispatched += c
            self.tokens_generated += used
            total_used += used
            self.flops_done += used * self.cfg.flops_per_token(p + used // 2)
            finished = hit_eos or len(req.generated) >= req.max_tokens
            if finished and used < c:
                # the used-th token was an ACCEPTED draft — already fed
                # through the model at position p + used
                slot.position = p + used + 1
                slot.dev_position = slot.position
                self._finish(slot, pending_last=False)
            elif finished:
                slot.position = p + c
                slot.dev_position = slot.position
                self._finish(slot, pending_last=True)
            else:
                # KV rewind: rejected drafts left stale rows at positions
                # >= p + c; the next fed token overwrites p + c before any
                # query can attend there, and the position mask hides the
                # rest until the stream grows past them
                slot.position = p + c
                slot.dev_position = slot.position
                slot.last_used = end
                if self.paged and slot.psess is not None:
                    # rewind = page-tail truncation: pages holding ONLY
                    # rejected-draft garbage return to the pool right now
                    slot.psess.position = slot.position
                    self._truncate_session_pages(slot.psess)
        if self._last_decode_end is not None and total_used:
            self.itl_ms_recent.append(
                1000 * (end - self._last_decode_end) / total_used
            )
        self._last_decode_end = end

    def _drain_readbacks(self, block: bool) -> None:
        """Process landed readbacks in FIFO order. An entry is forced to
        completion when ``block`` asks for one (idle drain) or whenever the
        queue is deeper than the pipeline depth — the queue must NEVER grow
        past depth+1, or every response is delivered queue-length × chunk
        wall LATE. (Round-5 hardware run: one forced drain per iteration
        while prefill turns appended two entries grew the queue to ~40 —
        admission was 160 ms but TTFT read 6 s, all of it delivery lag —
        the length bound is the backpressure when readiness polls lag.)

        Forced waits are ADMISSION-AWARE (_wait_admitting): while the oldest
        entry's value crosses the device boundary, the submit queue keeps
        being polled and a newcomer's first prefill chunk is dispatched the
        moment it arrives — dispatches are async, so the device pipelines
        the prefill behind the in-flight decode chunk while the host keeps
        waiting. (The round-5 ~180 ms admission p50 was exactly this wait:
        one full chunk wall between queue polls.)"""
        # (Eager out-of-band delivery of first-token entries was tried and
        # reverted: it blocks the worker on an extra fetch per prefill for
        # a TTFT change inside run-to-run noise, at ~7% decode throughput.)
        while self._readbacks:
            entry = self._readbacks[0]
            arr = entry[3] if entry[0] == "first" else entry[2]
            if not (block or len(self._readbacks) > self._PIPELINE_DEPTH):
                if not arr.is_ready():
                    return
            elif self.adaptive_decode:
                # adaptive_decode=False is the FIXED-CADENCE baseline
                # scheduler — it hard-blocks in processing like the round-5
                # engine did
                self._wait_admitting(arr)
                if self._sentinel:
                    return
            self._readbacks.popleft()
            if entry[0] == "first":
                self._process_first(entry)
            elif entry[0] == "fused":
                self._process_fused(entry)
            else:
                self._process_chunk(entry)
            block = False

    @_phase("engine.wait_device")
    def _wait_admitting(self, arr) -> None:
        """Forced-drain wait that keeps admitting: poll the submit queue
        while the readback completes, and dispatch a fresh arrival's FIRST
        prefill chunk immediately (later chunks ride the normal interleave).
        Waiting happens ON the queue (get with a small timeout), so an
        enqueue wakes the worker instantly. Backends whose arrays can't
        poll readiness get one admission pass, then fall back to the hard
        block inside processing."""
        while not self._sentinel:
            self._pump_queue(0.0)
            if self._sentinel:
                return
            if self._waiting:
                self._admit_waiting()
            while any(
                s.request is not None
                and s.pending_prompt
                and s.request.prefill_started_at is None
                for s in self.slots
            ):
                try:
                    self._prefill_tick()
                except Exception as e:
                    # same per-request isolation as the main loop's tick
                    self._note_error(e)
                    slot = self._prefilling_slot
                    if slot is not None and slot.request is not None:
                        self._fail_item(slot.request, _as_prefill_failure(e))
                        self._reset_slot(slot)
                    self._ensure_device_state()
                finally:
                    self._prefilling_slot = None
            try:
                if arr.is_ready():
                    return
            except Exception:
                return  # not pollable: processing's np.asarray blocks instead
            try:
                item = self._queue.get(timeout=0.001)
            except queue.Empty:
                continue
            if item is None:
                self._sentinel = True
                return
            self._waiting.append(item)

    def _process_first(self, entry) -> None:
        _, slot, req, first, sampled = entry
        if slot.request is not req:
            return  # request failed/superseded while the copy was in flight
        with self._spans.span("engine.process_readback", request_id=req.id):
            self._deliver_first(slot, req, first, sampled)

    def _deliver_first(self, slot: Slot, req: GenRequest, first, sampled) -> None:
        with self._spans.span("engine.wait_device"):
            first_id = int(np.asarray(first)[0])
        self._launches.ready(sampled)
        now = time.monotonic()
        req.ttft_ms = 1000 * (now - req.submitted_at)
        self.ttft_ms_recent.append(req.ttft_ms)
        # the other two TTFT phases (queue-wait lands at prefill start):
        # prefill span and the readback tail after first-token injection
        if req.prefill_started_at is not None and req.prefill_done_at is not None:
            self.prefill_ms_recent.append(
                1000 * (req.prefill_done_at - req.prefill_started_at)
            )
            self.first_readback_ms_recent.append(1000 * (now - req.prefill_done_at))
        req.generated.append(first_id)
        req.emit_appended(1)
        self.tokens_generated += 1
        if len(req.generated) >= req.max_tokens or (
            not req.ignore_eos and first_id == self.tokenizer.eos_id
        ):
            # first token not yet in KV: carried into the next turn's prompt
            self._finish(slot, pending_last=True)

    @_phase("engine.process_readback")
    def _process_chunk(self, entry) -> None:
        _, snapshot, toks_dev, opened = entry
        with self._spans.span("engine.wait_device"):
            toks = np.asarray(toks_dev)  # [chunk, B]
        self._launches.ready(opened)
        chunk = toks.shape[0]
        # ITL = wall time between consecutive chunk completions (including
        # any interleaved prefill chunk) per generated token
        end = time.monotonic()
        if self._last_decode_end is not None:
            self.itl_ms_recent.append(1000 * (end - self._last_decode_end) / chunk)
        self._last_decode_end = end
        eos = self.tokenizer.eos_id
        for slot, req, start in snapshot:
            if slot.request is not req:
                continue  # finished in an earlier (lagged) entry
            if not req.generated:
                # first token's readback hasn't been processed yet (it sits
                # later in the FIFO)? cannot happen: FIFO order guarantees
                # the "first" entry precedes every chunk that continues it
                continue
            outs = toks[:, slot.idx]
            remaining = req.max_tokens - len(req.generated)
            used = 0
            hit_eos = False
            for j in range(min(chunk, remaining)):
                used += 1
                if not req.ignore_eos and int(outs[j]) == eos:
                    hit_eos = True
                    break
            req.generated.extend(int(t) for t in outs[:used])
            req.emit_appended(used)
            self.tokens_generated += used
            # useful decode FLOPs only: overshoot tokens and parked lanes
            # are real compute but wasted — MFU should show that, not hide it
            self.flops_done += used * self.cfg.flops_per_token(start + used // 2)
            finished = hit_eos or len(req.generated) >= req.max_tokens
            if finished and self._hybrid:
                # a recurrent state cannot be rewound, so the device never
                # feeds a reply's last token (the lane's stop / EOS mask):
                # the state stands at the tokens before it, wherever in the
                # chunk the reply ended, and the token is carried over
                slot.position = start + used
                self._finish(slot, pending_last=True)
            elif finished and used < chunk:
                # chunk overshot: the used-th token was already fed at
                # position start+used; later writes overwrite the overshoot
                slot.position = start + used + 1
                self._finish(slot, pending_last=False)
            elif finished:
                slot.position = start + chunk
                self._finish(slot, pending_last=True)
            else:
                slot.position = start + chunk

    @_phase("engine.process_readback")
    def _process_fused(self, entry) -> None:  # atp: hot
        """Process one fused loop's packed readback — the loop's ONE host
        sync. The host rescans the emitted tokens against its own remaining
        budget and EOS policy (the same scan _process_chunk runs), so stale
        lanes and mid-flight aborts resolve identically in both modes; the
        device's finish reasons are trusted only for device-state
        bookkeeping. A finished lane parked in-loop, so its finishing token
        was never fed: ``pending_last=True`` for every fused finish, and
        slot.position lands at start+used (no overshoot feed to roll back)."""
        _, snapshot, packed_dev, chunk, opened = entry
        cap_rows = self._fused_cap + 1
        # [cap_rows+5, B]: tokens / counts / reasons / steps / nacc / ndr
        with self._spans.span("engine.wait_device"):
            packed = np.asarray(packed_dev)
        self._launches.ready(opened)
        steps = int(packed[cap_rows + 2, 0])
        self.fused_steps_total += steps
        if steps < chunk:
            self.fused_early_exits_total += 1
            self.fused_exit_reason_hist["early_all_finished"] = (
                self.fused_exit_reason_hist.get("early_all_finished", 0) + 1
            )
        else:
            self.fused_exit_reason_hist["limit"] = (
                self.fused_exit_reason_hist.get("limit", 0) + 1
            )
        end = time.monotonic()
        # ITL per TOKEN, not per iteration: in-loop spec can emit several
        # tokens per iteration, and the bench compares fused vs unfused on
        # token cadence. The deepest lane's emission count is the loop's
        # token depth; a loop whose lanes all went stale falls back to the
        # iteration count.
        depth = max(
            (int(packed[cap_rows, s.idx]) for s, r, _, _ in snapshot if s.request is r),
            default=0,
        ) or steps
        if self._last_decode_end is not None and depth:
            self.itl_ms_recent.append(1000 * (end - self._last_decode_end) / depth)
        self._last_decode_end = end
        # HBM accounting happens here (not at dispatch) because the
        # executed step count is data-dependent: weights stream once per
        # while_loop iteration actually run, plus each lane's KV prefix
        self.hbm_bytes_read += steps * self.param_hbm_bytes + sum(
            steps * self._kv_read_bytes(p + steps // 2) for _, _, p, _ in snapshot
        )
        eos = self.tokenizer.eos_id
        for slot, req, start, _adv in snapshot:
            if slot.request is not req:
                continue  # finished/aborted in an earlier (lagged) entry
            if not req.generated:
                continue  # FIFO order puts the "first" entry before any loop
            cnt = int(packed[cap_rows, slot.idx])
            reason = int(packed[cap_rows + 1, slot.idx])
            self.inloop_spec_accepted += int(packed[cap_rows + 3, slot.idx])
            self.inloop_spec_drafted += int(packed[cap_rows + 4, slot.idx])
            outs = packed[:, slot.idx][:cnt]
            remaining = req.max_tokens - len(req.generated)
            used = 0
            hit_eos = False
            for j in range(min(cnt, remaining)):
                used += 1
                if not req.ignore_eos and int(outs[j]) == eos:
                    hit_eos = True
                    break
            req.generated.extend(int(t) for t in outs[:used])
            req.emit_appended(used)
            self.tokens_generated += used
            self.flops_done += used * self.cfg.flops_per_token(start + used // 2)
            finished = hit_eos or len(req.generated) >= req.max_tokens
            if finished:
                # the host scan is AUTHORITATIVE for budget finishes (the
                # device only ever declares EOS; cap-hit lanes froze with
                # reason 0). An EOS finish never fed its token (in-loop
                # park) and a budget finish froze before feeding past its
                # cap: pending_last=True either way, position at start+used
                slot.position = start + used
                self._finish(slot, pending_last=True)
            elif reason != 0:
                # defensive: the device parked a lane the host scan wants
                # to keep (cannot happen while ignore_eos policies agree —
                # but a parked live lane would decode garbage at scratch
                # forever, so re-point it at its last token explicitly)
                slot.position = start + used
                slot.dev_position = slot.position
                self._inject_lane(
                    slot.idx,
                    jnp.int32(int(outs[used - 1])),
                    slot.position,
                    req.temperature,
                    req.top_k,
                    req.top_p,
                )
            else:
                # live (or frozen-at-cap) lane: dev_position was advanced by
                # the budget upper bound at dispatch; settle it to the REAL
                # device position (start + cnt) plus the upper bounds of any
                # still-in-flight loops that include this lane
                slot.position = start + used
                pending = sum(
                    adv2
                    for e in self._readbacks
                    if e[0] == "fused"
                    for s2, r2, _p2, adv2 in e[1]
                    if s2 is slot and r2 is req
                )
                slot.dev_position = start + cnt + pending


def _resolve(future: asyncio.Future, result: dict) -> None:
    if not future.done():
        future.set_result(result)


def _resolve_value(future: asyncio.Future, value) -> None:
    if not future.done():
        future.set_result(value)


def _reject(future: asyncio.Future, error: Exception) -> None:
    if not future.done():
        # EngineOverloaded covers worker-side PagePoolExhausted: pool
        # backpressure must reach the serve layer typed (429), not be
        # laundered into a generic 500. PrefillFailed must survive for the
        # same reason: the serve layer marks its 500 poisoned so the proxy
        # charges the tightened dead-letter budget instead of archiving it
        if isinstance(
            error, (EngineShutdown, RequestAborted, EngineOverloaded, PrefillFailed)
        ):
            future.set_exception(error)  # callers can catch the type
        else:
            future.set_exception(RuntimeError(f"engine worker error: {error}"))
