"""Paged-KV continuous-batching engine with prefix page sharing
(reference: vLLM's PagedAttention as delegated by
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_models.py, and
the prefix-aware machinery in serve/request_router/; re-designed
TPU-native: page pools in the Pallas paged-attention kernel's layout,
one jitted decode step for the whole active batch).

HBM scales with tokens-in-flight (`num_pages x page_size`), not
`max_batch x max_len`; full prompt pages shared byte-identically across
requests via a prefix hash (system prompts stored once); admission
blocks on page budget, not on a row's shape.

Scheduling is continuous (iteration-level): every tick fills
freed slots from the waiting queue, advances at most
`prefill_decode_ratio` chunked-prefill chunks interleaved with the
decode batch, and under page pressure preempts the youngest sequence
(pages released, request parked for re-admission with its generated
tokens as a prompt extension) instead of exhausting the pool. Prefix
reuse rides a radix tree over KV pages (`radix.py`): admission maps the
longest cached prefix copy-on-write into the block table and prefills
only the tail.

The tick runs one decode step ahead of the host: a visit dispatches step
n+1 before it reads step n's tokens, which stay on the device and feed
step n+1 there, so the host's part of a tick runs while the chip
computes (`PagedLLMEngine.step`).

This file is the SCHEDULER and names no kind of model. What a row of a
model keeps between calls, the programs that reach it and the counters
of it are its kind's, one class a kind in `kinds.py`.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import os
import queue
import re
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .._internal import accel as _accel
from .._internal.config import CONFIG
from ..models.moe import sorted_buckets
from ..ops.paged_attention import paged_kernel
from . import reqtrace
from ._metrics import llm_metrics
from .radix import RadixPrefixCache
from .sampling import SAMPLER_TIERS, sample_tokens
from .staging import StagedRows

_TAGS = {"engine": "paged"}
# gauges are per-process series (see _metrics.py on the merge semantics)
_GAUGE_TAGS = {"engine": "paged", "pid": str(os.getpid())}


@dataclasses.dataclass
class GenerationRequest:
    prompt_tokens: List[int]
    max_new_tokens: int = 32
    request_id: str = ""
    temperature: Optional[float] = None
    # 0/None = no k filter; 1.0/None = no nucleus filter (vLLM-style
    # SamplingParams; applied inside the jitted decode, sampling.py)
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    # request-observatory labels: propagated by the serve proxy
    # (X-RTPU-Tenant, matched route prefix) down to the engine and
    # folded into per-tenant/per-route percentiles (llm/reqtrace.py)
    tenant: Optional[str] = None
    route: Optional[str] = None
    # for a model that generates by diffusion over blocks (`kinds.py`;
    # None: the model configuration's own): denoising forwards a block
    # (1 .. block_length: quality against latency), the unmasking rule
    # ("static" or "dynamic": both rank by low confidence) and the dynamic
    # rule's threshold; every other model ignores them
    denoising_steps: Optional[int] = None
    remasking: Optional[str] = None
    confidence_threshold: Optional[float] = None


@dataclasses.dataclass
class PagedEngineConfig:
    # A model's configuration, of ONE kind of cache (`kinds.kind_of`): what
    # the engine asks of it is the docstring of its kind's class in
    # `kinds.py` (dense, recurrent, pooled, windowed, latent, indexed,
    # blockwise)
    model: Any
    max_batch: int = 4            # concurrent decode rows
    max_len: int = 512            # per-request logical cap
    page_size: int = 16
    num_pages: int = 256          # pool capacity = num_pages * page_size
    prefill_buckets: Tuple[int, ...] = (32, 64, 128, 256)
    temperature: float = 0.0
    eos_token: Optional[int] = None
    seed: int = 0
    # continuous batching: prefill chunks advanced per scheduler tick
    # (bounds how much prefill compute a tick may steal from decode)
    prefill_decode_ratio: int = 1

    @property
    def pages_per_seq(self) -> int:
        """The width of a row's block table (its kind's to say)."""
        return _kinds.ENGINES[_kinds.kind_of(self.model)]._pages_per_seq(self)


@functools.lru_cache(maxsize=8)
def _param_init(cfg, mesh):
    """(init program, param shardings) of a model on a mesh. Random
    weights are made ON the device(s), already in their final layout, by
    one jitted program — an eager flax init dispatches (and compiles)
    every initializer op by op. Cached, so engines of one configuration
    share one trace and one compile."""
    from ..parallel.mesh import unbox
    model = cfg.module()
    sample = jnp.zeros((1, 8), jnp.int32)
    pshard = None
    if mesh is not None:
        from ..parallel.mesh import DEFAULT_LOGICAL_AXIS_RULES
        from ..parallel.spmd import logical_names_tree, shardings_tree
        names = logical_names_tree(model, jax.random.PRNGKey(0), sample)
        pshard = shardings_tree(names, mesh,
                                dict(DEFAULT_LOGICAL_AXIS_RULES))
    def init_params(rng):
        return unbox(model.init(rng, sample)["params"])

    return jax.jit(init_params, out_shardings=pshard), pshard


# Visits between two settings of the engine's gauges, as many as between two
# flushes of the `tick` accumulator: the metrics flusher samples a gauge
# every 5 s, and `shared_pages` walks the whole radix (0.8 ms on a full one).
_GAUGES_EVERY = 16


def _no_phase(*names: str):
    """`StepTimer.phase` / `.part` for a caller outside any timer."""
    return contextlib.nullcontext()


def _no_poll():
    """`DryWatch.poll` for an engine that keeps no watch (the accel
    plane's kill switch)."""


def pool_copies(compiled_text: str, pool_shape) -> int:
    """`copy` ops of a compiled program (`compiled.as_text()`) whose
    result has the shape of one whole pool (a page pool's, a state
    pool's): each moves the pool to another layout or memory. Indexed by
    (page, offset) alone, the decode token's write cost four a layer a
    tick (PERF.md, PR 29)."""
    dims = ",".join(map(str, pool_shape))
    return len(re.findall(
        rf"= \w+\[{dims}\]\S* copy(?:-done)?\(", compiled_text))


def array_shapes(compiled_text: str, shape) -> int:
    """Arrays of `shape` (whatever their type and layout) named anywhere
    in a compiled program's text. A prefill chunk that applies the head
    to one row holds none of `[chunk, vocab]`."""
    dims = ",".join(map(str, shape))
    return len(re.findall(rf"\w+\[{dims}\]", compiled_text))


def chunk_logits(model, params, hidden, last):
    """The head in a prefill chunk, over the final norm's output `hidden`
    [1, chunk, hidden size]. `last`, an int32 scalar, is the row of the
    prompt's final token if this chunk holds it and -1 otherwise: the
    logits of that one row, [1, vocab] float32 (the first token is
    sampled from them), and zeros from a chunk that finishes nothing,
    which then neither runs the head nor reads its weights (a `cond`:
    one program a bucket either way). `last=None` is the form before PR
    40, logits at every position [1, chunk, vocab] float32, which the
    tick no longer runs; both forms share every line in front of the
    head and differ in the rows that meet it."""
    def head(rows):
        return model.apply({"params": params}, rows,
                           method="head").astype(jnp.float32)

    if last is None:
        return head(hidden)
    row = jax.lax.dynamic_slice_in_dim(
        hidden, jnp.maximum(last, 0), 1, axis=1)
    return jax.lax.cond(
        last >= 0, lambda: head(row)[:, 0],
        lambda: jnp.zeros((hidden.shape[0], model.config.vocab_size),
                          jnp.float32))


@functools.partial(jax.jit, static_argnames=("sampled",))
def first_token(tokens, last, slot, rng, temperature, top_k, top_p,
                sampled):
    """The token a prompt's prefill ends in, from its last position's
    logits `last` ([1, vocab], as the prompt's last `chunk_prefill`
    returned them; the [1] parameter vectors are the request's), into
    row `slot` of the engine's token vector: the first
    token reaches the host with the next read of the vector, not through
    a fetch of its own. `sampled`: the request has a temperature, so the
    token is drawn as `decode_step` draws; the argmax otherwise, in a
    program of its own (the sampler's program holds a sort of the
    vocabulary even where no batch takes that branch, and compiling that
    sort takes the TPU's compiler 10-35 s)."""
    if sampled:
        token = sample_tokens(rng, last, temperature, top_k, top_p)
    else:
        token = jnp.argmax(last, axis=-1)
    return tokens.at[slot].set(token[0].astype(jnp.int32))


class PagePool:
    """Physical page allocator with refcounts (shared prefix pages)."""

    def __init__(self, num_pages: int):
        self._free = list(range(num_pages - 1, 0, -1))
        # page 0 is the null page block tables pad with; never allocated
        self.refs = np.zeros(num_pages, np.int32)
        self.refs[0] = 1

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        page = self._free.pop()
        self.refs[page] = 1
        return page

    def incref(self, page: int):
        self.refs[page] += 1

    def decref(self, page: int):
        self.refs[page] -= 1
        if self.refs[page] == 0:
            self._free.append(page)

    def num_free(self) -> int:
        return len(self._free)


@dataclasses.dataclass
class _Seq:
    request: Optional[GenerationRequest] = None
    pages: List[int] = dataclasses.field(default_factory=list)
    own_from: int = 0            # pages[:own_from] are shared (prefix)
    length: int = 0              # cached tokens
    generated: List[int] = dataclasses.field(default_factory=list)
    # tokens computed or in flight for this row since admission: the one
    # its prefill ends in, then one a dispatched decode step. `generated`
    # holds those the host has read; `length` counts a dispatched step's
    # token as cached already
    dispatched: int = 0
    cancelled: bool = False
    # continuous-batching state
    phase: str = "decode"        # "prefill" until the prompt is cached
    prompt: List[int] = dataclasses.field(default_factory=list)
    # tokens generated before a preemption, re-prefilled as prompt
    resume: List[int] = dataclasses.field(default_factory=list)
    prefill_off: int = 0         # prompt tokens cached so far
    dense_caches: Any = None     # in-flight chunked-prefill cache
    # logits at the prompt's last position, [1, vocab] float32 on the
    # device: what the chunk that finished the prompt returned (or another
    # engine shipped); None until then and after `first_token` took it
    last_logits: Any = None
    admit_at: int = 0            # admission order (preemption picks max)
    # a row of a blockwise model (`kinds.BlockwiseEngine`). `length` is its COMMITTED length (a
    # commit forward dispatched counts): the positions in flight are the
    # open block's, `block_at` .. `block_at` + block_length - 1 (-1: no block
    # is open). `block_tail`: the prompt's last `len % block_length` tokens,
    # which open the first block as fixed ids (`block_fixed` of them in the
    # open block). `block_masks`: the masks the block holds after the
    # forwards dispatched, as the static rule counts them (the dynamic rule
    # may be ahead: `_emit_blocks`); `block_step`: denoising forwards
    # dispatched in it; `block_take`: the tokens it hands out; `block_last`:
    # no block opens behind it; `block_counted`: its tokens are in
    # `dispatched`; `block_rule`: the request's (denoising steps,
    # threshold). `flight`: per forward dispatched and not yet read,
    # (block_at, fixed, take, last, masks believed left)
    block_at: int = -1
    block_tail: List[int] = dataclasses.field(default_factory=list)
    block_fixed: int = 0
    block_masks: int = 0
    block_step: int = 0
    block_take: int = 0
    block_last: bool = False
    block_counted: bool = False
    block_rule: Tuple[int, float] = (1, 2.0)
    block_opened_ts: float = 0.0
    blocks_done: int = 0
    flight: "collections.deque" = dataclasses.field(
        default_factory=collections.deque)


class PagedLLMEngine:
    """The serve path's engine: submit/step/generate/stats, cancel() and
    per-token streaming callbacks. This class is the SCHEDULER: pages, rows,
    the step ahead, the radix. What a row of the model keeps and how a chunk
    and a step reach it is its kind's (`kinds.py`): `PagedLLMEngine(config)`
    makes the class `kinds.kind_of(config.model)` names, a subclass of this
    one, and the methods below marked "a kind's hook" are what the
    scheduler calls of it (here: what the dense kind does).

    Tensor parallelism: pass `mesh` (a jax Mesh with a `tensor` axis) and
    params + KV pages are sharded over it — params by their flax logical
    axes (heads/kv_heads/mlp/vocab -> tensor), pages on the kv_heads dim
    — so models larger than one chip's HBM serve across chips. The page
    table and scheduler stay host-side and see only logical page ids
    (reference: TP×PP engine-worker placement in
    llm/_internal/serve/deployments/llm/vllm/vllm_models.py:169-178,251;
    here TP is a mesh axis and GSPMD/shard_map insert the collectives)."""

    # what a kind says of itself (`kinds.py`): its name; whether its step
    # is told which rows decode, its chunks where the bucket's padding
    # starts, and whether a prompt's last chunk returns the logits a first
    # token is sampled from; the key `stats()` names its kernel by; why its
    # prefill cannot run on another engine, and why it cannot be built over a
    # tensor mesh (None: it can)
    kind = "dense"
    _takes_live = False
    _pads_told = False
    _samples_prompt = True
    _kernel_key = "paged_kernel"
    _not_shipped: Optional[str] = None
    _no_mesh: Optional[str] = None

    def __new__(cls, config: PagedEngineConfig, *args, **kwargs):
        if cls is PagedLLMEngine:
            cls = _kinds.ENGINES[_kinds.kind_of(config.model)]
        return object.__new__(cls)

    def __init__(self, config: PagedEngineConfig,
                 params: Optional[Any] = None, mesh=None):
        self.config = config
        cfg = config.model
        self.model = cfg.module()
        self.mesh = mesh
        self._tp = int(mesh.shape.get("tensor", 1)) if mesh is not None \
            else 1
        self._refuse()
        rng = jax.random.PRNGKey(config.seed)
        self._page_sharding = None
        self._dense_sharding = None
        init, pshard = _param_init(cfg, mesh)
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as PSpec
            # pages: [kv_heads, pages, page_size, hd] sharded on kv_heads
            self._page_sharding = NamedSharding(mesh, PSpec("tensor"))
            # dense prefill caches: [1, kv_heads, L, hd]
            self._dense_sharding = NamedSharding(mesh, PSpec(None, "tensor"))
        if params is None:
            params = init(rng)
        elif pshard is not None:
            # Params from a single-device engine or a checkpoint:
            # scatter to the mesh layout.
            params = jax.device_put(params, pshard)
        self.params = params
        # the buckets whose chunks take the routed experts' sorted form
        # (`models.moe.sorted_form`, a function of the bucket and the
        # experts' shapes: what the chunk's program did at trace time);
        # None for a model without routed experts
        self._sorted_buckets = sorted_buckets(params, config.prefill_buckets)
        self._rng = rng
        # what a row keeps, made by its kind (`_init_cache`): a page pool
        # per layer that attends (`k_pages`, `v_pages`, `index_pages`),
        # recurrent state beside the pages (`state`), accumulators the
        # model carries through the decode step (`counters`) and through
        # its prefill chunks (`chunk_counters`); `stats()` reads the host
        # copies `read_counters` publishes
        self.index_pages: List[Any] = []
        self.state = None
        self.counters: List[Any] = []
        self.chunk_counters: List[Any] = []
        self._init_cache()
        self._counters_host = jax.device_get(self.counters)
        self._counters_at = 0          # `_steps` when that copy was made
        self._counters_asked = False
        self._chunk_counters_host = jax.device_get(self.chunk_counters)
        # prompt tokens mapped from the radix and computed; radix nodes
        # evicted
        self._prefix_shared_tokens = 0
        self._prefill_computed_tokens = 0
        self._radix_evictions = 0
        self.pool = PagePool(config.num_pages)
        self.radix = RadixPrefixCache(
            self.pool, config.page_size,
            max_entries=int(CONFIG.prefix_cache_entries))
        # waiting queue: _pending is the thread-safe ingress; the tick
        # drains it into _parked, which also receives preempted requests
        # at its FRONT (they re-admit first)
        self._parked: "collections.deque" = collections.deque()
        self._admit_clock = 0
        self._preemptions = 0
        # recent TTFTs feed autoscaling_metrics() (median over a window)
        self._recent_ttfts: "collections.deque" = collections.deque(
            maxlen=64)
        self._prefix_hits = 0
        self._prefix_misses = 0
        self.seqs: List[_Seq] = [_Seq() for _ in range(config.max_batch)]
        self._pending: "queue.Queue[GenerationRequest]" = queue.Queue()
        self._by_id: Dict[str, _Seq] = {}
        self._steps = 0
        self._tokens_generated = 0
        # One decode step of lookahead. `_tokens` is the token vector on
        # the device, row = slot: the last dispatched decode step's
        # output, with the token a finished prefill ends in written at
        # its row since. It is the next step's input as it stands and
        # makes no trip through the host. `_unread` lists the (slot, seq)
        # whose newest token is in it and has not been read.
        self._tokens = jnp.zeros(self._report_shape(), jnp.int32)
        if mesh is not None:
            self._tokens = jax.device_put(
                self._tokens, NamedSharding(mesh, PSpec()))
        self._unread: List[Tuple[int, _Seq]] = []
        # what the step is told about its rows (tables, lengths, sampling
        # triples, the rows that decode), kept between visits on the host
        # and on the device: only what changed is written or sent
        self._stage = StagedRows(config.max_batch, config.pages_per_seq)
        # requests that ended since step() last returned
        self._finished: List[Tuple[GenerationRequest, Any]] = []
        # decode steps dispatched before the tokens of the step before
        # were read / reads with nothing dispatched behind them, by reason
        # / tokens computed for a row found finished or cancelled a tick
        # late (dropped: never emitted, never counted)
        self._lookahead_ticks = 0
        self._drained_ticks: Dict[str, int] = {}
        self._discarded_tokens = 0
        # what the visits dispatched (the `tick` row's counters)
        self._decode_rows = 0
        # decode steps dispatched, by the branch `sample_tokens` takes in
        # each (`sampling.SAMPLER_TIERS`)
        self._sampler_steps = [0] * len(SAMPLER_TIERS)
        self._prefill_chunks = 0
        self._prefill_chunks_sorted = 0     # of them, on the sorted form
        self._prefill_heads = 0     # chunks that ran the head (on one row)
        self._prompts_finished = 0
        # accelerator-plane step telemetry (StepTimer on the decode
        # tick): decode forward ≈ 2 FLOPs per param per token. Checked
        # once here so a killed plane costs the tick two attribute
        # loads, nothing more.
        self._accel = _accel if not _accel.accel_disabled() else None
        if self._accel is not None:
            # listeners precede this engine's prefill/decode compiles
            _accel.ensure_installed()
        # per-tick timings fold locally and flush one aggregated report
        # every 16 ticks — the tick itself pays a perf_counter pair
        self._step_accum = _accel.StepAccumulator("decode") \
            if self._accel is not None else None
        # the whole continuous tick by phase (kind "tick"; "decode" above
        # keeps its extent, dispatch to last callback, inside it)
        self._tick_accum = _accel.StepAccumulator("tick", timeline=True) \
            if self._accel is not None else None
        # the account of a dry device (README, "Tick phases"): polled
        # before and set after every program this engine dispatches
        # (`_dispatching` / `_dispatched`), where a phase of the tick ends
        # and inside the stepping thread's long loops (the radix's)
        self._dry = _accel.DryWatch() if self._accel is not None else None
        # one poll of it, from inside the stepping thread's loops
        self._poll = self._dry.poll if self._dry is not None else _no_poll
        if self._dry is not None:
            self.radix.poll = self._poll
        # perf_counter at the last tick's end if work was left then:
        # the next tick's `between` runs from it
        self._tick_end: Optional[float] = None
        self._num_params = sum(
            int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(self.params))
        # the five dense programs on every engine (the benchmark's harness
        # reaches for them there), then the kind's own over them
        self._dense_programs()
        self._kind_programs()

    # -- a kind's hooks: what it keeps (defaults: the dense kind's) --------

    @staticmethod
    def _pages_per_seq(config: PagedEngineConfig) -> int:
        return -(-config.max_len // config.page_size)

    def _refuse(self):
        """A kind's hook: what it cannot be built with (a tensor mesh, page
        sizes and buckets), before anything is allocated."""
        cfg = self.config.model
        if self._tp > 1 and self._no_mesh is not None:
            raise NotImplementedError(self._no_mesh)
        if self._tp > 1 and (cfg.num_kv_heads % self._tp
                             or cfg.num_heads % self._tp):
            raise ValueError(
                f"num_heads={cfg.num_heads}/num_kv_heads="
                f"{cfg.num_kv_heads} not divisible by tensor axis "
                f"size {self._tp}")

    def _pool_shape(self) -> Tuple[int, ...]:
        """A kind's hook: one page pool, in the kernel's layout."""
        cfg, config = self.config.model, self.config
        return (cfg.num_kv_heads, config.num_pages, config.page_size,
                cfg.head_dim_)

    def _kernel(self, reference: bool) -> str:
        """A kind's hook: the attention path its decode program is built
        with here (`stats()` names it)."""
        hd = self.config.model.head_dim_
        return paged_kernel(hd, reference, hd)

    def _zero_pools(self, shape) -> List[Any]:
        """A zeroed pool of `shape` per layer that attends."""
        cfg = self.config.model

        def zero():
            z = jnp.zeros(shape, cfg.dtype)
            if self._page_sharding is not None:
                z = jax.device_put(z, self._page_sharding)
            return z
        return [zero() for attends, _, _ in _kinds.layer_caches(cfg)
                if attends]

    def _init_pools(self):
        """A kind's hook: its page pools."""
        self.k_pages = self._zero_pools(self._pool_shape())
        self.v_pages = self._zero_pools(self._pool_shape())

    def _init_cache(self):
        """A kind's hook: everything a row keeps, and the kind's own
        counters."""
        self._paged_kernel = self._kernel(
            self.config.model.attention_impl == "reference")
        self._init_pools()

    def _report_shape(self) -> Tuple[int, ...]:
        """A kind's hook: the shape of `_tokens`, what a step returns to
        the host."""
        return (self.config.max_batch,)

    def _kind_programs(self):
        """A kind's hook: its own programs over the dense ones, under the
        same names. Needs `config` and `model` alone."""

    @property
    def _row_pools(self):
        """A kind's hook: what its programs take donated and hand back
        where they take the row's pools as one argument."""
        return self.k_pages

    @_row_pools.setter
    def _row_pools(self, pools):
        self.k_pages = pools

    # -- lowering, from the arguments the tick builds ----------------------

    def lower_chunk(self, bucket: Optional[int] = None):
        """The prefill chunk lowered at this engine's shapes (the largest
        bucket's unless told), from shapes alone, in the form the tick runs
        (`last` given where the kind's chunk takes it): its arguments are
        `_chunk_args`' of a row that holds nothing."""
        bucket = bucket or self.config.prefill_buckets[-1]
        like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        seq = _Seq(dense_caches=jax.eval_shape(self._dense_zero_caches))
        staged, extra = self._chunk_args(seq, bucket, bucket)
        last = (i32(),) if self._samples_prompt else ()
        with self._mesh_scope():
            return self._chunk_prefill.lower(*jax.tree_util.tree_map(
                like, (self.params, i32(1, bucket), i32(1, bucket), staged,
                       i32(), *extra, *last)))

    def _row_shapes(self, vec):
        """A kind's hook: the shapes of what a step is told about its rows
        (`rows` of `_step_args`); `vec(dtype, *shape)` is one a row."""
        return (vec(jnp.int32, self.config.pages_per_seq), vec(jnp.int32),
                vec(jnp.int32),
                jax.ShapeDtypeStruct(self._rng.shape, self._rng.dtype),
                vec(jnp.float32), vec(jnp.int32), vec(jnp.float32))

    def lower_decode(self):
        """The decode step lowered at this engine's shapes, from shapes
        alone (`_step_args` of them): the live page pools are neither read
        nor donated."""
        B = self.config.max_batch

        def like(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)

        def vec(dtype, *shape):
            return jax.ShapeDtypeStruct((B,) + shape, dtype)

        live = (vec(jnp.bool_),) if self._takes_live else ()
        with self._mesh_scope():
            return self._decode.lower(*jax.tree_util.tree_map(
                like, self._step_args(live, self._row_shapes(vec))))

    def decode_program_text(self) -> str:
        """Compiled text of the decode step: the one-shot probe of what
        the backend was really handed (is the Pallas paged-attention
        `tpu_custom_call` in it, or the gather path? is a page pool
        relaid out?). With a persistent compile cache the compile is a
        hit."""
        return self.lower_decode().compile().as_text()

    def pool_copies(self, compiled_text: str) -> int:
        """Whole-pool copies (`pool_copies`) at this engine's pool shape
        as one device holds it (and at the index-key pool's, for a model
        that keeps one). The decode step must hold none."""
        return sum(pool_copies(compiled_text,
                               pool.sharding.shard_shape(pool.shape))
                   for pool in self.k_pages[:1] + self.index_pages[:1])

    def state_copies(self, compiled_text: str) -> int:
        """Whole-pool copies of recurrent state in a compiled program: 0
        here; the kind that keeps state beside its pages counts them."""
        return 0

    def _mesh_scope(self):
        """Context for jit calls: marks the serving mesh active so the
        model's attention detects the tensor axis at trace time
        (shard_map over the Pallas/gather kernel)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from ..parallel.mesh import kernel_mesh
        return kernel_mesh(self.mesh)

    # -- submission / cancel ---------------------------------------------

    def submit(self, request: GenerationRequest,
               done_callback: Optional[Callable] = None,
               token_callback: Optional[Callable] = None):
        n = len(request.prompt_tokens)
        if n >= self.config.max_len:
            raise ValueError("prompt longer than max_len")
        self._check_request(request)
        request._done_callback = done_callback  # type: ignore
        request._token_callback = token_callback  # type: ignore
        request._submit_ts = time.monotonic()  # type: ignore
        reqtrace.record(request.request_id, reqtrace.QUEUED,
                        engine="paged", prompt_tokens=n,
                        max_new=request.max_new_tokens,
                        prefilled=hasattr(request, "_prefilled") or None,
                        tenant=getattr(request, "tenant", None),
                        route=getattr(request, "route", None))
        self._pending.put(request)
        llm_metrics().queue_depth.set(self._pending.qsize(),
                                      tags=_GAUGE_TAGS)

    def submit_prefilled(self, request: GenerationRequest, dense_caches,
                         last_logits,
                         done_callback: Optional[Callable] = None,
                         token_callback: Optional[Callable] = None):
        """Submit a request whose prefill ran on ANOTHER engine
        (prefill/decode disaggregation): `dense_caches` are per-layer
        (k, v) arrays trimmed to the prompt's pages, `last_logits` the
        prompt's final-position logits. Admission (page budget, prefix
        sharing) happens on the normal scheduler tick, which installs
        them where a local prefill would have finished its last chunk."""
        self._refuse_shipped("submit_prefilled")
        request._prefilled = (dense_caches, last_logits)  # type: ignore
        self.submit(request, done_callback, token_callback)

    def _check_request(self, request: GenerationRequest):
        """A kind's hook: what it refuses of a request (raises)."""

    def _refuse_shipped(self, what: str):
        """`what` ships dense K/V and a first token's logits from one engine
        to another: a kind whose prefill leaves anything else says why it
        cannot (`_not_shipped`)."""
        if self._not_shipped is not None:
            raise NotImplementedError(self._not_shipped.format(what=what))

    def cancel(self, request_id: str) -> bool:
        """Abort a request: frees its slot+pages on the next tick if
        running, or drops it from the queue."""
        seq = self._by_id.get(request_id)
        if seq is not None and seq.request is not None:
            seq.cancelled = True
            return True
        # queued: rebuild the queue without it
        kept = []
        dropped = None
        try:
            while True:
                request = self._pending.get_nowait()
                if request.request_id == request_id and dropped is None:
                    dropped = request
                    continue
                kept.append(request)
        except queue.Empty:
            pass
        for request in kept:
            self._pending.put(request)
        if dropped is None:
            # drained arrivals and preemption-parked requests
            for request in list(self._parked):
                if request.request_id == request_id:
                    try:
                        self._parked.remove(request)
                    except ValueError:
                        break  # admitted concurrently
                    dropped = request
                    break
        if dropped is not None:
            # queued cancellations must still resolve their waiters
            self._end_request(dropped, None, where="queued")
        return dropped is not None

    def has_work(self) -> bool:
        return (not self._pending.empty()) or bool(self._parked) or \
            any(s.request is not None for s in self.seqs)

    def fail_all(self, error: Exception):
        """Resolve every active and queued request with `error` (the
        serving drive loop calls this when step() raises — callers must
        see the failure, not hang on a silently-spinning engine)."""
        for i, seq in enumerate(self.seqs):
            if seq.request is not None:
                self._end_request(seq.request, error, index=i)
        self._unread.clear()
        self._drain_pending()
        while self._parked:
            self._end_request(self._parked.popleft(), error)

    def _end_request(self, request: GenerationRequest, result,
                     index: Optional[int] = None,
                     where: Optional[str] = None):
        """The one way a request leaves the engine. `result` is what its
        waiter receives and names the outcome: the tokens (done), None
        (cancelled, `where` it was) or the exception (error). `index` is
        the slot it holds, if any: pages released, slot reset.

        A row that ends with a decode step still in flight (its EOS or
        its cancellation was seen a tick late) was computed once more:
        that token is dropped here, and the step's stale write to the
        released pages (and state row) lands before any new owner's,
        because the device runs what it is handed in order and every
        write of a new owner is dispatched after this release."""
        if index is not None:
            seq = self.seqs[index]
            self._discarded_tokens += seq.dispatched - len(seq.generated)
            self._release(seq)
            self.seqs[index] = _Seq()
        metrics = llm_metrics()
        if result is None:
            outcome = "cancelled"
            reqtrace.record(request.request_id, reqtrace.CANCELLED,
                            where=where)
        elif isinstance(result, Exception):
            outcome = "error"
            reqtrace.record(request.request_id, reqtrace.FAILED,
                            error=type(result).__name__)
        else:
            outcome = "done"
            reqtrace.record(request.request_id, reqtrace.FINISHED,
                            tokens=len(result))
            submit_ts = getattr(request, "_submit_ts", None)
            if submit_ts is not None:
                metrics.request_latency.observe(
                    time.monotonic() - submit_ts, tags=_TAGS)
        metrics.requests_finished.inc(tags=dict(_TAGS, outcome=outcome))
        callback = getattr(request, "_done_callback", None)
        if callback is not None:
            callback(request, result)

    # -- scheduler tick ----------------------------------------------------

    def step(self) -> List[Tuple[GenerationRequest, Any]]:  # rtpu: hot-loop
        """One visit of the host to the continuous-batching tick, one
        decode step ahead of it. In order: grow pages for, stage and
        dispatch decode step n+1 for the rows known to decode again
        (`_decode_tick`); only then read step n's tokens, emit them and
        release the rows they finish; reap cancellations, fill freed
        slots from the waiting queue (radix prefix match, tail-only
        prefill setup), advance bounded chunked prefill and move finished
        prompts to the decode phase. Every host phase after the dispatch
        runs while the device computes step n+1. A row that finishes its
        prompt joins the next visit's dispatch: its first token is sampled
        on the device into the token vector (`first_token`) and read with
        that visit's tokens.

        What staging step n+1 needs of step n is deterministic (`length +
        1`, the page the token lands in, the finish rules that do not
        look at the token: `_exhausted`). What is not — EOS, a
        cancellation that lands after the dispatch — is seen one tick
        late, and the row's extra token dropped (`_end_request`). Whatever
        needs a token's value on the host first reads the step in flight
        with nothing behind it (`_drain`): page-pressure preemption, and
        a visit with no row left to dispatch. An engine without work has
        nothing unread.

        The accel plane's `tick` row splits the visit by phase (README,
        "Tick phases"): grow / stage / dispatch / wait / emit / reap /
        admit / prefill / state / gauges tile it; `wait` is the fetch of
        the step dispatched one visit earlier; `between` is the time
        since the last visit's end while work was waiting — the serving
        loop's executor hop and whatever else held this thread. Its
        counters say how often the step ahead was there, and how many
        rows and prefill chunks the visit dispatched; the row also has
        the visits' distribution and the slow ones whole (`extent_hist`,
        `slow`: a visit's extent is `between` + the visit)."""
        if self._counters_asked:
            self.read_counters()
        entered = time.perf_counter()
        tick = _accel.StepTimer("tick", sink=self._tick_accum,
                                watch=self._dry)
        if self._tick_end is not None:
            tick.outside("between", entered - self._tick_end)
        before = self._ahead_counts()
        with tick:
            self._decode_tick(tick.phase)
            with tick.phase("reap"):
                self._reap_cancelled()
            with tick.phase("admit"):
                self._admit()
            with tick.phase("prefill"):
                self._prefill_tick(tick.part)
            self._after_prefill(tick.phase)
            self._steps += 1
            if self._steps % _GAUGES_EVERY == 0:
                with tick.phase("gauges"):
                    self._set_gauges()
            for name, count in self._ahead_counts().items():
                tick.count(name, count - before[name])
        if self.has_work():
            self._tick_end = time.perf_counter()
        else:
            self._tick_end = None
            if self._dry is not None:
                self._dry.idle()
            # drained: flush the partial windows so step telemetry
            # never lags an idle engine by up to `every` ticks
            self._flush_step_rows()
            self.read_counters()
        finished, self._finished = self._finished, []
        return finished

    def read_counters(self):
        """Publish the model's accumulators to stats(): a fetch from the
        device that waits for the step in flight. For the stepping thread
        between steps only (the arrays are donated to every decode step,
        and a reader on another thread would find them deleted): `step`
        calls it when the engine drains and, once, after a stats() call
        found the host copy older than the last step; a caller that runs
        between steps (the benchmark's marks) may call it itself."""
        if self.counters:
            with _accel.pause("read_counters"):
                counters, of_chunks = jax.device_get(  # host-sync ok:
                    (self.counters, self.chunk_counters))   # on request
            self._counters_host = counters
            self._chunk_counters_host = of_chunks
        self._counters_at, self._counters_asked = self._steps, False

    def _ahead_counts(self) -> Dict[str, int]:
        """How the step ahead fared so far: decode steps dispatched
        before the last one's tokens were read, reads with nothing
        dispatched behind them, tokens dropped a tick late; and what the
        visits dispatched: decode rows, the steps and the kept arrays sent
        for them (`stage_steps`, `stage_uploads`), prefill chunks, those of
        them that ran the head, the prompts they finished, and the decode
        steps by the sampler's branch; for a model with routed experts, the
        chunks whose bucket put them on the sorted form
        (`prefill_chunks_sorted`)."""
        counts = {"lookahead_ticks": self._lookahead_ticks,
                  "drained_ticks": sum(self._drained_ticks.values()),
                  "discarded_tokens": self._discarded_tokens,
                  "decode_rows": self._decode_rows,
                  "stage_steps": self._stage.steps,
                  "stage_uploads": self._stage.uploads,
                  **{f"sampler_{tier}_steps": steps for tier, steps
                     in zip(SAMPLER_TIERS, self._sampler_steps)},
                  "prefill_chunks": self._prefill_chunks,
                  "prefill_heads": self._prefill_heads,
                  "prompts_finished": self._prompts_finished}
        if self._sorted_buckets is not None:
            # chunks whose routed experts ran as sorted pairs
            counts["prefill_chunks_sorted"] = self._prefill_chunks_sorted
        counts.update(self._kind_counts())
        return counts

    def _kind_counts(self) -> Dict[str, int]:
        """A kind's hook: its own counters, as `_ahead_counts` (and so the
        `tick` row and `stats()`) reports them."""
        return {}

    def _dispatching(self):
        """Just before this engine hands the device a program."""
        if self._dry is not None:
            self._dry.dispatching()

    def _dispatched(self, out):
        """Just after: `out`, an array of the program's outputs that
        nothing donates before the next dispatch."""
        if self._dry is not None:
            self._dry.dispatched(out)

    def _flush_step_rows(self):
        """When the engine drains and in `stats()`: the accumulators hand
        their partial window to the accel plane, and the gauges are set
        (`step` sets them every `_GAUGES_EVERY` visits besides)."""
        for accum in (self._step_accum, self._tick_accum):
            if accum is not None:
                accum.flush()
        self._set_gauges()

    def _waiting_count(self) -> int:
        return self._pending.qsize() + len(self._parked)

    def _set_gauges(self):
        metrics = llm_metrics()
        waiting = self._waiting_count()
        metrics.queue_depth.set(waiting, tags=_GAUGE_TAGS)
        metrics.running.set(
            sum(1 for s in self.seqs if s.request is not None),
            tags=_GAUGE_TAGS)
        free = self.pool.num_free()
        metrics.kv_utilization.set(
            1.0 - free / max(1, self.config.num_pages), tags=_GAUGE_TAGS)
        metrics.kv_occupancy.set(self.config.num_pages - 1 - free,
                                 tags=_GAUGE_TAGS)
        metrics.waiting.set(waiting, tags=_GAUGE_TAGS)
        metrics.shared_pages.set(self.radix.shared_pages(),
                                 tags=_GAUGE_TAGS)

    def _reap_cancelled(self):
        """Release cancelled sequences in ANY phase (a mid-prefill
        cancel must return its pages too) before admission reuses the
        slots."""
        for i, seq in enumerate(self.seqs):
            if seq.request is not None and seq.cancelled:
                self._end_request(seq.request, None, index=i,
                                  where=seq.phase)

    def _drain_pending(self):
        try:
            while True:
                self._parked.append(self._pending.get_nowait())
        except queue.Empty:
            pass

    # -- park bookkeeping (request observatory + park histogram) ---------

    def _compile_total(self) -> float:
        """Disjoint backend-compile seconds so far (the PR-7 tracker);
        0 when the accel plane is killed — compile attribution then
        degrades to zero, it never invents time."""
        return (self._accel.backend_compile_seconds_total()
                if self._accel is not None else 0.0)

    def _park_note(self, request: GenerationRequest, reason: str):
        """Open a park episode ONCE (admission retries every tick while
        pages are short — one PARKED event and one histogram sample per
        episode, not per retry)."""
        if getattr(request, "_rt_park_ts", None) is None:
            request._rt_park_ts = time.monotonic()  # type: ignore
            request._rt_park_reason = reason  # type: ignore
            reqtrace.record(request.request_id, reqtrace.PARKED,
                            reason=reason)

    def _unpark_note(self, request: GenerationRequest) -> float:
        """Close a park episode at (re-)admission: observe the park
        histogram by reason, accumulate per-request park seconds (the
        why_slow park bucket's metric twin), and stamp RESUMED for
        preempted requests. Returns total park seconds so far."""
        park_ts = getattr(request, "_rt_park_ts", None)
        if park_ts is not None:
            parked = time.monotonic() - park_ts
            reason = getattr(request, "_rt_park_reason", "unknown")
            llm_metrics().park_seconds.observe(
                parked, tags=dict(_TAGS, reason=reason))
            request._rt_park_total = parked + \
                getattr(request, "_rt_park_total", 0.0)  # type: ignore
            request._rt_park_ts = None  # type: ignore
            if getattr(request, "_resume_tokens", None):
                reqtrace.record(request.request_id, reqtrace.RESUMED,
                                reason=reason,
                                parked_s=round(parked, 6))
        return getattr(request, "_rt_park_total", 0.0)

    def _admit(self):
        self._drain_pending()
        for index, seq in enumerate(self.seqs):
            if seq.request is not None:
                continue
            if not self._parked:
                return
            request = self._parked.popleft()
            try:
                if not self._begin_prefill(index, request):
                    self._park_note(request, "no_pages")
                    self._parked.appendleft(request)
                    return
                shipped = getattr(request, "_prefilled", None)
                if shipped is None:
                    self._stage_prefill_cache(seq)
                else:
                    # prefilled elsewhere (`submit_prefilled`): enters
                    # where a local prefill would have finished its last
                    # chunk; after a preemption it re-prefills locally
                    del request._prefilled
                    caches, last_logits = shipped
                    seq.last_logits = jnp.asarray(
                        last_logits, jnp.float32)[None, :]
                    seq.dense_caches = [(jnp.asarray(k), jnp.asarray(v))
                                        for (k, v) in caches]
                    seq.prefill_off = len(seq.prompt)
                    self._finish_prefill(index)
            except Exception as e:  # noqa: BLE001
                held = self.seqs[index].request is request
                self._end_request(request, e, index if held else None)

    def _begin_prefill(self, index: int,
                       request: GenerationRequest) -> bool:
        """Admit a request into the prefill phase: radix-match the
        longest cached prefix (mapped copy-on-write into the block
        table) and allocate only the tail prompt pages. Returns False
        when pages are short even after pressure eviction (caller
        re-parks the request)."""
        ps = self.config.page_size
        resume = list(getattr(request, "_resume_tokens", []))
        prompt = list(request.prompt_tokens) + resume
        shared = self._match_prefix(prompt)
        n_prompt_pages = -(-len(prompt) // ps)
        tail_pages = self._pages_to_admit(
            prompt, n_prompt_pages - len(shared))
        if tail_pages is None:
            return False
        if self.pool.num_free() < tail_pages:
            self._radix_evictions += self.radix.evict_pages(
                tail_pages - self.pool.num_free())
            if self.pool.num_free() < tail_pages:
                for page in shared:
                    self.pool.decref(page)
                return False
        new_ids = []
        for _ in range(tail_pages):
            page = self.pool.alloc()
            assert page is not None, "budget checked above"
            new_ids.append(page)
        seq = self.seqs[index]
        seq.request = request
        seq.prompt = prompt
        seq.resume = resume
        seq.phase = "prefill"
        seq.pages = shared + new_ids
        seq.own_from = len(shared)
        seq.length = 0
        seq.generated = []
        seq.dispatched = 0
        seq.cancelled = False
        seq.prefill_off = len(shared) * ps
        self._prefix_shared_tokens += len(shared) * ps
        seq.dense_caches = None
        seq.last_logits = None
        self._admit_clock += 1
        seq.admit_at = self._admit_clock
        self._by_id[request.request_id] = seq
        self._unpark_note(request)
        reqtrace.record(request.request_id, reqtrace.ADMITTED,
                        shared_pages=len(shared),
                        tail_pages=tail_pages,
                        resume_tokens=len(resume) or None)
        return True

    def _pages_to_admit(self, prompt: List[int],
                        tail_pages: int) -> Optional[int]:
        """A kind's hook: the pages admission allocates a prompt now, of
        the `tail_pages` the radix did not give it; None where the kind's
        own budget is not free."""
        return tail_pages

    def _stage_prefill_cache(self, seq: _Seq):
        """A kind's hook: what a row about to prefill stages. Here the
        dense chunk cache, with its shared span gathered in so the tail
        attends over it without recomputing; nothing for a kind whose
        chunks write the row's pages."""
        with self._mesh_scope():
            self._dispatching()
            dense = self._dense_zero_caches()
            if seq.own_from:
                pad = np.zeros(self.config.pages_per_seq, np.int32)
                pad[:seq.own_from] = seq.pages[:seq.own_from]
                dense = self._gather_pages(self.k_pages, self.v_pages,
                                           dense, jnp.asarray(pad))
            self._dispatched(jax.tree_util.tree_leaves(dense)[0])
        seq.dense_caches = dense

    def _prefill_tick(self, part=_no_phase):
        """Advance at most `prefill_decode_ratio` prefill chunks,
        round-robin across prefilling sequences in admission order, so
        a long prompt never stalls the decode batch for more than one
        bounded chunk per tick. `part` (`StepTimer.part`) times the two
        halves of the `prefill` phase apart, in the visits that run them:
        a chunk's staging and dispatch (`prefill_chunk_s`), and what a
        finished prompt costs the stepping thread (`prefill_finish_s`:
        the page write, the radix insert, `first_token`)."""
        budget = max(1, self.config.prefill_decode_ratio)
        order = sorted(
            (i for i, s in enumerate(self.seqs)
             if s.request is not None and s.phase == "prefill"
             and not s.cancelled),
            key=lambda i: self.seqs[i].admit_at)
        if not any(s.request is not None and s.phase == "decode"
                   for s in self.seqs):
            # nothing decoding → no decode latency to protect; drain
            # the prefill backlog at full speed (cold-start ramp)
            budget = max(budget, len(order))
        while budget > 0 and order:
            i = order.pop(0)
            seq = self.seqs[i]
            if not self._samples_prompt \
                    and seq.prefill_off >= len(seq.prompt):
                # nothing is left to compute and nothing is sampled from a
                # prompt (every other kind runs a chunk even of no token:
                # its first token comes from the chunk's logits)
                with part("prefill", "finish"):
                    self._finish_prefill(i)
                self._prompts_finished += 1
                continue
            if not self._chunk_pages(i, seq):
                budget -= 1
                continue     # parked again: the pool is short
            with part("prefill", "chunk"):
                self._prefill_heads += self._prefill_chunk(seq)
            self._prefill_chunks += 1
            budget -= 1
            if seq.prefill_off >= len(seq.prompt):
                with part("prefill", "finish"):
                    self._finish_prefill(i)
                self._prompts_finished += 1
            else:
                order.append(i)

    def _after_prefill(self, phase):
        """A kind's hook: what the prompts that finished this visit left
        for it to do behind the prefill phase (`phase`: the tick's)."""

    def _chunk_size(self, seq: _Seq) -> Tuple[int, int]:
        """(bucket, real tokens) of `seq`'s next prefill chunk."""
        rem = len(seq.prompt) - seq.prefill_off
        chunk = self._bucket(min(rem, self.config.prefill_buckets[-1]))
        return chunk, min(rem, chunk)

    def _chunk_pages(self, index: int, seq: _Seq) -> bool:
        """A kind's hook: the pages `seq`'s next chunk needs that admission
        did not allocate; False where the pool is short and the row went
        back to the queue."""
        return True

    def _row_table(self, seq: _Seq):
        """One row's block table: its pages, then the null page."""
        table = np.zeros((self.config.pages_per_seq,), np.int32)
        table[:len(seq.pages)] = seq.pages
        return table

    def _prefill_chunk(self, seq: _Seq) -> bool:
        """One bucket-rounded chunk of `seq`'s remaining prompt into its
        dense cache — one compiled shape per bucket, whatever the
        prompt's length. Returns whether the chunk ran the head: only the
        chunk that holds the prompt's final token does, on that row, and
        leaves its logits in `seq.last_logits`."""
        cfg = self.config
        prompt = seq.prompt
        off = seq.prefill_off
        chunk, take = self._chunk_size(seq)
        if self._sorted_buckets and chunk in self._sorted_buckets:
            self._prefill_chunks_sorted += 1
        tokens = np.zeros((1, chunk), np.int32)
        tokens[0, :take] = prompt[off:off + take]
        positions = np.minimum(
            np.arange(off, off + chunk, dtype=np.int32),
            cfg.model.max_seq_len - 1)[None, :]
        trace = not reqtrace.reqtrace_disabled()
        if trace:
            chunk_t0 = time.monotonic()
            compile_t0 = self._compile_total()
        # the row the first token is sampled from, if this chunk holds it:
        # the program applies the head to that row and to nothing else
        finishes = self._samples_prompt and off + take == len(prompt)
        last = (jnp.asarray(take - 1 if finishes else -1, jnp.int32),) \
            if self._samples_prompt else ()
        staged, extra = self._chunk_args(seq, chunk, take)
        with self._mesh_scope():
            self._dispatching()
            logits, staged = self._chunk_prefill(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                staged, jnp.asarray(off, jnp.int32), *extra, *last)
            self._dispatched(logits)
        self._chunk_done(seq, staged, chunk, take)
        if finishes:
            # stays on the device: `first_token` samples from it there
            seq.last_logits = logits
        seq.prefill_off = off + take
        if trace and seq.request is not None:
            # no chunk waits for the device: dur_s is the launch alone
            reqtrace.record(
                seq.request.request_id, reqtrace.PREFILL_CHUNK,
                tokens=take, bucket=chunk,
                valid=take if self._pads_told else None,
                dur_s=round(time.monotonic() - chunk_t0, 6),
                compile_s=round(
                    self._compile_total() - compile_t0, 6) or None)
        # counts COMPUTED tokens only — a radix-shared span costs zero
        llm_metrics().prefill_tokens.inc(take, tags=_TAGS)
        self._prefill_computed_tokens += take
        return finishes

    def _chunk_args(self, seq: _Seq, chunk: int, take: int):
        """A kind's hook: (what `seq`'s next chunk of `chunk` positions,
        `take` of them real, takes donated; its arguments behind `offset`
        and in front of `last`). Builds and counts nothing else:
        `lower_chunk` calls it too."""
        return seq.dense_caches, ()

    def _chunk_done(self, seq: _Seq, staged, chunk: int, take: int):
        """A kind's hook: store what the chunk handed back, count what the
        kind counts, and what follows the chunk (`seq.prefill_off` is
        still the chunk's offset)."""
        seq.dense_caches = staged

    def _write_owned_pages(self, dense_caches, write_ids, start_page):
        """Commit owned prompt pages from a dense prefill cache to the
        pools. The id list is padded to pages_per_seq with the null
        page so `_write_pages` keeps one compiled shape per dense-cache
        length instead of one per tail size."""
        cfg = self.config
        ids = list(write_ids) + [0] * (cfg.pages_per_seq
                                       - len(write_ids))
        with self._mesh_scope():
            self._dispatching()
            self.k_pages, self.v_pages = self._write_pages(
                self.k_pages, self.v_pages, dense_caches,
                jnp.asarray(ids, jnp.int32),
                jnp.asarray(start_page * cfg.page_size, jnp.int32))
            self._dispatched(self.k_pages[0])

    def _finish_prefill(self, index: int):
        """Prompt fully cached: write the owned tail pages, commit full
        pages to the radix, sample the first token from the one row of
        logits the last chunk returned (`seq.last_logits`) into the token
        vector ON THE DEVICE, and move the sequence to the decode phase.
        Nothing here waits for the device: the host
        work (the radix insert above all) runs under the decode step in
        flight, and the token is read with the next visit's."""
        seq = self.seqs[index]
        self._commit_prompt(index, seq)
        seq.dense_caches = None
        self._register_prefix(seq.prompt, seq.pages)
        self._enter_decode(index, seq)

    def _commit_prompt(self, index: int, seq: _Seq):
        """A kind's hook: what a prompt's staged prefill leaves behind.
        Here its owned tail pages are written from the dense cache; a kind
        whose chunks wrote the row's pages writes none, one with state
        leaves it due (`_after_prefill`)."""
        write_ids = seq.pages[seq.own_from:]
        if write_ids:
            self._write_owned_pages(seq.dense_caches, write_ids,
                                    seq.own_from)

    def _enter_decode(self, index: int, seq: _Seq):
        """A kind's hook: the row's first step. Here the first token is
        sampled from `seq.last_logits` into the token vector."""
        request = seq.request
        temp, top_k, top_p = self._sampling(request)
        key = self._rng
        if temp > 0:
            self._rng, key = jax.random.split(self._rng)
        self._dispatching()
        self._tokens = first_token(
            self._tokens, seq.last_logits, np.int32(index), key,
            np.full((1,), temp, np.float32), np.full((1,), top_k, np.int32),
            np.full((1,), top_p, np.float32), sampled=temp > 0)
        self._dispatched(self._tokens)
        seq.last_logits = None
        self._tokens.copy_to_host_async()
        self._unread.append((index, seq))
        seq.phase = "decode"
        seq.length = len(seq.prompt)
        seq.generated = []
        seq.dispatched = 1

    def _exhausted(self, seq: _Seq) -> bool:
        """Whether the newest token computed or in flight for `seq` is
        its last by the rules that do not look at it: the request's
        budget (tokens from before a preemption count) or the engine's
        length cap. Such a row is in no further decode step. (A kind's
        hook: one whose step is not one token has its own rule.)"""
        return len(seq.resume) + seq.dispatched \
            >= seq.request.max_new_tokens \
            or seq.length >= self.config.max_len - 1

    def _finished_after(self, seq: _Seq, token: int) -> bool:
        """Whether `token`, just emitted, was `seq`'s last: EOS, or the
        last of an exhausted row."""
        eos = self.config.eos_token
        return (eos is not None and token == eos) \
            or (seq.dispatched == len(seq.generated)
                and self._exhausted(seq))

    def _finish(self, index: int):
        seq = self.seqs[index]
        request = seq.request
        tokens = seq.resume + seq.generated
        self._end_request(request, tokens, index=index)
        self._finished.append((request, tokens))

    def _alloc_page(self) -> Optional[int]:
        """Allocate with radix pressure relief: cold unshared prefix
        pages are reclaimed before giving up."""
        page = self.pool.alloc()
        if page is None and self.radix.evict_pages(1):
            self._radix_evictions += 1
            page = self.pool.alloc()
        return page

    def _ensure_decode_pages(self, active: List[int]) -> List[int]:
        """Lazy page growth before the decode tick: every decoding
        sequence needs the page its next token writes into. Under pool
        exhaustion the YOUNGEST sequence is preempted (pages released,
        request parked at the queue front with its generated tokens as
        a prompt extension) until the rest fit — the continuous-batching
        answer to OOM. A preemption needs every token of its row on the
        host, so the step in flight is read first (`_drain`), which may
        itself end rows and free the pages that were short."""
        ps = self.config.page_size
        at = self._lands_at
        rows = {i: self.seqs[i] for i in active}
        for n, i in enumerate(
                sorted(active, key=lambda i: self.seqs[i].admit_at)):
            if not n & 7:
                self._poll()
            seq = rows[i]
            while self.seqs[i] is seq \
                    and at(seq.length) // ps >= len(seq.pages):
                page = self._alloc_page()
                if page is not None:
                    seq.pages.append(page)
                    continue
                if self._unread:
                    self._drain("preempt")
                    continue
                victim = max((j for j in rows if self.seqs[j] is rows[j]),
                             key=lambda j: rows[j].admit_at)
                self._preempt(victim, reason="page_pressure")
        return [i for i in active if self.seqs[i] is rows[i]]

    def _lands_at(self, length: int) -> int:
        """A kind's hook: the row of its pages the next step of a row of
        `length` writes last: its length, unless the kind's rows keep
        something else than their context or a step carries more than one
        position."""
        return length

    def _preempt(self, index: int, reason: str):
        self._drain("preempt")
        seq = self.seqs[index]
        request = seq.request
        if request is None:
            return   # ended by a token the drain read
        # generated-so-far becomes a prompt extension; re-admission
        # radix-matches the already-registered prompt pages, so only
        # the generated span (plus the partial page) re-prefills (all of
        # it for a model with recurrent state, which registers none)
        request._resume_tokens = seq.resume + list(seq.generated)
        self._release(seq)
        self.seqs[index] = _Seq()
        reqtrace.record(request.request_id, reqtrace.PREEMPTED,
                        reason=reason,
                        generated=len(request._resume_tokens))
        self._park_note(request, reason)
        self._parked.appendleft(request)
        self._preemptions += 1
        llm_metrics().preemptions.inc(tags=dict(_TAGS, reason=reason))

    def _bucket(self, n: int) -> int:
        for b in self.config.prefill_buckets:
            if n <= b:
                return b
        return self.config.prefill_buckets[-1]

    def prefill_only(self, prompt: List[int]):
        """Run chunked prefill WITHOUT admitting a sequence: returns
        (last_token_logits, per-layer dense (k, v) numpy pairs) trimmed to
        whole pages. This is the prefill half of prefill/decode
        disaggregation (reference:
        llm/_internal/serve/deployments/prefill_decode_disagg/) — the KV
        ships to a decode engine's `submit_prefilled`."""
        self._refuse_shipped("prefill_only")
        seq = _Seq(prompt=list(prompt))
        self._stage_prefill_cache(seq)
        while seq.prefill_off < len(seq.prompt):
            self._prefill_chunk(seq)
        n_tok = -(-len(prompt) // self.config.page_size) * \
            self.config.page_size
        out = [(np.asarray(k[:, :, :n_tok]), np.asarray(v[:, :, :n_tok]))
               for (k, v) in seq.dense_caches]
        return np.asarray(seq.last_logits[0], np.float64), out

    def _match_prefix(self, prompt: List[int]) -> List[int]:
        """Longest cached full-page prefix of `prompt`: refcounted page
        ids the caller maps copy-on-write into its block table. (A kind's
        hook, with `_register_prefix`: one whose pages are not a prefix's
        whole K/V matches and registers nothing.)"""
        shared = self.radix.match(prompt)
        if shared:
            self._prefix_hits += 1
            llm_metrics().prefix_hits.inc(tags=_TAGS)
        elif len(prompt) >= self.config.page_size:
            self._prefix_misses += 1
            llm_metrics().prefix_misses.inc(tags=_TAGS)
        return shared

    def _register_prefix(self, prompt: List[int], pages: List[int]):
        """Commit the full prompt pages for reuse; the radix enforces
        the entry budget (`RTPU_PREFIX_CACHE_ENTRIES`)."""
        n_full = len(prompt) // self.config.page_size
        # re-read the flag so tests / live reconfig take effect
        self.radix.max_entries = int(CONFIG.prefix_cache_entries)
        if n_full:
            before = self.radix.entries
            added = self.radix.insert(prompt, pages[:n_full])
            self._radix_evictions += before + added - self.radix.entries
        llm_metrics().prefix_entries.set(self.radix.entries,
                                         tags=_GAUGE_TAGS)

    def _evict_prefixes(self, max_entries: int):
        self.radix.evict(max_entries)
        llm_metrics().prefix_entries.set(self.radix.entries,
                                         tags=_GAUGE_TAGS)

    def prefix_pinned_pages(self) -> set:
        """Distinct physical pages the radix holds a reference on."""
        return set(self.radix.pages())

    def page_leak_check(self) -> int:
        """Pool-balance audit: recompute every page's expected refcount
        from live sequences plus the prefix store and compare against
        the allocator. Returns the number of inconsistent pages (0 =
        balanced); call between steps — completions, cancels, preempts
        and evictions must all keep this at zero."""
        expected = np.zeros(self.config.num_pages, np.int64)
        expected[0] = 1  # the null pad page
        for seq in self.seqs:
            for page in seq.pages:
                expected[page] += 1
        for page in self.radix.pages():
            expected[page] += 1
        bad = int(np.sum(expected != self.pool.refs))
        # the free list must hold exactly the zero-ref pages
        if len(self.pool._free) != int(np.sum(self.pool.refs[1:] == 0)):
            bad += 1
        return bad

    def autoscaling_metrics(self) -> Dict[str, Any]:
        """Signals for the serve autoscaler's closed loop (the replica's
        get_metrics() forwards them to the controller): waiting work,
        recent median TTFT, and KV page occupancy."""
        ttfts = sorted(self._recent_ttfts)
        usable = max(1, self.config.num_pages - 1)
        out: Dict[str, Any] = {
            "queued": self._waiting_count(),
            "kv_occupancy": 1.0 - self.pool.num_free() / usable,
        }
        if ttfts:
            out["ttft_s"] = ttfts[len(ttfts) // 2]
        return out

    def _sampling(self, request: GenerationRequest):
        """(temperature, top_k, top_p) as the sampler takes them: 0
        disables the k filter, 1.0 the nucleus."""
        temp = request.temperature
        top_p = getattr(request, "top_p", None)
        return (temp if temp is not None else self.config.temperature,
                getattr(request, "top_k", None) or 0,
                top_p if top_p is not None else 1.0)

    def _drain(self, reason: str, phase=_no_phase):
        """Read the step in flight with nothing dispatched behind it: the
        exception path, taken where the host needs a token's value (or
        has no row to dispatch) before it can go on. Counted by reason."""
        if not self._unread:
            return
        self._drained_ticks[reason] = \
            self._drained_ticks.get(reason, 0) + 1
        unread, self._unread = self._unread, []
        with phase("wait"), _accel.pause("drain/" + reason):
            values = self._fetch(self._tokens)
            if self._dry is not None:
                self._dry.waited(self._tokens)
        with phase("emit"):
            self._emit_tokens(unread, values)

    def _fetch(self, tokens) -> List[int]:
        """The token vector on the host: the one wait for the device a
        visit has (for the step dispatched a visit earlier, and for the
        prefill chunks behind it whose first tokens the vector holds)."""
        # behind the next step's dispatch wherever a row decodes again
        return np.asarray(tokens).tolist()  # host-sync ok: the one fetch

    def _emit_tokens(self, unread: List[Tuple[int, _Seq]],
                     values: List[int]):
        """Hand the tokens just read to their rows, in slot order within
        a step and a finished prefill's after them; end the rows they
        finish. A row that ended since its entry was made is skipped (its
        token was counted as discarded when it was released), a row
        cancelled since is released here and its token dropped."""
        for slot, seq in unread:
            if self.seqs[slot] is not seq:
                continue
            if seq.cancelled:
                self._end_request(seq.request, None, index=slot,
                                  where="decode")
                continue
            token = values[slot]
            seq.generated.append(token)
            self._tokens_generated += 1
            if len(seq.generated) == 1:
                self._note_first_token(seq)
            callback = getattr(seq.request, "_token_callback", None)
            if callback is not None:
                callback(seq.request, token)
            if self._finished_after(seq, token):
                self._finish(slot)

    def _note_first_token(self, seq: _Seq):
        request = seq.request
        submit_ts = getattr(request, "_submit_ts", None)
        park_s = getattr(request, "_rt_park_total", 0.0)
        if submit_ts is not None and not seq.resume:
            ttft = time.monotonic() - submit_ts
            llm_metrics().ttft.observe(ttft, tags=_TAGS)
            self._recent_ttfts.append(ttft)
            # the DECODE stamp splits a parked request's TTFT: park_s
            # is the admission-blocked share, the rest is real prefill
            reqtrace.record(request.request_id, reqtrace.DECODE,
                            ttft_s=round(ttft, 6),
                            park_s=round(park_s, 6) or None)
        else:
            reqtrace.record(request.request_id, reqtrace.DECODE,
                            resumed=True,
                            park_s=round(park_s, 6) or None)

    def _release(self, seq: _Seq):
        for page in seq.pages:
            self.pool.decref(page)
        self._by_id.pop(seq.request.request_id, None)

    def _decode_tick(self, phase):  # rtpu: hot-loop
        """The decode half of a visit: dispatch the next step for every
        row that will decode again, THEN read the tokens of the step
        dispatched a visit earlier. `phase` is the tick's
        `StepTimer.phase`. What the step is told about its rows is kept
        between visits (`staging.StagedRows`): `sync` writes the rows that
        joined, left or took a page, the accounts of what the step attends
        are sums over the kept lengths and page counts, and of the arrays
        only `lengths` is sent every visit."""
        tick_start = time.monotonic()
        with phase("grow"):
            # a row whose token in flight is its last by rule, or that was
            # cancelled, is in no further step; lazy page growth for the
            # rest (+ preemption under pressure)
            active = self._ensure_decode_pages([
                i for i, s in enumerate(self.seqs)
                if s.request is not None and s.phase == "decode"
                and not s.cancelled and not self._exhausted(s)])
        if not active:
            self._drain("idle", phase)
            return
        with phase("stage"):
            trace = not reqtrace.reqtrace_disabled()
            if trace:
                # snapshot ids now: finished slots are reset before the
                # compile delta is attributed below
                trace_rids = [self.seqs[i].request.request_id
                              for i in active]
                compile_t0 = self._compile_total()
            stage, poll = self._stage, self._poll
            poll()
            stage.sync(active, self.seqs, self._sampling)
            poll()
            # what the step attends, by the kind's own account (sums over
            # the kept lengths and page counts)
            self._account_decode(stage, active)
            seqs = self.seqs
            for i in active:
                # this step's token, in flight from here on
                seq = seqs[i]
                seq.length += 1
                seq.dispatched += 1
            poll()
            self._decode_rows += len(active)
            self._sampler_steps[stage.tier] += 1
            poll()
            self._rng, key = jax.random.split(self._rng)
        accel = self._accel
        timer = accel.StepTimer(
            "decode", tokens=len(active),
            flops=2.0 * self._num_params * len(active),
            sink=self._step_accum) \
            if accel is not None else None
        with timer if timer is not None else contextlib.nullcontext():
            with self._mesh_scope():
                with (timer.device() if timer is not None
                      else contextlib.nullcontext()):
                    with phase("stage"):
                        def send(name):
                            poll()
                            return stage.send(name)
                        rows = (send("tables"), send("lengths"),
                                self._tokens, key, send("temps"),
                                send("top_ks"), send("top_ps"))
                        # the rows that decode, for the layers that count
                        # or scan
                        live = (send("live"),) if self._takes_live else ()
                    with phase("dispatch"):
                        unread, tokens = self._unread, self._tokens
                        self._dispatching()
                        self._step_taken(
                            self._decode(*self._step_args(live, rows)))
                        self._dispatched(self._tokens)
                        # the copy to the host starts when the step ends,
                        # whatever is queued behind it by then
                        self._tokens.copy_to_host_async()
                        stage.sent(advance=True)
                        self._unread = [(i, self.seqs[i]) for i in active]
                        # freed here, inside a phase, not after the last
                        del rows, live
                    self._after_dispatch(stage, active, phase)
                    if unread:
                        self._lookahead_ticks += 1
                        with phase("wait"):
                            values = self._fetch(tokens)
            if unread:
                with phase("emit"):
                    self._emit_tokens(unread, values)
            with phase("gauges"):
                if trace:
                    compile_s = self._compile_total() - compile_t0
                    if compile_s > 1e-6:
                        # every dispatched request's wall clock contained
                        # the stall — charge it to each (why_slow's
                        # compile bucket, subtracted from its decode
                        # span)
                        for rid in trace_rids:
                            reqtrace.record(
                                rid, reqtrace.COMPILE,
                                compile_s=round(compile_s, 6),
                                phase="decode")
                metrics = llm_metrics()
                metrics.token_latency.observe(
                    time.monotonic() - tick_start, tags=_TAGS)
                metrics.decode_tokens.inc(len(active), tags=_TAGS)

    def _account_decode(self, stage: StagedRows, active: List[int]):
        """A kind's hook: what it counts of the step about to be dispatched
        over the rows `active`, from `stage`'s arrays as the step before
        left them (`lengths`: the position each row's token goes to)."""

    def _step_args(self, live, rows):
        """A kind's hook: the arguments of `_decode`, from the rows' (`rows`:
        tables, lengths, the token vector, the key and the sampling triple;
        `live`: the rows that decode in a 1-tuple, () unless `_takes_live`)
        and what the kind keeps. Arrays or shapes alike: `lower_decode`
        calls it too."""
        return (self.params, self.k_pages, self.v_pages, *rows)

    def _step_taken(self, out):
        """A kind's hook: store what `_decode` handed back."""
        self._tokens, self.k_pages, self.v_pages = out

    def _after_dispatch(self, stage: StagedRows, active: List[int], phase):
        """A kind's hook: what it dispatches behind the step, before the
        tokens of the step before are read."""

    # -- conveniences ------------------------------------------------------

    def generate(self, prompts: List[List[int]],
                 max_new_tokens: int = 32,
                 timeout_s: float = 300.0) -> List[List[int]]:
        results: Dict[int, List[int]] = {}
        for i, prompt in enumerate(prompts):
            self.submit(GenerationRequest(
                prompt_tokens=prompt, max_new_tokens=max_new_tokens,
                request_id=str(i)))
        deadline = time.monotonic() + timeout_s
        while len(results) < len(prompts):
            if time.monotonic() > deadline:
                raise TimeoutError("generation timed out")
            for request, tokens in self.step():
                results[int(request.request_id)] = tokens
        return [results[i] for i in range(len(prompts))]

    def stats(self) -> Dict[str, Any]:
        """The engine's running sums and sizes. Beside the keys of every
        model, those of `_ahead_counts`, its kind's among them
        (`_kind_counts` in `kinds.py` says what each counts);
        `radix_evict_walks`: walks of the whole radix that eviction made, at
        most one a call that had to drop a node (`radix.py`); `stage_steps`
        / `stage_uploads`: steps dispatched and the kept arrays sent for
        them (`staging.StagedRows`; their quotient is the uploads a step, of
        the five arrays, or six, that a step takes)."""
        self._flush_step_rows()  # surfaces the partial window
        index_bytes = sum(int(np.prod(pool.shape)) * pool.dtype.itemsize
                          for pool in self.index_pages)
        cache_bytes = index_bytes + sum(
            int(np.prod(pool.shape)) * pool.dtype.itemsize
            for pool in self.k_pages + self.v_pages)
        kinds = _kinds.layer_caches(self.config.model)
        # the copy `read_counters` last published; where steps have run
        # since, the stepping thread is asked for a newer one and makes it
        # before its next step (nothing here touches the donated arrays)
        counters = self._counters_host
        self._counters_asked = bool(counters) \
            and self._counters_at != self._steps
        param_bytes = sum(
            int(np.prod(p.shape)) * p.dtype.itemsize
            for p in jax.tree_util.tree_leaves(self.params))
        return {
            "steps": self._steps,
            "tokens_generated": self._tokens_generated,
            "active": sum(1 for s in self.seqs if s.request is not None),
            "pending": self._waiting_count(),
            "free_pages": self.pool.num_free(),
            "prefix_entries": self.radix.entries,
            "prefix_hits": self._prefix_hits,
            "prefix_misses": self._prefix_misses,
            "preemptions": self._preemptions,
            # the step ahead (`_ahead_counts`; `drained_by`: why)
            **self._ahead_counts(),
            "drained_by": dict(self._drained_ticks),
            # the device out of work (`accel.DryWatch`): programs
            # dispatched, those that found it dry, the seconds it was
            # (`dry_s`, the mean of the two bounds) and under which phase
            "dry": dict(self._dry.totals,
                        by_phase=dict(self._dry.totals["by_phase"]))
            if self._dry is not None else {},
            "sampler": dict(zip(SAMPLER_TIERS, self._sampler_steps)),
            # recurrent state beside the pages (zeros for a model
            # without it; its kind's `stats` says the last two)
            "state_bytes": sum(
                a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(self.state)),
            "state_installs": 0,
            "prefix_skipped_recurrent": 0,
            # what each layer keeps: p(ages), s(tate), c(ounters); and,
            # per layer that counts, per expert held: tokens routed to it
            # and decode steps that routed it any, as of the last
            # `read_counters`
            "layer_kinds": ["".join(tag for tag, kept in zip("psc", kind)
                                    if kept) or "-" for kind in kinds],
            "expert_pairs": [pairs.tolist() for pairs, _ in counters],
            "expert_steps": [steps.tolist() for _, steps in counters],
            # the same of a blockwise model's prefill chunks of the
            # largest bucket (`prefill_chunks_largest` of them)
            "chunk_expert_pairs": [pairs.tolist() for pairs, _
                                   in self._chunk_counters_host],
            "chunk_expert_steps": [steps.tolist() for _, steps
                                   in self._chunk_counters_host],
            # pool-balance audit; exact only between steps
            "leaked_pages": self.page_leak_check(),
            # "pallas" or "gather": the path `decode_step` holds, of its
            # kind's kernel (`paged_kernel` / `latent_kernel` /
            # `sparse_kernel`: the key says which)
            self._kernel_key: self._paged_kernel,
            "index_cache_bytes": index_bytes,
            "tp": self._tp,
            "hbm_cache_bytes": cache_bytes,
            # per-chip residency: pages shard on kv_heads, params on
            # their logical axes — both divide by the tensor degree (the
            # fsdp/replicated leaves make this a ceiling for params)
            "hbm_cache_bytes_per_device": cache_bytes // self._tp,
            "hbm_param_bytes": param_bytes,
            "hbm_param_bytes_per_device": self._param_bytes_per_device(),
        }

    def _param_bytes_per_device(self) -> int:
        """Actual per-device parameter residency: sums each leaf's
        addressable shard size on device 0 (exact, not estimated)."""
        total = 0
        for p in jax.tree_util.tree_leaves(self.params):
            if hasattr(p, "sharding") and hasattr(p, "addressable_shards"):
                shard = p.addressable_shards[0]
                total += int(np.prod(shard.data.shape)) * p.dtype.itemsize
            else:
                total += int(np.prod(p.shape)) * p.dtype.itemsize
        return total


# the kinds subclass the engine above: bound last, and by module, so that
# either module may be imported first
from . import kinds as _kinds  # noqa: E402
